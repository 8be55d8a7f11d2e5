"""Seeds of the weights that every architecture shares (``arch/*.py``
draws its weights from them, on the device, from ``--seed``): a seed of
any size as two traced words, and one key for each of the embedding, the
layers, the final norm and the head, the layers' split one key per layer,
so that the reference can draw any one layer again by itself."""
from __future__ import annotations

import jax
import numpy as np


def seed_words(seed: int):
    """A seed of any size as two int32 words, so that it is traced and not
    compiled into the programs."""
    return np.int32(seed & 0x7FFFFFFF), np.int32((seed >> 31) & 0x7FFFFFFF)


def base_keys(words):
    """(embed, layers, final norm, head) keys of a seed."""
    lo, hi = words
    return jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(lo), hi), 4)


def layer_keys(words, L: int):
    return jax.random.split(base_keys(words)[1], L)
