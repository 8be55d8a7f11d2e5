"""Seeded random weights of a dense decoder (Qwen1.5 / Yi / Llama family),
made on the device from ``--seed``.

The weights are drawn in the published (Hugging Face) layout, one key per
layer, so that the reference can draw any one layer again by itself. The
program receives them in its own layout through ``program_params``, one
jitted call that draws every layer in the served dtype. The one change of
layout: the program's RoPE rotates interleaved pairs (2i, 2i+1), the
published models rotate the halves (i, i + head_dim/2). Permuting the
columns of wq, wk, bq and bk within each head maps one onto the other and
leaves every attention score unchanged; nothing else moves.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Dims:
    d: int          # hidden_size
    f: int          # intermediate_size
    L: int          # num_hidden_layers
    H: int          # num_attention_heads
    KH: int         # num_key_value_heads
    D: int          # head_dim
    V: int          # vocab_size
    bias: bool      # q/k/v projections carry a bias
    eps: float
    theta: float
    dtype: str

    @classmethod
    def of(cls, c: dict) -> "Dims":
        H = c["num_attention_heads"]
        return cls(d=c["hidden_size"], f=c["intermediate_size"],
                   L=c["num_hidden_layers"], H=H,
                   KH=c["num_key_value_heads"],
                   D=c.get("head_dim", c["hidden_size"] // H),
                   V=c["vocab_size"], bias=bool(c["attention_bias"]),
                   eps=float(c["rms_norm_eps"]),
                   theta=float(c["rope_theta"]), dtype=c["torch_dtype"])

    def matmul_params(self) -> int:
        """Weights of the matrix products of one token, the head included
        (embedding lookup, norms and biases excluded)."""
        attn = self.d * (self.H + 2 * self.KH) * self.D + self.H * self.D * self.d
        return self.L * (attn + 3 * self.d * self.f) + self.d * self.V


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


def _normal(key, shape, scale, dt):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)


def layer_weights(key, m: Dims) -> dict:
    """One layer in the published layout: x @ w for every projection."""
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 12)
    qd, kvd = m.H * m.D, m.KH * m.D
    w = {"norm1": (1.0 + 0.1 * jax.random.normal(ks[0], (m.d,))).astype(dt),
         "wq": _normal(ks[1], (m.d, qd), 1 / math.sqrt(m.d), dt),
         "wk": _normal(ks[2], (m.d, kvd), 1 / math.sqrt(m.d), dt),
         "wv": _normal(ks[3], (m.d, kvd), 1 / math.sqrt(m.d), dt),
         "wo": _normal(ks[4], (qd, m.d), 1 / math.sqrt(2 * m.L * qd), dt),
         "norm2": (1.0 + 0.1 * jax.random.normal(ks[5], (m.d,))).astype(dt),
         "w1": _normal(ks[6], (m.d, m.f), 1 / math.sqrt(m.d), dt),
         "w3": _normal(ks[7], (m.d, m.f), 1 / math.sqrt(m.d), dt),
         "w2": _normal(ks[8], (m.f, m.d), 1 / math.sqrt(2 * m.L * m.f), dt)}
    if m.bias:
        w["bq"] = _normal(ks[9], (qd,), 0.1, dt)
        w["bk"] = _normal(ks[10], (kvd,), 0.1, dt)
        w["bv"] = _normal(ks[11], (kvd,), 0.1, dt)
    return w


def embed_weights(words, m: Dims):
    return _normal(base_keys(words)[0], (m.V, m.d), 0.02, jnp.dtype(m.dtype))


def final_weights(words, m: Dims):
    """(final norm (d,), head (d, V))."""
    _, _, kn, kh = base_keys(words)
    dt = jnp.dtype(m.dtype)
    norm = (1.0 + 0.1 * jax.random.normal(kn, (m.d,))).astype(dt)
    return norm, _normal(kh, (m.d, m.V), 1 / math.sqrt(m.d), dt)


def rope_perm(D: int) -> np.ndarray:
    """Column order that takes a head from halves to interleaved pairs."""
    half = D // 2
    return np.stack([np.arange(half), np.arange(half) + half], -1).reshape(D)


def _to_program_layer(w: dict, m: Dims) -> dict:
    perm = rope_perm(m.D)

    def heads(x, n):                       # permute within each head
        return x.reshape(*x.shape[:-1], n, m.D)[..., perm].reshape(x.shape)

    attn = {"wq": heads(w["wq"], m.H), "wk": heads(w["wk"], m.KH),
            "wv": w["wv"], "wo": w["wo"]}
    if m.bias:
        attn.update(bq=heads(w["bq"], m.H), bk=heads(w["bk"], m.KH),
                    bv=w["bv"])
    return {"norm1": w["norm1"], "attn": attn, "norm2": w["norm2"],
            "mlp": {"w1": w["w1"], "w3": w["w3"], "w2": w["w2"]}}


@partial(jax.jit, static_argnums=(0,))
def _program_params(m: Dims, words):
    layers = jax.lax.map(lambda k: _to_program_layer(layer_weights(k, m), m),
                         layer_keys(words, m.L))
    norm, head = final_weights(words, m)
    return {"embed": embed_weights(words, m), "layers": layers,
            "final_norm": norm, "lm_head": head}


def program_params(m: Dims, seed: int):
    """Every weight of the model in the program's layout, in one call."""
    return _program_params(m, seed_words(seed))
