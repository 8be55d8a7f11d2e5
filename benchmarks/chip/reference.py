"""The comparison of served tokens with a plain reference in float32, and
its int8 control.

The reference of a configuration is its architecture's
(``arch/<arch>.py``): its per-layer keys and weights, its layer, embedding
and head. Every product runs in float32 at ``HIGHEST`` precision. It
imports nothing of the program and takes nothing that the program made:
it draws each layer's weights again from the seed, one layer at a time,
and runs the sampled sequences through it before it draws the next, so
that it fits on the chip beside nothing else.

The control (``quant=True``) is the same forward with every matrix
product in int8 (``mm``): weights scaled per output column, activations
per row, the products exact, the scales applied after. It stands for the
step down in precision that would tempt a later change, and has to read as
not correct.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import weights as W

HI = lax.Precision.HIGHEST
f32 = jnp.float32


def mm(x, w, quant):
    """x @ w in float32 at HIGHEST, or in int8 for the control."""
    w = w.astype(f32)
    if not quant:
        return jnp.matmul(x, w, precision=HI)
    sw = jnp.maximum(jnp.abs(w).max(axis=0), 1e-30) / 127.0
    sx = jnp.maximum(jnp.abs(x).max(axis=-1, keepdims=True), 1e-30) / 127.0
    return jnp.matmul(jnp.round(x / sx), jnp.round(w / sw),
                      precision=HI) * sx * sw


@jax.jit
def _gaps(ref, served, ctrl):
    """Per position: how far the served token's logit, and the control's
    first choice, lie below the reference's best."""
    top = ref.max(-1)
    pick = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    cpick = jnp.take_along_axis(ref, ctrl.argmax(-1)[:, None], -1)[:, 0]
    return top - pick, ref.argmax(-1) == served, top - cpick


ROWS = 256           # positions per call of the head


def _bucket(n: int) -> int:
    b = 512
    while b < n:
        b *= 2
    return b


def compare(arch, m, seed: int, sample: list, control: bool) -> dict:
    """Teacher-forced comparison of served tokens with the reference of
    ``arch`` (an ``arch/<arch>.py`` module) at sizes ``m``.

    ``sample``: (prompt, served tokens) pairs. For each served token,
    the gap by which its logit lies below the reference's best at that
    position; with ``control``, the same gap for the token that the int8
    forward puts first. Returns the widest and the mean gaps and the share
    of served tokens that are the reference's first choice."""
    words = W.seed_words(seed)
    seqs, rows, served = [], [], []
    for prompt, out in sample:
        toks = list(prompt) + list(out[:-1])
        S = _bucket(len(toks))
        seqs.append(np.pad(np.asarray(toks, np.int32), (0, S - len(toks))))
        rows.append(np.arange(len(prompt) - 1, len(toks)))
        served.append(np.asarray(out, np.int32))
    streams = [False, True] if control else [False]
    hs = {q: [arch.embed(words, jnp.asarray(t), m=m) for t in seqs]
          for q in streams}
    keys = arch.layer_keys(words, m)
    for li in range(m.L):
        w = arch.layer_weights(keys[li], m=m)
        for q in streams:
            hs[q] = [arch.layer(h, w, m=m, quant=q) for h in hs[q]]
        del w
    gap, match, cgap = [], [], []
    for i in range(len(seqs)):
        for j in range(0, len(rows[i]), ROWS):
            r = rows[i][j:j + ROWS]
            n = len(r)
            r = np.pad(r, (0, ROWS - n))          # one shape for every block
            t = np.pad(served[i][j:j + ROWS], (0, ROWS - n))
            ref = arch.head(words, hs[False][i][r], m=m, quant=False)
            ctrl = arch.head(words, hs[True][i][r], m=m, quant=True) \
                if control else ref
            g, a, c = _gaps(ref, jnp.asarray(t), ctrl)
            gap.append(np.asarray(g)[:n])
            match.append(np.asarray(a)[:n])
            cgap.append(np.asarray(c)[:n])
    gap, match, cgap = (np.concatenate(x) for x in (gap, match, cgap))
    return {"logit_gap": float(gap.max()), "mean_gap": float(gap.mean()),
            "argmax_match": float(match.mean()),
            "served_tokens": int(len(gap)),
            "control_gap": float(cgap.max()) if control else None,
            "control_mean_gap": float(cgap.mean()) if control else None}
