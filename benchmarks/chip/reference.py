"""Plain reference of a dense decoder in float32, and its int8 control.

It follows the published Qwen1.5 / Yi / Llama decoder: RMSNorm, q/k/v
projections (with a bias where the configuration has one), RoPE on the
halves of each head, causal softmax attention with grouped kv heads,
a SwiGLU MLP, a final RMSNorm and an untied head. Every product runs in
float32 at ``HIGHEST`` precision. It imports nothing of the program and
takes nothing that the program made: it draws each layer's weights again
from the seed (``weights.py``), one layer at a time, and runs the sampled
sequences through it before it draws the next, so that it fits on the
chip beside nothing else.

The control (``quant=True``) is the same forward with every matrix
product in int8: weights scaled per output column, activations per row,
the products exact, the scales applied after. It stands for the step down
in precision that would tempt a later change, and has to read as not
correct.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import weights as W

HI = lax.Precision.HIGHEST
f32 = jnp.float32


def _mm(x, w, quant):
    w = w.astype(f32)
    if not quant:
        return jnp.matmul(x, w, precision=HI)
    sw = jnp.maximum(jnp.abs(w).max(axis=0), 1e-30) / 127.0
    sx = jnp.maximum(jnp.abs(x).max(axis=-1, keepdims=True), 1e-30) / 127.0
    return jnp.matmul(jnp.round(x / sx), jnp.round(w / sw),
                      precision=HI) * sx * sw


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(f32)


def _rope(x, theta):
    """x: (S, n, D), rotating the halves (i, i + D/2) of each head."""
    S, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(S, dtype=f32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _attend(q, k, v, qb):
    """Causal attention, one block of qb queries at a time."""
    S, H, D = q.shape
    KH = k.shape[1]
    qg = q.reshape(S, KH, H // KH, D)

    def block(i):
        qs = lax.dynamic_slice_in_dim(qg, i * qb, qb, 0)
        s = jnp.einsum("qkgd,skd->kgqs", qs, k, precision=HI) / math.sqrt(D)
        ok = jnp.arange(S)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v,
                          precision=HI).reshape(qb, H * D)

    return lax.map(block, jnp.arange(S // qb)).reshape(S, H * D)


@partial(jax.jit, static_argnames=("m", "quant"))
def layer(h, w, *, m: W.Dims, quant: bool):
    """One decoder layer over a whole sequence, h: (S, d) float32."""
    S = h.shape[0]
    x = _rms(h, w["norm1"], m.eps)
    q, k, v = (_mm(x, w[n], quant) for n in ("wq", "wk", "wv"))
    if m.bias:
        q, k, v = (t + w[b].astype(f32)
                   for t, b in ((q, "bq"), (k, "bk"), (v, "bv")))
    q = _rope(q.reshape(S, m.H, m.D), m.theta)
    k = _rope(k.reshape(S, m.KH, m.D), m.theta)
    qb = max(16, min(512, 1 << int(math.log2(max(1, 2 ** 28 // (m.H * S))))))
    a = _attend(q, k, v.reshape(S, m.KH, m.D), min(qb, S))
    h = h + _mm(a, w["wo"], quant)
    x = _rms(h, w["norm2"], m.eps)
    rows = min(S, 2048)

    def mlp(xb):
        g = _mm(xb, w["w1"], quant)
        return _mm(jax.nn.silu(g) * _mm(xb, w["w3"], quant), w["w2"], quant)

    f = lax.map(mlp, x.reshape(S // rows, rows, m.d)).reshape(S, m.d)
    return h + f


@partial(jax.jit, static_argnames=("m",))
def _layer_weights(key, *, m):
    return W.layer_weights(key, m)


@partial(jax.jit, static_argnames=("m",))
def _embed(words, toks, *, m):
    return jnp.take(W.embed_weights(words, m), toks, axis=0).astype(f32)


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(words, h, *, m, quant):
    norm, head = W.final_weights(words, m)
    return _mm(_rms(h, norm, m.eps), head, quant)


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


def compare(m: W.Dims, seed: int, sample: list, control: bool) -> dict:
    """Teacher-forced comparison of served tokens with the reference.

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
    hs = {q: [_embed(words, jnp.asarray(t), m=m) for t in seqs]
          for q in streams}
    keys = W.layer_keys(words, m.L)
    for li in range(m.L):
        w = _layer_weights(keys[li], m=m)
        for q in streams:
            hs[q] = [layer(h, w, m=m, quant=q) for h in hs[q]]
        del w
    gap, match, cgap = [], [], []
    for i in range(len(seqs)):
        for j in range(0, len(rows[i]), ROWS):
            r = rows[i][j:j + ROWS]
            n = len(r)
            r = np.pad(r, (0, ROWS - n))          # one shape for every block
            t = np.pad(served[i][j:j + ROWS], (0, ROWS - n))
            ref = _head(words, hs[False][i][r], m=m, quant=False)
            ctrl = _head(words, hs[True][i][r], m=m, quant=True) \
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
