"""A dense decoder (Qwen1.5 / Yi / Llama family): its sizes, the program's
configuration and weights, and its plain reference.

Weights: seeded random, made on the device from ``--seed``. They are drawn
in the published (Hugging Face) layout, one key per layer, so that the
reference can draw any one layer again by itself. The program receives
them in its own layout through ``program_params``, one jitted call that
draws every layer in the served dtype. The one change of layout: the
program's RoPE rotates interleaved pairs (2i, 2i+1), the published models
rotate the halves (i, i + head_dim/2). Permuting the columns of wq, wk, bq
and bk within each head maps one onto the other and leaves every attention
score unchanged; nothing else moves.

Reference: the published decoder in float32: RMSNorm, q/k/v projections
(with a bias where the configuration has one), RoPE on the halves of each
head, causal softmax attention with grouped kv heads, a SwiGLU MLP, a
final RMSNorm and an untied head. Every product runs at ``HIGHEST``
precision, or in int8 for the control (``reference.mm``). It imports
nothing of the program and takes nothing that the program made: it draws
each layer's weights again from the seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import weights as W
from reference import HI, f32, mm as _mm


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


def model_config(c: dict, m: Dims):
    """The program's configuration of this model."""
    from repro.configs.base import ModelConfig
    return ModelConfig(name=c["name"], family="dense", num_layers=m.L,
                       d_model=m.d, num_heads=m.H, num_kv_heads=m.KH,
                       head_dim=m.D, d_ff=m.f, vocab_size=m.V,
                       qkv_bias=m.bias, rope_theta=m.theta, norm_eps=m.eps,
                       param_dtype=m.dtype,
                       tie_embeddings=bool(c["tie_word_embeddings"]))


# -- weights -------------------------------------------------------------------

def _normal(key, shape, scale, dt):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)


def _layer_weights(key, m: Dims) -> dict:
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
    return _normal(W.base_keys(words)[0], (m.V, m.d), 0.02,
                   jnp.dtype(m.dtype))


def final_weights(words, m: Dims):
    """(final norm (d,), head (d, V))."""
    _, _, kn, kh = W.base_keys(words)
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
    layers = jax.lax.map(lambda k: _to_program_layer(_layer_weights(k, m), m),
                         W.layer_keys(words, m.L))
    norm, head = final_weights(words, m)
    return {"embed": embed_weights(words, m), "layers": layers,
            "final_norm": norm, "lm_head": head}


def program_params(m: Dims, seed: int):
    """Every weight of the model in the program's layout, in one call."""
    return _program_params(m, W.seed_words(seed))


# -- reference -----------------------------------------------------------------

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
def layer(h, w, *, m: Dims, quant: bool):
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


def layer_keys(words, m: Dims):
    """One key per layer, from which ``layer_weights`` draws it."""
    return W.layer_keys(words, m.L)


@partial(jax.jit, static_argnames=("m",))
def layer_weights(key, *, m):
    return _layer_weights(key, m)


@partial(jax.jit, static_argnames=("m",))
def embed(words, toks, *, m):
    return jnp.take(embed_weights(words, m), toks, axis=0).astype(f32)


@partial(jax.jit, static_argnames=("m", "quant"))
def head(words, h, *, m, quant):
    norm, lm_head = final_weights(words, m)
    return _mm(_rms(h, norm, m.eps), lm_head, quant)
