"""Public jit'd wrappers for the flash-attention Pallas kernels."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (flash_attention_fwd,
                                                  paged_flash_prefill_fwd)
from repro.kernels.paged_attention.ops import (_default_interpret, _pad_axis,
                                               _sublane)


@partial(jax.jit, static_argnames=("causal", "window", "q_block", "k_block",
                                   "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, q_block=256,
                    k_block=512, interpret=False):
    """q: (B, Sq, H, D); k, v: (B, Sk, KH, D) with H % KH == 0.
    Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    G = H // KH
    q_block = min(q_block, max(16, Sq))
    k_block = min(k_block, max(16, Sk))

    pq = (-Sq) % q_block
    pk = (-Sk) % k_block
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v

    # (B, S, H, D) -> (B*KH, G, S, D) / (B*KH, S, D)
    qr = qp.reshape(B, Sq + pq, KH, G, D).transpose(0, 2, 3, 1, 4)
    qr = qr.reshape(B * KH, G, Sq + pq, D)
    kr = kp.transpose(0, 2, 1, 3).reshape(B * KH, Sk + pk, D)
    vr = vp.transpose(0, 2, 1, 3).reshape(B * KH, Sk + pk, D)

    out = flash_attention_fwd(qr, kr, vr, causal=causal, window=window,
                              q_block=q_block, k_block=k_block, seq_k=Sk,
                              interpret=interpret)
    out = out.reshape(B, KH, G, Sq + pq, D).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, Sq + pq, H, D)[:, :Sq]


@partial(jax.jit, static_argnames=("group", "q_block", "interpret"))
def _paged_prefill_rows(qr, k_pages, v_pages, block_tables, kv_lens,
                        q_starts, *, group, q_block, interpret):
    qp = _pad_axis(qr, 2, q_block)
    out = paged_flash_prefill_fwd(qp, k_pages, v_pages,
                                  block_tables.astype(jnp.int32),
                                  kv_lens.astype(jnp.int32),
                                  q_starts.astype(jnp.int32),
                                  group=group, q_block=q_block,
                                  interpret=interpret)
    return out[:, :, :qr.shape[2]]


def paged_flash_prefill(q, k_pages, v_pages, block_tables, q_offset, kv_len,
                        *, interpret=None):
    """Chunked-prefill flash attention reading the paged pool directly.

    Drop-in for ``paged_prefill_attention_ref``: q is (B, C, H, D), a chunk
    whose first token sits at absolute position ``q_offset`` and whose own
    KV is already written into the pages; ``kv_len`` counts the valid
    positions (cached prefix + this chunk). No (B, S, KH, D) gather is
    materialized — the kernel streams pages straight from the pool.
    ``q_offset`` / ``kv_len`` may be scalars or (B,). Returns (B, C, H, D).
    """
    if interpret is None:
        interpret = _default_interpret()
    B, C, H, D = q.shape
    KH = k_pages.shape[1]
    assert H % KH == 0, \
        f"query heads ({H}) must be a multiple of kv heads ({KH})"
    G = H // KH
    # fold (token, group) into query rows: r = c * G + g
    qr = q.reshape(B, C, KH, G, D).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(B, KH, C * G, D)
    sub = _sublane(q.dtype)
    q_block = min(128, -(-C * G // sub) * sub)
    starts = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    out = _paged_prefill_rows(qr, k_pages, v_pages, block_tables, lens,
                              starts, group=G, q_block=q_block,
                              interpret=interpret)
    out = out.reshape(B, KH, C, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, C, H, D)
