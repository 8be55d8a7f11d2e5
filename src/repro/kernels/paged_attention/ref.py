"""Pure-jnp oracle for paged decode attention: gather pages into a contiguous
cache, then masked softmax attention for a single query token. Also hosts the
paged *prefill* read path used by chunked prefill: a multi-token query block
attending over the page pool (cached prefix pages + the chunk's own pages)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gather_kv(pages, block_tables):
    """pages: (NP, KH, page, D) head-major pool; block_tables: (B, PPS)
    -> the contiguous seq-major view (B, PPS*page, KH, D)."""
    g = pages[block_tables]                   # (B, PPS, KH, page, D)
    B, PPS, KH, page, D = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, PPS * page, KH, D)


def paged_attention_ref(q, k_pages, v_pages, block_tables, context_lens):
    """q: (B, H, D); pages: (NP, KH, page, D); returns (B, H, D)."""
    B, H, D = q.shape
    KH = k_pages.shape[1]
    G = H // KH
    k = gather_kv(k_pages, block_tables)      # (B, S, KH, D)
    v = gather_kv(v_pages, block_tables)
    S = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    qr = q.reshape(B, KH, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qr, k.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, :] < context_lens[:, None]      # (B, S)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


def decode_tail_attention_ref(q, k_ctx, v_ctx, context_lens, k_tail, v_tail,
                              tail_lens):
    """Split decode attention: committed context view + in-flight tail.

    q: (B, H, D); k_ctx/v_ctx: (B, S, KH, D) a contiguous view of the
    committed pages (only ``[0, context_lens[b])`` valid); k_tail/v_tail:
    (B, KH, Kt, D) head-major tokens generated this fused call
    (``[0, tail_lens[b])`` valid). Scores for both segments are concatenated before ONE softmax,
    so the result equals attention over the contiguous positions
    ``[0, context_lens[b] + tail_lens[b])``. Returns (B, H, D).
    """
    B, H, D = q.shape
    KH = k_ctx.shape[2]
    G = H // KH
    S = k_ctx.shape[1]
    Kt = k_tail.shape[2]
    scale = 1.0 / math.sqrt(D)
    qr = q.reshape(B, KH, G, D).astype(jnp.float32)
    s_ctx = jnp.einsum("bhgd,bkhd->bhgk", qr,
                       k_ctx.astype(jnp.float32)) * scale
    s_tail = jnp.einsum("bhgd,bhkd->bhgk", qr,
                        k_tail.astype(jnp.float32)) * scale
    m_ctx = jnp.arange(S)[None, :] < context_lens[:, None]
    m_tail = jnp.arange(Kt)[None, :] < tail_lens[:, None]
    s = jnp.concatenate(
        [jnp.where(m_ctx[:, None, None, :], s_ctx, NEG_INF),
         jnp.where(m_tail[:, None, None, :], s_tail, NEG_INF)], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    out = (jnp.einsum("bhgk,bkhd->bhgd", p[..., :S],
                      v_ctx.astype(jnp.float32))
           + jnp.einsum("bhgk,bhkd->bhgd", p[..., S:],
                        v_tail.astype(jnp.float32)))
    return out.reshape(B, H, D).astype(q.dtype)


def fused_decode_attention_ref(q, k_pages, v_pages, block_tables,
                               context_lens, k_tail, v_tail, tail_lens):
    """Oracle for the fused decode-tail kernel: gather pages, then split
    attention. Same signature as ``ops.fused_decode_attention``."""
    k_ctx = gather_kv(k_pages, block_tables)
    v_ctx = gather_kv(v_pages, block_tables)
    return decode_tail_attention_ref(q, k_ctx, v_ctx, context_lens,
                                     k_tail, v_tail, tail_lens)


def paged_prefill_attention_ref(q, k_pages, v_pages, block_tables, q_offset,
                                kv_len):
    """Chunked-prefill attention over a paged KV cache.

    q: (B, C, H, D) — a chunk of C query tokens whose first token sits at
    absolute position ``q_offset``; the chunk's own KV must already be
    written into the pages. Gathers the sequence's pages into a contiguous
    view and runs causal flash-style attention with ``kv_len`` valid
    positions (cached prefix + this chunk). Returns (B, C, H, D).
    """
    from repro.models.layers import chunked_attention

    k = gather_kv(k_pages, block_tables)      # (B, S_ctx, KH, D)
    v = gather_kv(v_pages, block_tables)
    return chunked_attention(q, k, v, causal=True, q_offset=q_offset,
                             kv_len=kv_len)
