"""PagedAttention decode Pallas TPU kernel.

TPU adaptation of vLLM's PagedAttention (DESIGN.md §2): the per-sequence block
table lives in scalar-prefetch (SMEM) and *drives the DMA schedule* — the
BlockSpec index_map dereferences ``block_tables[b, pi]`` so each grid step
streams exactly one KV page HBM->VMEM. The pool is head-major,
``(NP, KH, page, D)``, so each streamed block is one ``(page, D)`` tile
whose last two dims meet the TPU's (sublane, 128-lane) tiling; an
online-softmax accumulator in VMEM scratch merges pages (flash-decoding
style).

Grid: (B, KH, pages_per_seq) — pages innermost for the accumulator carry.

GQA: the G = H // KH query heads sharing a KV head ride along the q tile's
sublane axis, so one page DMA serves all of them in a single (G, page)
MXU contraction. The ops wrapper pads G up to the dtype's sublane tile for
real-TPU lowering; the kernel itself is grouping-agnostic (G=1 MHA,
G=H MQA, anything between).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size, num_pages, scale):
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = lens_ref[b]
    page_start = pi * page_size
    live = page_start < ctx

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                   # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)                   # (page, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, page)
        pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < ctx, s, NEG_INF)
        m_prev = m_scr[...]                                   # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(pi == num_pages - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention_fwd(q, k_pages, v_pages, block_tables, context_lens, *,
                        interpret=False):
    """q: (B, KH, G, D); k_pages/v_pages: (NP, KH, page, D) head-major, so
    one (page, D) tile per grid step satisfies the TPU's (8, 128) tiling;
    block_tables: (B, PPS) int32; context_lens: (B,) int32.
    Returns (B, KH, G, D)."""
    B, KH, G, D = q.shape
    page = k_pages.shape[2]
    PPS = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_paged_kernel, page_size=page,
                               num_pages=PPS, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KH, PPS),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, pi, tables, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, pi, tables, lens: (tables[b, pi], h, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, pi, tables, lens: (tables[b, pi], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, pi, tables, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    # batch and kv-head grid axes are independent; the page axis carries
    # the online-softmax accumulator and must run in order
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, context_lens, q, k_pages, v_pages)


# -- fused decode: paged context + in-flight tail ----------------------------

def _decode_tail_kernel(tables_ref, clens_ref, tlens_ref, q_ref, k_ref, v_ref,
                        kt_ref, vt_ref, o_ref, m_scr, l_scr, acc_scr, *,
                        page_size, num_pages, scale):
    """One extra grid step past the pages attends the in-flight tail.

    The K-step fused decode loop keeps the tokens generated *this call* in
    small (B, KH, K, D) tail buffers instead of scattering them into the
    page pool every step.  Grid step ``pi == num_pages`` folds that tail
    into the same online-softmax accumulator the page steps built, so one
    kernel launch covers committed context + uncommitted tail.
    """
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _online_update(k, v, valid):
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    ctx = clens_ref[b]
    page_start = pi * page_size
    is_tail = pi == num_pages

    @pl.when(jnp.logical_and(pi < num_pages, page_start < ctx))
    def _pages():
        k = k_ref[0, 0].astype(jnp.float32)                   # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], page_size), 1)
        _online_update(k, v, pos < ctx)

    @pl.when(jnp.logical_and(is_tail, tlens_ref[b] > 0))
    def _tail():
        k = kt_ref[0, 0].astype(jnp.float32)                  # (Kt, D)
        v = vt_ref[0, 0].astype(jnp.float32)
        j = jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], kt_ref.shape[2]), 1)
        _online_update(k, v, j < tlens_ref[b])

    @pl.when(is_tail)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_decode_tail_fwd(q, k_pages, v_pages, block_tables, context_lens,
                          k_tail, v_tail, tail_lens, *, interpret=False):
    """q: (B, KH, G, D); k_pages/v_pages: (NP, KH, page, D);
    k_tail/v_tail: (B, KH, Kt, D) this call's in-flight tokens;
    block_tables: (B, PPS), context_lens / tail_lens: (B,), all int32.
    Returns (B, KH, G, D).  Position ``i`` attends committed context
    ``[0, context_lens[i])`` from the pages plus tail rows
    ``[0, tail_lens[i])`` — exactly contiguous positions
    ``[0, context_lens[i] + tail_lens[i])``."""
    B, KH, G, D = q.shape
    page = k_pages.shape[2]
    Kt = k_tail.shape[2]
    PPS = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_decode_tail_kernel, page_size=page,
                               num_pages=PPS, scale=scale)
    # grid step PPS is the tail step; its page index_map is clamped onto a
    # real page (the block is DMA'd but unread — only the tail refs are)
    last = PPS - 1

    def page_map(b, h, pi, tables, clens, tlens):
        return (tables[b, jnp.minimum(pi, last)], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KH, PPS + 1),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, pi, tables, clens, tlens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, D), page_map),
            pl.BlockSpec((1, 1, page, D), page_map),
            pl.BlockSpec((1, 1, Kt, D),
                         lambda b, h, pi, tables, clens, tlens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Kt, D),
                         lambda b, h, pi, tables, clens, tlens: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, D),
            lambda b, h, pi, tables, clens, tlens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, context_lens, tail_lens, q, k_pages, v_pages,
      k_tail, v_tail)
