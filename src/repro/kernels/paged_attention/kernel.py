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
The fused decode-tail kernel below takes a block of kv heads a step instead
(grid (B, KH // hb, pages_per_seq + 1)), so one page of the block's heads
is one contiguous DMA, and names no page past a slot's context.

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

# VMEM the decode-tail kernel's blocks and f32 temporaries may take, well
# inside v5e's 16 MiB default scoped limit
TAIL_VMEM_BUDGET = 8 << 20


def _lane_padded(rows, cols, itemsize):
    """VMEM bytes of one (rows, cols) tile, its lane axis padded to 128."""
    return rows * -(-cols // 128) * 128 * itemsize


def decode_tail_heads_per_block(kv_heads, group, page, head_dim, tail,
                                itemsize):
    """Kv heads the decode-tail kernel streams per grid step: the largest
    divisor of ``kv_heads`` whose VMEM fits ``TAIL_VMEM_BUDGET``. Per head: the K
    and V page, the K and V tail, q and the output, each double-buffered;
    the f32 softmax scratch; and the f32 copies of a page's K and V and
    its scores that the update makes. ``group`` and ``tail`` are the
    padded sizes the kernel sees."""
    per_head = (2 * (2 * page + 2 * tail + 2 * group) * head_dim * itemsize
                + 2 * _lane_padded(group, 1, 4)
                + _lane_padded(group, head_dim, 4)
                + 2 * page * head_dim * 4 + 2 * _lane_padded(group, page, 4))
    return max(h for h in range(1, kv_heads + 1)
               if kv_heads % h == 0
               and (h == 1 or h * per_head <= TAIL_VMEM_BUDGET))


def live_page(tables, clens, b, pi, *, page_size):
    """Page that grid step ``(b, pi)`` streams: ``tables[b, pi]`` while
    page ``pi`` holds context, else the slot's last live page (its first,
    with no context). Dead steps and the tail step so name the block the
    step before them named, and the pipeline copies nothing for them."""
    last = jnp.clip(pl.cdiv(clens[b], page_size) - 1, 0, tables.shape[1] - 1)
    return tables[b, jnp.minimum(pi, last)]


def _decode_tail_kernel(tables_ref, clens_ref, tlens_ref, q_ref, k_ref, v_ref,
                        kt_ref, vt_ref, o_ref, m_scr, l_scr, acc_scr, *,
                        page_size, num_pages, scale):
    """One extra grid step past the pages attends the in-flight tail.

    The K-step fused decode loop keeps the tokens generated *this call* in
    small (B, KH, K, D) tail buffers instead of scattering them into the
    page pool every step.  Grid step ``pi == num_pages`` folds that tail
    into the same online-softmax accumulator the page steps built, so one
    kernel launch covers committed context + uncommitted tail.  A step
    holds a block of kv heads; the update runs batched over them.
    """
    b = pl.program_id(0)
    pi = pl.program_id(2)
    heads, group = q_ref.shape[1], q_ref.shape[2]

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _online_update(k, v, valid):
        q = q_ref[0].astype(jnp.float32) * scale              # (hb, G, D)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)                      # (hb, G, rows)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    ctx = clens_ref[b]
    page_start = pi * page_size
    is_tail = pi == num_pages

    @pl.when(jnp.logical_and(pi < num_pages, page_start < ctx))
    def _pages():
        k = k_ref[0].astype(jnp.float32)                      # (hb, page, D)
        v = v_ref[0].astype(jnp.float32)
        pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (heads, group, page_size), 2)
        _online_update(k, v, pos < ctx)

    @pl.when(jnp.logical_and(is_tail, tlens_ref[b] > 0))
    def _tail():
        k = kt_ref[0].astype(jnp.float32)                     # (hb, Kt, D)
        v = vt_ref[0].astype(jnp.float32)
        j = jax.lax.broadcasted_iota(
            jnp.int32, (heads, group, kt_ref.shape[2]), 2)
        _online_update(k, v, j < tlens_ref[b])

    @pl.when(is_tail)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_decode_tail_fwd(q, k_pages, v_pages, block_tables, context_lens,
                          k_tail, v_tail, tail_lens, *, interpret=False):
    """q: (B, KH, G, D); k_pages/v_pages: (NP, KH, page, D);
    k_tail/v_tail: (B, KH, Kt, D) this call's in-flight tokens;
    block_tables: (B, PPS), context_lens / tail_lens: (B,), all int32.
    Returns (B, KH, G, D).  Position ``i`` attends committed context
    ``[0, context_lens[i])`` from the pages plus tail rows
    ``[0, tail_lens[i])`` — exactly contiguous positions
    ``[0, context_lens[i] + tail_lens[i])``.

    Grid: (B, KH // hb, PPS + 1), ``hb`` kv heads a step
    (``decode_tail_heads_per_block``): the pool is head-major, so one page
    of ``hb`` heads is one contiguous DMA.  Steps past a slot's context, and the tail step, name its last
    live page (``live_page``)."""
    B, KH, G, D = q.shape
    page = k_pages.shape[2]
    Kt = k_tail.shape[2]
    PPS = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    hb = decode_tail_heads_per_block(KH, G, page, D, Kt,
                                     k_pages.dtype.itemsize)

    kernel = functools.partial(_decode_tail_kernel, page_size=page,
                               num_pages=PPS, scale=scale)

    def page_map(b, h, pi, tables, clens, tlens):
        return (live_page(tables, clens, b, pi, page_size=page), h, 0, 0)

    def slot_map(b, h, pi, tables, clens, tlens):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KH // hb, PPS + 1),
        in_specs=[
            pl.BlockSpec((1, hb, G, D), slot_map),
            pl.BlockSpec((1, hb, page, D), page_map),
            pl.BlockSpec((1, hb, page, D), page_map),
            pl.BlockSpec((1, hb, Kt, D), slot_map),
            pl.BlockSpec((1, hb, Kt, D), slot_map),
        ],
        out_specs=pl.BlockSpec((1, hb, G, D), slot_map),
        scratch_shapes=[
            pltpu.VMEM((hb, G, 1), jnp.float32),
            pltpu.VMEM((hb, G, 1), jnp.float32),
            pltpu.VMEM((hb, G, D), jnp.float32),
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
