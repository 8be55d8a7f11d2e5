"""FlashAttention forward Pallas TPU kernel (prefill / training attention).

Grid: (B*KH, G, num_q_blocks, num_k_blocks), k innermost so the online-softmax
accumulators (m, l, acc) persist in VMEM scratch across k-blocks. Fully-masked
causal blocks skip compute via ``pl.when``. Tiles are MXU-aligned (block sizes
are multiples of 128 on the contracting/lane dims for the TPU target).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, window, q_block, k_block, num_k_blocks,
                  seq_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * q_block
    k_start = ki * k_block
    # skip fully-masked blocks (strictly above the causal diagonal)
    live = jnp.bool_(True)
    if causal:
        live = k_start <= q_start + q_block - 1
    if window:
        live = jnp.logical_and(live, q_start - (k_start + k_block - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (qb, D)
        k = k_ref[0].astype(jnp.float32)                     # (kb, D)
        v = v_ref[0].astype(jnp.float32)                     # (kb, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, k_block), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, k_block), 1)
        mask = kpos < seq_k
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                  # (qb, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_prefill_kernel(tables_ref, lens_ref, starts_ref, q_ref, k_ref,
                          v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                          page_size, num_pages, q_block, group):
    """Causal flash attention whose KV stream is a paged pool.

    Query rows fold (chunk position, GQA group) as ``r = c * G + g`` so one
    q tile serves all G heads of each token; the row's absolute position is
    ``starts[b] + r // G``. The page axis is innermost: the block table in
    scalar prefetch drives the page DMA (as in the decode kernel) and the
    online-softmax accumulators carry across pages.
    """
    b = pl.program_id(0)
    qi = pl.program_id(2)
    pi = pl.program_id(3)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = lens_ref[b]
    start = starts_ref[b]
    page_start = pi * page_size
    row0 = qi * q_block
    # causal skip: the page is dead if it starts past this tile's last
    # query position (and past the valid kv prefix)
    max_qpos = start + (row0 + q_block - 1) // group
    live = jnp.logical_and(page_start < kv_len, page_start <= max_qpos)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (qb, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, page_size), 0)
        qpos = start + rows // group
        kpos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, page_size), 1)
        mask = jnp.logical_and(kpos < kv_len, kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
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


def paged_flash_prefill_fwd(q, k_pages, v_pages, block_tables, kv_lens,
                            q_starts, *, group, q_block, interpret=False):
    """q: (B, KH, R, D) with R = C * G query rows (row ``c*G+g`` is head
    group ``g`` of chunk token ``c``), padded to a q_block multiple;
    k_pages / v_pages: (NP, KH, page, D) head-major (the decode kernels'
    pool layout); block_tables: (B, PPS); kv_lens / q_starts: (B,) int32.
    Returns (B, KH, R, D)."""
    B, KH, R, D = q.shape
    page = k_pages.shape[2]
    PPS = block_tables.shape[1]
    assert R % q_block == 0
    nq = R // q_block
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_paged_prefill_kernel, scale=scale,
                               page_size=page, num_pages=PPS,
                               q_block=q_block, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KH, nq, PPS),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, D),
                         lambda b, h, qi, pi, t, kl, qs: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, qi, pi, t, kl, qs: (t[b, pi], h, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, qi, pi, t, kl, qs: (t[b, pi], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, D),
                               lambda b, h, qi, pi, t, kl, qs: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(block_tables, kv_lens, q_starts, q, k_pages, v_pages)


def flash_attention_fwd(q, k, v, *, causal=True, window=0, q_block=256,
                        k_block=512, seq_k=None, interpret=False):
    """q: (BKH, G, Sq, D); k, v: (BKH, Sk, D). Returns (BKH, G, Sq, D).

    Sq / Sk must already be padded to block multiples; ``seq_k`` is the true
    (unpadded) kv length used for masking.
    """
    BKH, G, Sq, D = q.shape
    _, Sk, _ = k.shape
    seq_k = seq_k or Sk
    assert Sq % q_block == 0 and Sk % k_block == 0
    nq, nk = Sq // q_block, Sk // k_block
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, k_block=k_block, num_k_blocks=nk, seq_k=seq_k)

    return pl.pallas_call(
        kernel,
        grid=(BKH, G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, D), lambda b, g, qi, ki: (b, g, qi, 0)),
            pl.BlockSpec((1, k_block, D), lambda b, g, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, k_block, D), lambda b, g, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, D),
                               lambda b, g, qi, ki: (b, g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BKH, G, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
