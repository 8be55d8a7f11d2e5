"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid: (B*H, num_chunks) — chunks innermost so the inter-chunk SSM state
(P, N) persists in VMEM scratch. Each grid step computes the intra-chunk
quadratic term ((C B^T) ⊙ decay) @ x plus the inter-chunk contribution from
the carried state, then advances the state. Chunk size Q is a multiple of 128
so the (Q, Q) and (Q, N) tiles are MXU-aligned on the TPU target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_scr, *,
                chunk, num_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    a = a_ref[0].astype(jnp.float32)          # (Q, 1)
    B = b_ref[0].astype(jnp.float32)          # (Q, N)
    C = c_ref[0].astype(jnp.float32)          # (Q, N)

    a_cs = jnp.cumsum(a[:, 0])                # (Q,)
    # intra-chunk decay: L[i,j] = exp(a_cs[i]-a_cs[j]) for i>=j else 0
    seg = a_cs[:, None] - a_cs[None, :]
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(i >= j, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    y = jax.lax.dot_general(scores * L, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (Q,P)

    # inter-chunk contribution from carried state: exp(a_cs) * C @ state^T
    state = state_scr[...]                    # (P, N)
    y_off = jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (Q,P)
    y = y + y_off * jnp.exp(a_cs)[:, None]
    # every grid step is a live chunk (S % chunk == 0): the per-chunk output
    # store and state advance are unconditional by design, not dead steps
    y_ref[0] = y.astype(y_ref.dtype)  # firstlint: disable=pallas-kernel-safety -- grid has no dead steps; each ci writes its own block

    # state update: state' = exp(a_sum)*state + (x * exp(a_sum - a_cs))^T @ B
    a_sum = a_cs[-1]
    decay_in = jnp.exp(a_sum - a_cs)          # (Q,)
    xw = x * decay_in[:, None]                # (Q, P)
    upd = jax.lax.dot_general(xw, B, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)     # (P, N)
    state_scr[...] = state * jnp.exp(a_sum) + upd  # firstlint: disable=pallas-kernel-safety -- carried SSM state must advance on every chunk

    @pl.when(ci == num_chunks - 1)
    def _final():
        st_ref[0] = state_scr[...]


def ssd_fwd(x, a, B, C, *, chunk, interpret=False):
    """x: (BH, S, P); a: (BH, S, 1); B, C: (BH, S, N). S % chunk == 0.
    Returns (y (BH, S, P), final_state (BH, P, N))."""
    BH, S, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ci: (b, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, P, N), lambda b, ci: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, a, B, C)
