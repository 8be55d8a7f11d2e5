"""Public wrappers for the paged-attention decode kernels.

GQA handling lives here: the kernel grids iterate (batch, kv head or block
of kv heads, page) and expect the query tensor grouped as (B, KH, G, D)
with G = H // KH query heads sharing each KV head. Real-TPU lowering
requires the (G, D) query tile's sublane axis to be a multiple of the
dtype's min tile (8 for f32, 16 for bf16), which odd groupings (e.g.
yi's 56q/8kv -> G=7) and small groups (G < 8) violate — so the wrapper
pads the group axis up to the sublane tile, lets the padded rows compute
garbage against the same pages, and slices them off. MQA (KH=1) and MHA
(G=1) are just the endpoints of the same path. The fused-decode wrapper
pads the in-flight tail the same way along its token axis.

``interpret`` resolution: ``interpret`` is a static argument of the inner
jitted functions, so its value must be stable across calls — a per-call
``jax.default_backend()`` probe could flip (e.g. a test harness forcing a
platform mid-process) and silently retrace every kernel mid-serve. The
platform is therefore resolved ONCE, at first use, and cached in
``_BACKEND_INTERPRET``: compiled kernels on ``tpu``, the interpreter on
``cpu`` (tests), and an error on any other platform — a device that can
neither compile the kernels nor be mistaken for a test host is never
quietly run through the interpreter. ``kernels_compiled()`` exposes the
same answer to the serving layer for dispatch decisions.

Layouts: the page pools are head-major, ``(NP, KH, page, D)``, and so are
the fused loop's in-flight tails, ``(B, KH, Kt, D)`` — every block the
kernels stream is one ``(rows, D)`` tile.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attention.kernel import (paged_attention_fwd,
                                                  paged_decode_tail_fwd)

_BACKEND_INTERPRET: bool | None = None


def _default_interpret() -> bool:
    """Resolve (once) whether Pallas runs interpreted on this platform:
    compiled on ``tpu``, interpreted on ``cpu``, an error elsewhere."""
    global _BACKEND_INTERPRET
    if _BACKEND_INTERPRET is None:
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise RuntimeError(
                f"Pallas kernels compile for 'tpu' and run interpreted on "
                f"'cpu'; platform {platform!r} supports neither")
        _BACKEND_INTERPRET = platform == "cpu"
    return _BACKEND_INTERPRET


def kernels_compiled() -> bool:
    """True when compiled Pallas lowering is available (TPU backend)."""
    return not _default_interpret()


def _sublane(dtype) -> int:
    return 16 if dtype == jnp.bfloat16 else 8


def _group(q, KH):
    B, H, D = q.shape
    assert H % KH == 0, \
        f"query heads ({H}) must be a multiple of kv heads ({KH})"
    return q.reshape(B, KH, H // KH, D)


def _pad_axis(x, axis, mult):
    n = x.shape[axis]
    np_ = -(-n // mult) * mult
    if np_ == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, np_ - n)
    return jnp.pad(x, pad)


@partial(jax.jit, static_argnames=("interpret",))
def _paged_attention_grouped(qr, k_pages, v_pages, block_tables,
                             context_lens, *, interpret):
    """qr: (B, KH, G, D) grouped queries. Returns (B, KH, G, D)."""
    G = qr.shape[2]
    qp = _pad_axis(qr, 2, _sublane(qr.dtype))
    out = paged_attention_fwd(qp, k_pages, v_pages,
                              block_tables.astype(jnp.int32),
                              context_lens.astype(jnp.int32),
                              interpret=interpret)
    return out[:, :, :G]


def paged_attention(q, k_pages, v_pages, block_tables, context_lens, *,
                    interpret=None):
    """Decode attention over a paged KV cache.

    q: (B, H, D) one query token per sequence;
    k_pages / v_pages: (NP, KH, page_size, D) the global page pool;
    block_tables: (B, pages_per_seq) int32 page ids (pad with 0 beyond len);
    context_lens: (B,) int32 valid token counts.
    ``interpret=None`` auto-selects once per process: compiled Pallas on
    TPU, the interpreter elsewhere (CPU tests / parity checks).
    Returns (B, H, D).
    """
    if interpret is None:
        interpret = _default_interpret()
    B, H, D = q.shape
    out = _paged_attention_grouped(_group(q, k_pages.shape[1]), k_pages,
                                   v_pages, block_tables, context_lens,
                                   interpret=interpret)
    return out.reshape(B, H, D)


@partial(jax.jit, static_argnames=("interpret",))
def _fused_decode_grouped(qr, k_pages, v_pages, block_tables, context_lens,
                          k_tail, v_tail, tail_lens, *, interpret):
    G = qr.shape[2]
    qp = _pad_axis(qr, 2, _sublane(qr.dtype))
    # tail rides the kernel's sublane axis too: pad the token axis and let
    # tail_lens mask the padded rows
    kt = _pad_axis(k_tail, 2, _sublane(k_tail.dtype))
    vt = _pad_axis(v_tail, 2, _sublane(v_tail.dtype))
    out = paged_decode_tail_fwd(qp, k_pages, v_pages,
                                block_tables.astype(jnp.int32),
                                context_lens.astype(jnp.int32),
                                kt, vt, tail_lens.astype(jnp.int32),
                                interpret=interpret)
    return out[:, :, :G]


def fused_decode_attention(q, k_pages, v_pages, block_tables, context_lens,
                           k_tail, v_tail, tail_lens, *, interpret=None):
    """Decode attention over committed pages + an in-flight tail buffer.

    The K-step fused decode loop accumulates this call's freshly generated
    KV in (B, KH, K, D) tail buffers and defers the page-pool scatter to
    the end of the call; position ``b`` attends pages ``[0, context_lens[b])``
    plus tail rows ``[0, tail_lens[b])``.  Shapes as ``paged_attention``
    plus k_tail/v_tail: (B, KH, Kt, D) and tail_lens: (B,).
    Returns (B, H, D).
    """
    if interpret is None:
        interpret = _default_interpret()
    B, H, D = q.shape
    out = _fused_decode_grouped(_group(q, k_pages.shape[1]), k_pages,
                                v_pages, block_tables, context_lens,
                                k_tail, v_tail, tail_lens,
                                interpret=interpret)
    return out.reshape(B, H, D)


# -- shard_map variants ------------------------------------------------------
# GSPMD cannot partition a Pallas kernel body, so under a mesh the kernel
# runs per-shard via shard_map over the kv-head axis: queries (grouped) and
# the page pools both split on KH, block tables / lengths are replicated,
# and no collective is needed — each kv head's attention is independent.
# Requires KH % mesh.shape[axis] == 0 (the caller falls back to the jnp
# reference otherwise).


def shardable_kv_heads(num_kv_heads: int, mesh, axis: str = "model") -> bool:
    return mesh is not None and num_kv_heads % mesh.shape[axis] == 0


def paged_attention_sharded(q, k_pages, v_pages, block_tables, context_lens,
                            *, mesh, axis: str = "model", interpret=None):
    """``paged_attention`` under a mesh: per-shard kernels over kv heads."""
    if interpret is None:
        interpret = _default_interpret()
    B, H, D = q.shape
    qr = _group(q, k_pages.shape[1])
    heads = P(None, axis, None, None)
    fn = shard_map(
        partial(_paged_attention_grouped, interpret=interpret),
        mesh=mesh,
        in_specs=(heads, heads, heads, P(None, None), P(None)),
        out_specs=heads,
        check_vma=False,
    )
    out = fn(qr, k_pages, v_pages, block_tables.astype(jnp.int32),
             context_lens.astype(jnp.int32))
    return out.reshape(B, H, D)


def fused_decode_attention_sharded(q, k_pages, v_pages, block_tables,
                                   context_lens, k_tail, v_tail, tail_lens,
                                   *, mesh, axis: str = "model",
                                   interpret=None):
    """``fused_decode_attention`` under a mesh (tails split on KH too)."""
    if interpret is None:
        interpret = _default_interpret()
    B, H, D = q.shape
    qr = _group(q, k_pages.shape[1])
    heads = P(None, axis, None, None)
    fn = shard_map(
        partial(_fused_decode_grouped, interpret=interpret),
        mesh=mesh,
        in_specs=(heads, heads, heads, P(None, None), P(None), heads, heads,
                  P(None)),
        out_specs=heads,
        check_vma=False,
    )
    out = fn(qr, k_pages, v_pages, block_tables.astype(jnp.int32),
             context_lens.astype(jnp.int32), k_tail, v_tail,
             tail_lens.astype(jnp.int32))
    return out.reshape(B, H, D)
