"""Operations and bytes that the model and its attention kernels need,
computed from shapes. The roofline and utilization readers divide these
by device time from the trace; nothing here reads a clock.

``m`` is an architecture's ``Dims`` (``arch/<arch>.py``); ``page`` the KV
page size in tokens; bytes are of the served dtype (2 for bfloat16).
"""
from __future__ import annotations

import math


def token_flops(m, ctx: int) -> float:
    """Model FLOPs of one token computed at a context of ``ctx`` positions
    (itself included): 2 per matmul weight, the head included, plus
    QK^T and PV over the context in every layer."""
    return 2.0 * m.matmul_params() + 4.0 * m.L * m.H * m.D * ctx


def chunk_flops(m, pos: int, n: int) -> float:
    """``token_flops`` summed over a prefill chunk of n tokens that starts
    at position ``pos``."""
    ctx_sum = n * pos + n * (n + 1) / 2
    return 2.0 * m.matmul_params() * n + 4.0 * m.L * m.H * m.D * ctx_sum


def kv_page_bytes(m, page: int, itemsize: int = 2) -> int:
    """K and V of one page, one layer."""
    return 2 * page * m.KH * m.D * itemsize


def decode_attn_bytes(m, ctx0: int, steps: int, page: int,
                      itemsize: int = 2) -> float:
    """Bytes the fused decode-tail kernel needs for one sequence over
    ``steps`` decode steps of one call, all layers: at step j the committed
    pages that cover ``ctx0`` positions, the j + 1 tail rows of K and V,
    the query and the output."""
    pages = math.ceil(ctx0 / page)
    per_step = pages * kv_page_bytes(m, page, itemsize) \
        + 2 * m.H * m.D * itemsize
    tail = 2 * m.KH * m.D * itemsize * steps * (steps + 1) / 2
    return m.L * (steps * per_step + tail)


def prefill_attn_flops(m, pos: int, n: int) -> float:
    """Causal attention of a prefill chunk of n tokens that starts at
    position ``pos``, all layers: QK^T and PV, 4 H D FLOPs a layer for
    each (query, key) pair a query sees, itself included."""
    return 4.0 * m.L * m.H * m.D * (n * pos + n * (n + 1) / 2)


def prefill_attn_bytes(m, pos: int, n: int, page: int,
                       itemsize: int = 2) -> float:
    """Bytes the paged flash-prefill kernel needs for that chunk, all
    layers: the K/V pages that cover its context, each read once, and its
    queries and output."""
    pages = math.ceil((pos + n) / page)
    return m.L * (pages * kv_page_bytes(m, page, itemsize)
                  + 2 * n * m.H * m.D * itemsize)
