"""Share (%) of its roofline that the paged flash-prefill kernel reaches
in the traced window. Least time of each chunk: the larger of the causal
attention FLOPs it needs (``costs.prefill_attn_flops``) over the chip's
bf16 peak and the bytes it needs (``costs.prefill_attn_bytes``) over its
HBM bandwidth, each chunk counted by the share of its call that lies
inside the trace. Kernel time: the kernel's events in the device trace,
clipped to the trace, named after its jitted wrapper
(``_paged_prefill_rows``)."""
import costs
import trace_reduce

KERNEL = r"^_paged_prefill_rows"


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    m, pk = rec.dims, rec.peaks
    least = sum(rec.trace_share(t0, t1) * max(
        costs.prefill_attn_flops(m, pos, n) / pk["bf16_flops"],
        costs.prefill_attn_bytes(m, pos, n, rec.page)
        / pk["hbm_bytes_per_s"])
        for t0, t1, pos, n in rec.prefill_calls if n)
    t = trace_reduce.op_seconds(rec.trace, KERNEL)
    if not least or t <= 0:
        return None
    return 100.0 * least / t
