"""Share (%) of its roofline that the fused decode-tail attention kernel
reaches in the traced window. Least time: the bytes it needs (the K/V
pages each live sequence's context covers, the in-flight tail, q and the
output; ``costs.decode_attn_bytes``) over the chip's HBM bandwidth, each
call counted by the share of it that lies inside the trace. Kernel time:
the kernel's events in the device trace, clipped to the trace, named
after the Pallas wrapper (``_fused_decode_grouped``). Memory bound by
construction: one query row per kv head group against the context."""
import costs
import trace_reduce

KERNEL = r"^_fused_decode"


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    nbytes = sum(rec.trace_share(t0, t1) * sum(
        costs.decode_attn_bytes(rec.dims, ctx0, p, rec.page)
        for ctx0, p in live if p) for t0, t1, _, _, live in rec.decode_calls)
    t = trace_reduce.op_seconds(rec.trace, KERNEL)
    if not nbytes or t <= 0:
        return None
    return 100.0 * nbytes / rec.peaks["hbm_bytes_per_s"] / t
