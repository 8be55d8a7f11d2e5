"""Model FLOPs utilization (%) of the whole step in the traced window:
the FLOPs of every token computed there (prompt tokens the cache did not
hold, and output tokens; ``costs.token_flops`` at each token's context)
over window x chips x peak bf16 FLOP/s. A call that straddles an edge of
the trace counts by the share of it that lies inside."""
import costs


def read(rec):
    if rec.trace is None or rec.peaks is None or rec.trace.window_ns <= 0:
        return None
    m = rec.dims
    flops = sum(rec.trace_share(t0, t1) * costs.chunk_flops(m, pos, n)
                for t0, t1, pos, n in rec.prefill_calls if n)
    for t0, t1, _, _, live in rec.decode_calls:
        share = rec.trace_share(t0, t1)
        if share:
            flops += share * sum(costs.token_flops(m, ctx0 + j + 1)
                                 for ctx0, p in live for j in range(p))
    if not flops:
        return None
    return 100.0 * flops / (rec.trace.window_ns / 1e9 * rec.chips
                            * rec.peaks["bf16_flops"])
