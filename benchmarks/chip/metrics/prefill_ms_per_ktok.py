"""Device time of the chunked-prefill programs per 1000 prompt tokens
they computed, in the traced window: the ``jit__chunk_prefill_impl``
programs' device seconds, clipped to the trace, over the tokens of the
chunks whose calls lie in the trace (a call that straddles an edge counts
by its share inside). The host's own time in ``prefill_chunk`` is the
dispatch alone: the call returns before its program runs."""
import trace_reduce

PROGRAM = r"^jit__chunk_prefill_impl$"


def read(rec):
    if rec.trace is None:
        return None
    tokens = sum(rec.trace_share(t0, t1) * n
                 for t0, t1, _, n in rec.prefill_calls)
    t = trace_reduce.module_seconds(rec.trace, PROGRAM)
    return t * 1e6 / tokens if tokens > 0 and t > 0 else None
