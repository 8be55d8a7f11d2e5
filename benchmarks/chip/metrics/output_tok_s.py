"""Throughput of a queued job: the answer tokens of every request over
(the last request's final frame - the window's opening). A job with a
request that never finished has no makespan: nothing to read."""


def read(rec):
    if not rec.requests or any(r.finished is None for r in rec.requests):
        return None
    span = max(r.finished for r in rec.requests) - rec.window[0]
    tokens = sum(len(r.tokens) for r in rec.requests)
    return tokens / span if span > 0 and tokens else None
