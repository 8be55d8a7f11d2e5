"""Share (%) of the window's ``fused_decode`` calls that ran one step
where the configuration asks for more: the engine clamps K to 1 while a
prefill is in flight or the batch just changed (or the pool is tight)."""


def read(rec):
    calls = [c for c in rec.decode_calls if rec.in_window(c[0])]
    if not calls:
        return None
    return 100.0 * sum(c[3] == 1 for c in calls) / len(calls)
