"""Host time inside ``fused_decode`` (which ends when its tokens reach
the host), summed over the window's calls and divided by the decode
steps they ran."""


def read(rec):
    calls = [c for c in rec.decode_calls if rec.in_window(c[0])]
    steps = sum(c[3] for c in calls)
    return sum(c[1] - c[0] for c in calls) * 1e3 / steps if steps else None
