"""Share (%) of the traced window in which no operation ran on the
device: 1 - (union of the ops' intervals) / window, mean over chips."""


def read(rec):
    tr = rec.trace
    if tr is None or tr.chips == 0 or tr.window_ns <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_ns / tr.window_ns)
