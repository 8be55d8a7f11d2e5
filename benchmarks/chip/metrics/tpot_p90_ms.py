"""90th percentile, over requests that finished with at least 2 output
tokens, of (last frame - first frame) / (tokens - 1)."""
import numpy as np


def read(rec):
    v = [(r.frames[-1][0] - r.frames[0][0]) * 1e3 / (len(r.tokens) - 1)
         for r in rec.requests
         if r.finished is not None and len(r.tokens) >= 2]
    return float(np.percentile(v, 90)) if v else None
