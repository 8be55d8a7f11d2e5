"""A traffic kind that a checkout gains as a file alone (``cells.py`` puts
it in ``traffic/kinds/``): ``{"kind": "steady", "rate_rps": r}`` sends
round(r * T) requests due at even gaps over a window of T seconds, with
the lengths of ``prompt`` and ``output`` stratified as ``poisson`` draws
them."""
from __future__ import annotations

import numpy as np

from traffic import Request, quantiles


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    rng = np.random.default_rng(seed)
    n = max(1, round(float(mix["arrivals"]["rate_rps"]) * seconds))
    plen = rng.permutation(quantiles(mix["prompt"], n))
    olen = rng.permutation(quantiles(mix["output"], n))
    return [Request(f"r{i}", seconds * (i + 0.5) / n,
                    rng.integers(2, vocab, int(plen[i])).tolist(),
                    int(olen[i])) for i in range(n)]


def max_context(mix: dict) -> int:
    return int(mix["prompt"]["hi"] + mix["output"]["hi"])
