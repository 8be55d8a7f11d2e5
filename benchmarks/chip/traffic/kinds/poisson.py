"""Open-loop Poisson arrivals: ``{"kind": "poisson", "rate_rps": r}`` sends
round(r * T) requests due in a window of T seconds.

The length model is the lognormal fit to ShareGPT lengths of
``repro.data.workload.sharegpt_lengths`` (prompt mu 5.1, sigma 0.9; output
mu 5.0, sigma 0.8), and the arrivals are Poisson as in its
``make_workload``; both are copied here as parameters, not imported. The
draws are stratified (``traffic.quantiles``), so every seed serves the same
set of prompt lengths, output lengths and gaps between arrivals, each in an
order of its own. The seed changes the order and the token ids, not the
amount of work.

Parameters besides ``arrivals``: ``prompt`` / ``output``, each a length
distribution (``traffic.quantiles``).
"""
from __future__ import annotations

import numpy as np

from traffic import Request, quantiles


def poisson_arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """round(rate * seconds) arrival times in (0, seconds): the quantiles
    of the exponential gap at that rate, in the seed's order, scaled so
    that the last request is due just before the window closes."""
    n = max(1, round(rate * seconds))
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    c = np.cumsum(gaps)
    return seconds * (c - gaps[0] / 2) / c[-1]


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of one run, in the order they are sent."""
    rng = np.random.default_rng(seed)
    arr = mix["arrivals"]
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrivals {arr['kind']!r}")
    due = poisson_arrivals(float(arr["rate_rps"]), seconds, rng)
    n = len(due)
    plen = rng.permutation(quantiles(mix["prompt"], n))
    olen = rng.permutation(quantiles(mix["output"], n))
    return [Request(f"r{i}", float(due[i]),
                    rng.integers(2, vocab, int(plen[i])).tolist(),
                    int(olen[i])) for i in range(n)]


def max_context(mix: dict) -> int:
    """The longest prompt plus output that the mix can send."""
    return int(mix["prompt"]["hi"] + mix["output"]["hi"])
