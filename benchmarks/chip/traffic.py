"""One general generator for every traffic mix: ``traffic/<mix>.json``
holds the parameters, the seed the order and the token ids.

The length model is the lognormal fit to ShareGPT lengths of
``repro.data.workload.sharegpt_lengths`` (prompt mu 5.1, sigma 0.9; output
mu 5.0, sigma 0.8), and the arrivals are Poisson as in its
``make_workload``; both are copied here as parameters, not imported. The
draws are stratified: a run of N requests takes the N quantiles
(i + 1/2) / N of each distribution, so every seed serves the same set of
prompt lengths, output lengths and gaps between arrivals, each in an order
of its own. The seed changes the order and the token ids, not the amount
of work.

Parameters (see ``traffic/*.json``):

- ``arrivals``: ``{"kind": "poisson", "rate_rps": r}`` sends round(r * T)
  requests due in a window of T seconds, open loop.
- ``prompt`` / ``output``: a length distribution, ``{"kind":
  "lognormal", "mu", "sigma", "lo", "hi"}`` or ``{"kind": "uniform",
  "lo", "hi"}`` (bounds inclusive).
- ``temperature``: 0 for greedy requests; ``qos``: the request class.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    rid: str
    due: float            # seconds after the window opens
    prompt: list
    max_tokens: int


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified draws (i + 1/2) / n of a length distribution."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if dist["kind"] == "lognormal":
        nd = NormalDist()
        x = np.exp(dist["mu"] + dist["sigma"]
                   * np.array([nd.inv_cdf(v) for v in u]))
        return np.clip(x.astype(np.int64), lo, hi)
    if dist["kind"] == "uniform":
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi).astype(
            np.int64)
    raise ValueError(f"unknown length distribution {dist['kind']!r}")


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


def describe(reqs: list) -> dict:
    p = [len(r.prompt) for r in reqs]
    o = [r.max_tokens for r in reqs]
    return {"requests": len(reqs), "prompt_tokens": int(sum(p)),
            "output_tokens": int(sum(o)),
            "prompt_mean": float(np.mean(p)) if p else math.nan,
            "output_mean": float(np.mean(o)) if o else math.nan}
