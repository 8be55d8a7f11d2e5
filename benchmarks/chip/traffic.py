"""What every traffic mix shares: ``traffic/<mix>.json`` holds a mix's
parameters, and the generator of its ``arrivals.kind``,
``traffic/kinds/<kind>.py`` (found by ``spec.Spec.traffic_kind``), turns
them into requests: ``generate(mix, seed, seconds, vocab)`` gives the
requests of one run in the order they are sent, ``max_context(mix)`` the
longest prompt plus output the mix can send. The seed draws the order and
the token ids; lengths are stratified (``quantiles``), so every seed does
the same amount of work.

Common parameters (see ``traffic/*.json``):

- ``arrivals``: ``{"kind": <kind>, ...}``, the kind's own parameters
  beside it;
- length distributions, ``{"kind": "lognormal", "mu", "sigma", "lo",
  "hi"}`` or ``{"kind": "uniform", "lo", "hi"}`` (bounds inclusive);
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


def describe(reqs: list) -> dict:
    p = [len(r.prompt) for r in reqs]
    o = [r.max_tokens for r in reqs]
    return {"requests": len(reqs), "prompt_tokens": int(sum(p)),
            "output_tokens": int(sum(o)),
            "prompt_mean": float(np.mean(p)) if p else math.nan,
            "output_mean": float(np.mean(o)) if o else math.nan}
