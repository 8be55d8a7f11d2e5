"""The traffic generator: the same seed gives the same requests, every
seed the same set of sizes, and each mix follows its stated parameters."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def test_same_seed_same_requests():
    a = traffic.generate(mix("chat"), 2**31 + 17, 20, 151936)
    b = traffic.generate(mix("chat"), 2**31 + 17, 20, 151936)
    assert [(r.rid, r.due, r.prompt, r.max_tokens) for r in a] == \
        [(r.rid, r.due, r.prompt, r.max_tokens) for r in b]


@pytest.mark.parametrize("seeds", [(1, 2), (2**31 + 3, 2**32 + 7)])
def test_seeds_share_the_sizes_in_another_order(seeds):
    a, b = (traffic.generate(mix("chat"), s, 20, 151936) for s in seeds)
    assert Counter(len(r.prompt) for r in a) == \
        Counter(len(r.prompt) for r in b)
    assert Counter(r.max_tokens for r in a) == \
        Counter(r.max_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[0].prompt != b[0].prompt


def test_chat_follows_its_parameters():
    m = mix("chat")
    rate, T = m["arrivals"]["rate_rps"], 50.0
    reqs = traffic.generate(m, 5, T, 151936)
    assert len(reqs) == round(rate * T)
    due = np.array([r.due for r in reqs])
    assert np.all(np.diff(due) >= 0) and 0 < due[0] and due[-1] < T
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_tokens for r in reqs])
    assert p.min() >= m["prompt"]["lo"] and p.max() <= m["prompt"]["hi"]
    assert o.min() >= m["output"]["lo"] and o.max() <= m["output"]["hi"]
    # stratified lognormal: the median sits at exp(mu)
    assert abs(np.median(np.log(p)) - m["prompt"]["mu"]) < 0.05
    assert abs(np.median(np.log(o)) - m["output"]["mu"]) < 0.05
    # exponential gaps: their spread is their mean
    gaps = np.diff(due)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    assert all(2 <= t < 151936 for r in reqs for t in r.prompt)


def test_quantiles_cover_uniform_evenly():
    q = traffic.quantiles({"kind": "uniform", "lo": 16, "hi": 127}, 112)
    assert sorted(q.tolist()) == list(range(16, 128))


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError, match="unknown arrivals"):
        traffic.generate(dict(mix("chat"), arrivals={"kind": "queued"}),
                         1, 20, 151936)


def test_max_context_is_the_longest_prompt_and_answer():
    m = mix("chat")
    assert traffic.max_context(m) == m["prompt"]["hi"] + m["output"]["hi"]
