"""The traffic generator: the same seed gives the same requests, every
seed the same set of sizes, and each mix follows its stated parameters."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import spec
import traffic

BENCH = Path(__file__).resolve().parents[1]
MIXES = BENCH / "traffic"
SPEC = spec.Spec(BENCH.parents[1])


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def generate(m, seed, seconds, vocab):
    return SPEC.traffic_kind(m).generate(m, seed, seconds, vocab)


def test_same_seed_same_requests():
    a = generate(mix("chat"), 2**31 + 17, 20, 151936)
    b = generate(mix("chat"), 2**31 + 17, 20, 151936)
    assert [(r.rid, r.due, r.prompt, r.max_tokens) for r in a] == \
        [(r.rid, r.due, r.prompt, r.max_tokens) for r in b]


@pytest.mark.parametrize("seeds", [(1, 2), (2**31 + 3, 2**32 + 7)])
def test_seeds_share_the_sizes_in_another_order(seeds):
    a, b = (generate(mix("chat"), s, 20, 151936) for s in seeds)
    assert Counter(len(r.prompt) for r in a) == \
        Counter(len(r.prompt) for r in b)
    assert Counter(r.max_tokens for r in a) == \
        Counter(r.max_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[0].prompt != b[0].prompt


def test_chat_follows_its_parameters():
    m = mix("chat")
    rate, T = m["arrivals"]["rate_rps"], 50.0
    reqs = generate(m, 5, T, 151936)
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
        generate(dict(mix("chat"), arrivals={"kind": "no-such-kind"}),
                 1, 20, 151936)


def test_max_context_is_the_longest_prompt_and_answer():
    m = mix("chat")
    assert SPEC.traffic_kind(m).max_context(m) == \
        m["prompt"]["hi"] + m["output"]["hi"]


# -- the queued document-QA job ------------------------------------------------

def doc_prefix(a: list, b: list) -> int:
    """Length of the common prefix of two prompts."""
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


def docqa(seed, name="docqa"):
    m = mix(name)
    return m, generate(m, seed, 50, 64000)


@pytest.mark.parametrize("seeds", [(1, 2), (2**31 + 3, 2**32 + 7)])
def test_docqa_seeds_share_lengths_and_placement(seeds):
    (_, a), (_, b) = (docqa(s) for s in seeds)
    assert [(r.rid, r.due, len(r.prompt), r.max_tokens) for r in a] == \
        [(r.rid, r.due, len(r.prompt), r.max_tokens) for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))


def test_docqa_prompts_are_their_documents_then_a_question():
    m, reqs = docqa(2**31 + 41)
    arr = m["arrivals"]
    n, q = arr["documents_per_block"], arr["questions_per_document"]
    assert len(reqs) == arr["blocks"] * n * q
    assert all(r.due == 0.0 for r in reqs)           # the job is queued
    block = n * q
    questions, docs = [], []
    for b0 in range(0, len(reqs), block):
        for j in range(n):
            # round-robin: the block's k-th questions of its n documents
            mine = [reqs[b0 + k * n + j] for k in range(q)]
            d = min(doc_prefix(mine[0].prompt, x.prompt) for x in mine[1:])
            docs.append(d)
            assert all(x.prompt[:d] == mine[0].prompt[:d] for x in mine)
            questions += [len(x.prompt) - d for x in mine]
            # the next document in the block is another document
            if j + 1 < n:
                assert doc_prefix(mine[0].prompt,
                                  reqs[b0 + j + 1].prompt) < 8
    assert Counter(questions) == Counter(
        traffic.quantiles(m["question"], len(reqs)).tolist())
    assert Counter(docs) == Counter(
        traffic.quantiles(m["document"], len(docs)).tolist())
    assert Counter(r.max_tokens for r in reqs) == Counter(
        traffic.quantiles(m["output"], len(reqs)).tolist())
    assert abs(np.median(docs) - 4096) < 0.1 * 4096
    assert all(2 <= t < 64000 for r in reqs for t in r.prompt)


def test_docqa_max_context_is_the_longest_document_question_and_answer():
    m, reqs = docqa(7)
    kind = SPEC.traffic_kind(m)
    assert kind.max_context(m) == m["document"]["hi"] + \
        m["question"]["hi"] + m["output"]["hi"] == 12512
    longest = max(len(r.prompt) + r.max_tokens for r in reqs)
    assert m["document"]["hi"] < longest <= kind.max_context(m)
