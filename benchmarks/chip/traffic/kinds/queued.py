"""A queued batch job of questions over documents, all due when the window
opens (a ``/v1/batches`` job): ``{"kind": "queued", "blocks": b,
"documents_per_block": n, "questions_per_document": q}``.

The job is b whole blocks of n documents with q questions each. Each
prompt is its document's exact tokens followed by one question, so a
document's questions share it as a prefix. Within a block the requests go
round-robin: the first question of each of its n documents, then the
second question of each, and so on; block after block.

Parameters besides ``arrivals``, each a length distribution
(``traffic.quantiles``): ``document``, ``question`` and ``output`` (the
answer). The lengths and the placement are the same for every seed: their
stratified draws are laid out by a generator of a fixed seed
(``LAYOUT_SEED``). The run's seed draws only the token ids, so every seed
does the same work.
"""
from __future__ import annotations

import numpy as np

from traffic import Request, quantiles

LAYOUT_SEED = 0


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of the job, in the order they are queued; ``seconds``
    does not change the job."""
    arr = mix["arrivals"]
    blocks, n, q = (int(arr[k]) for k in
                    ("blocks", "documents_per_block",
                     "questions_per_document"))
    layout = np.random.default_rng(LAYOUT_SEED)
    docs = blocks * n
    dlen = layout.permutation(quantiles(mix["document"], docs))
    qlen = layout.permutation(quantiles(mix["question"], docs * q))
    olen = layout.permutation(quantiles(mix["output"], docs * q))
    rng = np.random.default_rng(seed)
    doc_toks = [rng.integers(2, vocab, int(d)).tolist() for d in dlen]
    reqs = []
    for b in range(blocks):
        for k in range(q):
            for j in range(b * n, (b + 1) * n):
                i = len(reqs)
                question = rng.integers(2, vocab, int(qlen[i])).tolist()
                reqs.append(Request(f"r{i}", 0.0, doc_toks[j] + question,
                                    int(olen[i])))
    return reqs


def max_context(mix: dict) -> int:
    """The longest document, question and answer together."""
    return int(mix["document"]["hi"] + mix["question"]["hi"]
               + mix["output"]["hi"])
