"""What the benchmark reads of ``qwen4b-chat`` stays as it was before the
architectures and traffic kinds were found by file: the chat mix's
requests, the dense decoder's weights and its reference logits, for fixed
seeds, against values recorded from the code before it moved."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import spec as spec_mod
import weights as W

BENCH = Path(__file__).resolve().parents[1]
FIX = Path(__file__).resolve().parent / "fixtures"
SPEC = spec_mod.Spec(BENCH.parents[1])
SEED = 2**31 + 99


@pytest.fixture(scope="module")
def tiny():
    cfg = json.loads((FIX / "tiny.json").read_text())
    arch = SPEC.arch(cfg)
    return arch, arch.Dims.of(cfg)


def test_chat_requests_are_unchanged():
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    h = hashlib.sha256()
    for seed in (2**31 + 17, 5):
        reqs = SPEC.traffic_kind(mix).generate(mix, seed, 50, 151936)
        h.update(repr([(r.rid, r.due, r.prompt, r.max_tokens)
                       for r in reqs]).encode())
    assert h.hexdigest() == \
        "510fa2bdd70a23ce70b27baa834809e296aa3d87cddf93db48728a15b8f76635"


def test_dense_weights_are_unchanged(tiny):
    arch, m = tiny
    params = arch.program_params(m, SEED)
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(params),
                             key=lambda x: str(x[0])):
        h.update(str(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == \
        "d2f1f91873f2ec16e4fe10e117fe01a2f86f823516dba4a1247c38e30984f211"


# reference logits at positions 0, 100 and 511 of a 512-token sequence,
# their first six entries, float32 on the CPU
LOGITS = [[-0.3892303705215454, 0.6850723028182983, -1.9424463510513306,
           -0.14641247689723969, 0.6769046783447266, 0.9135861992835999],
          [2.6120574474334717, -1.3878828287124634, 0.06500501930713654,
           0.17633189260959625, 1.2114753723144531, -0.5697195529937744],
          [0.9974327087402344, -0.47583192586898804, -0.1227526143193245,
           0.2970963716506958, 1.2055373191833496, -0.24773401021957397]]


def test_dense_reference_logits_are_unchanged(tiny):
    arch, m = tiny
    words = W.seed_words(SEED)
    toks = np.arange(2, 2 + 512, dtype=np.int32) * 7 % 1024
    h = arch.embed(words, jnp.asarray(toks), m=m)
    keys = arch.layer_keys(words, m)
    for li in range(m.L):
        h = arch.layer(h, arch.layer_weights(keys[li], m=m), m=m,
                       quant=False)
    rows = np.array([0, 100, 511] + [0] * (reference.ROWS - 3))
    ref = np.asarray(arch.head(words, h[rows], m=m, quant=False))
    # float32 sums in another order on another CPU move the last bits only
    np.testing.assert_allclose(ref[:3, :6], LOGITS, rtol=0, atol=1e-4)
    assert float(np.abs(ref[:3]).sum()) == pytest.approx(2408.548828125,
                                                         rel=1e-5)
    cmp = reference.compare(arch, m, SEED, [(list(range(2, 40)),
                                             list(range(50, 70)))], True)
    assert cmp["served_tokens"] == 20
    assert cmp["mean_gap"] == pytest.approx(3.7013587951660156, rel=1e-4)
    assert cmp["logit_gap"] == pytest.approx(5.886919021606445, rel=1e-4)
