"""The engine's own instrumentation: the reason counters of K clamps,
the scopes and names its compiled programs carry, and the host spans it
writes into a profiler trace."""
import logging
import re

import jax
import numpy as np
import pytest

from repro.analysis import analyze_source, get_rules
from repro.serving.request import InferenceRequest, SamplingParams

K = 8
REASONS = ("k1_prefill", "k1_batch", "k_pool")


def _requests(vocab, spec, seed=0):
    """spec: [(prompt length, max_tokens), ...] -> greedy requests r0.."""
    rng = np.random.default_rng(seed)
    return [InferenceRequest(
        model="m", prompt_tokens=rng.integers(2, vocab, size=n).tolist(),
        request_id=f"r{i}",
        sampling=SamplingParams(max_tokens=m, temperature=0.0))
        for i, (n, m) in enumerate(spec)]


def _observe_fused_calls(eng):
    """Record, for each fused call the engine makes, (K asked, K run,
    prefill in flight, batch changed) as the engine saw them."""
    be, calls = eng.backend, []
    orig = be.fused_decode

    def fused_decode(k, host_state=None):
        seen = (bool(eng.prefilling), bool(eng.slots.dirty))
        toks, produced, done = orig(k, host_state)
        calls.append((k, toks.shape[0], *seen))
        return toks, produced, done

    be.fused_decode = fused_decode
    return calls


def test_k_clamps_count_under_one_reason_each(llama, engine_factory):
    """Chunked prefill of a 45-token prompt while a 10-token one decodes
    (prefill in flight), admissions and finishes (batch changed), and a
    pool of 7 usable 16-token pages too tight for 8 more tokens of both
    sequences at once."""
    cfg, model, params = llama
    eng = engine_factory(model, params, num_pages=8, decode_steps_per_sync=K,
                         chunked_prefill_budget=16)
    calls = _observe_fused_calls(eng)
    for r in _requests(cfg.vocab_size, [(10, 40), (45, 20)]):
        eng.add_request(r)
    outs = eng.run_to_completion()
    assert len(outs) == 2
    want = {
        "k1_prefill": sum(pre for _, _, pre, _ in calls),
        "k1_batch": sum(dirty and not pre for _, _, pre, dirty in calls),
        "k_pool": sum(run < asked for asked, run, _, _ in calls),
    }
    got = {k: eng.stats[k] for k in REASONS}
    assert got == want
    assert all(v > 0 for v in got.values()), got
    assert sum(got.values()) == sum(run < K for _, run, _, _ in calls)
    assert eng.stats["decode_syncs"] == len(calls)


def test_no_clamp_is_counted_when_k_is_one(llama, engine_factory,
                                           run_engine):
    cfg, model, params = llama
    eng = engine_factory(model, params, decode_steps_per_sync=1,
                         chunked_prefill_budget=16)
    run_engine(eng, _requests(cfg.vocab_size, [(10, 12), (30, 9)]))
    assert eng.stats["decode_syncs"] > 0
    assert all(eng.stats[k] == 0 for k in REASONS)


def _op_names(hlo: str) -> list:
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["reference", "tail"])
def test_fused_decode_program_carries_its_scopes(llama, engine_factory,
                                                 run_engine, use_kernel):
    """Both impl paths of the paged fused decode: the program is named
    ``fused_decode``, its sampler ops sit under ``sample`` and its
    attention ops under ``decode_attention``."""
    cfg, model, params = llama
    eng = engine_factory(model, params, decode_steps_per_sync=4,
                         use_kernel=use_kernel)
    run_engine(eng, _requests(cfg.vocab_size, [(10, 12), (20, 10)]))
    be = eng.backend
    tables, lens = be._dev_tables
    args = (be.params, be.pools, be._dec_st, tables, lens)
    if be._fused_tail_path:
        args = (be.params, be.pools, be._gather_view(be.pools, tables),
                be._dec_st, tables, lens)
    hlo = be._fused[4].lower(*args).compile().as_text()
    assert re.search(r"^HloModule jit_fused_decode\b", hlo, re.M)
    names = _op_names(hlo)
    assert any("/sample/" in n for n in names)
    assert any("/decode_attention/" in n for n in names)
    # the scopes hold what they name: no matmul of the model step is
    # under sample, and the sampler's sort is under nothing else
    sorts = [ln for ln in hlo.splitlines() if " sort(" in ln]
    assert sorts and all("/sample/" in " ".join(_op_names(ln))
                         for ln in sorts)


def _compiled_programs(caplog, drive) -> list:
    """Names of the jitted programs compiled while ``drive()`` runs."""
    caplog.set_level(logging.WARNING, logger="jax._src.interpreters.pxla")
    caplog.clear()
    with jax.log_compiles(True):
        drive()
    return re.findall(r"Compiling jit\(([^)]*)\)", caplog.text)


@pytest.mark.parametrize("backend", ["slots", "paged"])
def test_no_backend_program_is_unnamed(llama, engine_factory, caplog,
                                       backend):
    """Every program the engine compiles: one-shot prefill, fused decode
    and speculative verify on both backends, and the paged backend's
    swap-in after a swapped-out preemption."""
    cfg, model, params = llama
    eng = engine_factory(model, params, backend=backend,
                         draft=(model, params), spec_tokens=2,
                         decode_steps_per_sync=2,
                         preempt_swap=backend == "paged")

    def drive():
        for r in _requests(cfg.vocab_size, [(10, 12), (20, 10)]):
            eng.add_request(r)
        for _ in range(3):
            eng.step()
        if backend == "paged":
            assert eng.preempt("r0")
        eng.run_to_completion()

    names = _compiled_programs(caplog, drive)
    want = {"prefill", "fused_decode", "spec_verify"}
    if backend == "paged":
        want.add("swap")
        assert eng.stats["swap_ins"] == 1
    assert want <= set(names), names
    assert not [n for n in names if "unknown" in n or "lambda" in n], names


def _engine_spans(path) -> list:
    """(name, start_ns, end_ns, stats) of the ``engine.`` host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
            for plane in pd.planes if not plane.name.startswith("/device")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("engine.")]


def _inside(sp, outer) -> bool:
    return outer[1] <= sp[1] and sp[2] <= outer[2]


def test_engine_spans_nest_in_a_cpu_trace(llama, engine_factory, tmp_path):
    cfg, model, params = llama
    eng = engine_factory(model, params, decode_steps_per_sync=4,
                         chunked_prefill_budget=16)
    reqs = _requests(cfg.vocab_size, [(10, 9), (30, 7)])
    # compile every shape first: the trace holds steps, not compiles
    warm = _requests(cfg.vocab_size, [(10, 9), (30, 7)], seed=1)
    for r in warm:
        r.request_id = "warm-" + r.request_id
        eng.add_request(r)
    eng.run_to_completion()
    steps0, chunks0 = eng.stats["steps"], eng.stats["prefill_chunks"]
    with jax.profiler.trace(str(tmp_path)):
        for r in reqs:
            eng.add_request(r)
        eng.run_to_completion()
    spans = _engine_spans(next(tmp_path.rglob("*.xplane.pb")))
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    assert set(by) == {"engine.step", "engine.admit", "engine.prefill",
                       "engine.decode", "engine.decode.prep",
                       "engine.decode.wait", "engine.decode.unpack"}
    assert len(by["engine.step"]) == eng.stats["steps"] - steps0
    for name in ("engine.admit", "engine.prefill", "engine.decode"):
        assert all(any(_inside(sp, st) for st in by["engine.step"])
                   for sp in by[name]), name
    for dec in by["engine.decode"]:
        held = [sp[0] for sp in spans if sp is not dec and _inside(sp, dec)]
        assert sorted(held) == ["engine.decode.prep", "engine.decode.unpack",
                                "engine.decode.wait"]
    ids = {r.request_id for r in reqs}
    assert sorted(sp[3]["request_id"] for sp in by["engine.admit"]) \
        == sorted(ids)
    assert {sp[3]["request_id"] for sp in by["engine.prefill"]} == ids
    assert len(by["engine.prefill"]) == eng.stats["prefill_chunks"] - chunks0
    assert len(by["engine.prefill"]) > len(reqs)    # a prompt in chunks


LINT_SRC = """
import jax
from functools import partial


def _named(name, fn):
    fn.__name__ = name
    return fn


class B:
    def _impl(self, x, *, K):
        return x.item() * K

    def build(self, K):
        self._f[K] = jax.jit(_named("prog", partial(self._impl, K=K)),
                             donate_argnums=(0,))
"""


def test_lint_sees_through_a_program_name():
    """A jitted ``partial`` wrapped to name its program stays a root of
    the host-sync rule's hot set."""
    kept, _ = analyze_source(LINT_SRC, "named.py",
                             get_rules(["host-sync-in-hot-path"]))
    assert [f.rule for f in kept] == ["host-sync-in-hot-path"]
    assert "_impl" in kept[0].render()
