"""Speculative decoding: draft-and-verify must be an optimization, never a
different sampler.

The acceptance rule is the seeded-sampler exact-match test (see
repro.serving.sampler): the target's per-position sample is deterministic
given (seed_base, n_gen), so accepted-prefix + residual-resample streams are
token-identical to non-speculative decoding for every sampling mode — which
these tests assert at both acceptance extremes (draft == target: everything
accepted; cold random draft: everything rejected) and for the KV caches
left behind after accept/rollback."""
import numpy as np
import pytest

from repro.serving import backends

PAGE = 16


@pytest.fixture(scope="session")
def cold_draft(lm_factory):
    """Same arch as the target but independently initialized: its proposals
    are (almost) always rejected — the k=0-accepted edge case."""
    _, model, params = lm_factory(seed=99)
    return model, params


@pytest.fixture
def run(engine_factory, run_engine):
    def _run(model, params, reqs, *, draft=None, **cfg_kw):
        eng = engine_factory(model, params, draft=draft, **cfg_kw)
        return run_engine(eng, reqs)
    return _run


# ---------------------------------------------------------------------------
# sampler-level unit behaviour
# ---------------------------------------------------------------------------

def test_spec_accept_counts_prefix_and_residual():
    import jax.numpy as jnp
    from repro.serving.sampler import spec_accept
    targets = jnp.asarray([[5, 6, 7, 8],        # all drafts match -> bonus
                           [5, 9, 7, 8],        # mismatch at j=1
                           [1, 2, 3, 4]])       # mismatch at j=0
    draft = jnp.asarray([[5, 6, 7],
                         [5, 6, 7],
                         [9, 2, 3]])
    emit, n_emit = spec_accept(targets, draft)
    assert n_emit.tolist() == [4, 2, 1]
    assert np.asarray(emit).tolist() == [
        [True, True, True, True],
        [True, True, False, False],
        [True, False, False, False]]


def test_spec_targets_fold_matches_step_seeds():
    """Verify-position j must fold the SAME seed the non-speculative loop
    folds when emitting its (n_gen + j)-th token, so greedy and seeded
    top-p streams stay identical."""
    import jax
    import jax.numpy as jnp
    from repro.serving.sampler import (sample_from_logits, seed_base,
                                      fold_seeds, spec_targets)
    B, T, V = 2, 3, 64
    logits = jax.random.normal(jax.random.PRNGKey(0), (B, T, V))
    temps = jnp.asarray([0.0, 0.9])
    tps = jnp.asarray([1.0, 0.9])
    bases = jnp.asarray([seed_base(3), seed_base(11)], jnp.uint32)
    n_gen = jnp.asarray([4, 9], jnp.int32)
    got = spec_targets(logits, temps, tps, bases, n_gen)
    for j in range(T):
        want = sample_from_logits(logits[:, j], temps, tps,
                                  fold_seeds(bases, n_gen + j))
        assert np.array_equal(np.asarray(got[:, j]), np.asarray(want))


# ---------------------------------------------------------------------------
# token identity at both acceptance extremes
# ---------------------------------------------------------------------------

def test_spec_all_accepted_matches_nonspec(llama, backend, sampling,
                                           request_factory, run):
    """k = all accepted: the draft IS the target, so every proposal
    survives and rounds emit k+1 tokens (accepted prefix + bonus)."""
    cfg, model, params = llama
    kw = dict(max_slots=3, max_seq_len=96, backend=backend, page_size=PAGE)
    reqs = request_factory(cfg.vocab_size, n=3, **sampling)
    ref, _ = run(model, params, reqs, **kw)
    backends.reset_transfer_stats()
    got, eng = run(model, params, reqs, draft=(model, params),
                   spec_tokens=4, **kw)
    assert got == ref
    assert backends.TRANSFER_STATS["decode_logits_transfers"] == 0
    assert eng.stats["spec_rounds"] > 0
    assert eng.spec_acceptance_rate() > 0.8
    # accept-heavy rounds emit multiple tokens per sync
    assert eng.stats["decode_syncs"] * 2 < eng.stats["decode_tokens"]


def test_spec_none_accepted_matches_nonspec(llama, cold_draft, backend,
                                            sampling, request_factory, run):
    """k = 0 accepted: a cold random draft disagrees everywhere, every
    round falls back to the single residual-resampled target token — the
    stream must STILL be identical to non-speculative decoding."""
    cfg, model, params = llama
    kw = dict(max_slots=3, max_seq_len=96, backend=backend, page_size=PAGE)
    reqs = request_factory(cfg.vocab_size, n=3, **sampling)
    ref, _ = run(model, params, reqs, **kw)
    got, eng = run(model, params, reqs, draft=cold_draft, spec_tokens=4,
                   **kw)
    assert got == ref
    assert eng.stats["spec_rounds"] > 0
    assert eng.spec_acceptance_rate() < 0.2


def test_spec_stop_token_mid_round(llama, request_factory, run):
    """A stop token landing inside the accepted prefix must truncate the
    round at exactly the same token as the per-step path."""
    cfg, model, params = llama
    kw = dict(max_slots=2, max_seq_len=96, backend="paged", page_size=PAGE)
    samp = dict(max_tokens=24, temperature=0.9, top_p=0.95)
    probe = request_factory(cfg.vocab_size, n=1, **samp)
    ref, _ = run(model, params, probe, **kw)
    toks, reason = ref["r0"]
    assert reason == "length"
    first = {}
    for j, t in enumerate(toks):
        first.setdefault(t, j)
    cands = sorted((j, t) for t, j in first.items()
                   if 2 <= j < 20 and (j + 1) % 5 != 0)
    if not cands:
        cands = sorted((j, t) for t, j in first.items() if j >= 1)
    j0, stop = cands[0]
    reqs = request_factory(cfg.vocab_size, n=2, stop=stop, **samp)
    ref_s, _ = run(model, params, reqs, **kw)
    got_s, eng = run(model, params, reqs, draft=(model, params),
                     spec_tokens=4, **kw)
    assert got_s == ref_s
    assert got_s["r0"][1] == "stop"
    assert len(got_s["r0"][0]) == j0 + 1


def test_spec_draft_resyncs_after_fused_fallback(llama, cold_draft,
                                                 engine_factory,
                                                 request_factory):
    """Staggered arrival: a long prompt admitted mid-stream forces the
    engine through fused-fallback rounds (the draft cache stands still
    while the target advances); when speculation resumes the draft must
    catch up on the emitted tokens it missed — previously this crashed
    with a forward rollback on the paged backend. The small chunk budget
    makes the fallback span exceed k+1 rounds, the worst case."""
    cfg, model, params = llama

    def drive(spec):
        eng = engine_factory(
            model, params, max_slots=4, max_seq_len=128, backend="paged",
            page_size=PAGE, chunked_prefill_budget=8,
            spec_tokens=4 if spec else 0,
            draft=cold_draft if spec else None)
        reqs = request_factory(cfg.vocab_size, n=1, plen=10, max_tokens=30,
                               seed0=0)
        eng.add_request(reqs[0])
        eng.step()
        eng.step()                       # r0 decoding (spec rounds begin)
        late = request_factory(cfg.vocab_size, n=1, plen=70, max_tokens=8,
                               seed0=1, rng_seed=11)[0]
        late.request_id = "late"
        eng.add_request(late)            # 9 chunks of fallback rounds
        outs = eng.run_to_completion()
        return {o.request_id: (o.output_tokens, o.finish_reason)
                for o in outs}, eng

    ref, _ = drive(spec=False)
    got, eng = drive(spec=True)
    assert got == ref
    assert eng.stats["spec_rounds"] > 0


def test_spec_composes_with_chunked_prefill_and_prefix_cache(
        llama, request_factory, run):
    """Speculation must compose with chunked prefill (rounds pause while
    prompts ingest) and prefix caching (shared pages + COW under verify
    writes) without changing outputs."""
    cfg, model, params = llama
    kw = dict(max_slots=3, max_seq_len=128, backend="paged", page_size=PAGE,
              chunked_prefill_budget=24, enable_prefix_cache=True)
    rng = np.random.default_rng(3)
    shared = rng.integers(2, cfg.vocab_size, size=2 * PAGE).tolist()
    prompts = [list(shared), list(shared)] + [
        shared + rng.integers(2, cfg.vocab_size, size=9).tolist()
        for _ in range(3)]
    reqs = request_factory(cfg.vocab_size, prompts=prompts, max_tokens=16)
    ref, er = run(model, params, reqs, **kw)
    got, eg = run(model, params, reqs, draft=(model, params),
                  spec_tokens=4, **kw)
    assert got == ref
    assert eg.cache_stats()["hit_tokens"] == er.cache_stats()["hit_tokens"]
    assert eg.cache_stats()["cow_copies"] >= 1
    assert eg.stats["spec_rounds"] > 0


# ---------------------------------------------------------------------------
# KV caches after accept/rollback == a non-speculative replay
# ---------------------------------------------------------------------------

def _gather_seq_kv(eng, rid):
    """(length, KV rows [0, length)) for one sequence, as numpy — the
    defined cache contents (positions past the length are write headroom:
    masked by every read and rewritten before the length crosses them)."""
    be = eng.backend
    if hasattr(be, "kv"):                               # paged
        table = be.kv._tables[rid]
        n = be.kv.length(rid)
        kp = np.asarray(be.pools["k"])
        vp = np.asarray(be.pools["v"])
        ps = be.page_size
        rows = [np.stack([pool[:, table[p // ps], :, p % ps]
                          for p in range(n)], 1) for pool in (kp, vp)]
        return n, rows
    s = be.slot(rid)                                    # dense slots
    n = int(np.asarray(be.cache["len"])[s])
    return n, [np.asarray(be.cache[c])[:, s, :, :n] for c in ("k", "v")]


@pytest.mark.parametrize("backend", ["paged", "slots"])
def test_spec_rollback_leaves_kv_as_nonspec_replay(llama, cold_draft,
                                                   backend, engine_factory,
                                                   request_factory):
    """Mid-generation, a speculating engine's per-sequence KV (including
    COW'd shared pages from prefix-cache hits) must equal a non-speculative
    engine replayed to the same per-sequence token counts: byte-identical
    on the paged backend (verify and decode share the attention
    formulation); on the dense backend the batched verify attention
    reassociates float32 sums vs the appended-decode read path, so rows
    match to 1e-5 while lengths and token streams stay exactly equal."""
    cfg, model, params = llama
    kw = dict(max_slots=3, max_seq_len=128, page_size=PAGE, backend=backend)
    if backend == "paged":
        kw["enable_prefix_cache"] = True
    rng = np.random.default_rng(3)
    shared = rng.integers(2, cfg.vocab_size, size=2 * PAGE).tolist()
    prompts = [list(shared), list(shared),
               shared + rng.integers(2, cfg.vocab_size, size=7).tolist()]
    reqs = request_factory(cfg.vocab_size, prompts=prompts, max_tokens=40)

    es = engine_factory(model, params, draft=cold_draft, spec_tokens=4,
                        **kw)
    for r in reqs:
        es.add_request(r)
    for _ in range(6):                   # stop mid-flight, caches still live
        es.step()
    assert es.running and es.stats["spec_rounds"] > 0
    want = {rid: list(run.output_tokens)
            for rid, run in es.running.items()}
    spec_kv = {rid: _gather_seq_kv(es, rid) for rid in es.running}

    en = engine_factory(model, params, **kw)
    for r in request_factory(cfg.vocab_size, prompts=prompts,
                             max_tokens=40):
        en.add_request(r)
    got = {}
    for _ in range(100):
        if len(got) == len(want):
            break
        en.step()
        for rid, run in en.running.items():
            if rid in want and rid not in got \
                    and len(run.output_tokens) == len(want[rid]):
                assert run.output_tokens == want[rid]
                got[rid] = _gather_seq_kv(en, rid)
    assert set(got) == set(want)
    for rid in want:
        n_s, kv_s = spec_kv[rid]
        n_r, kv_r = got[rid]
        assert n_s == n_r
        for a, b in zip(kv_s, kv_r):
            if backend == "paged":
                assert np.array_equal(a, b), f"{rid}: paged KV diverged"
            else:
                np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# DES mirror: SimEngine speculative rounds
# ---------------------------------------------------------------------------

def test_expected_spec_tokens():
    from repro.serving.costmodel import expected_spec_tokens
    assert expected_spec_tokens(0.0, 4) == 1.0          # nothing accepted
    assert expected_spec_tokens(1.0, 4) == 5.0          # everything + bonus
    mid = expected_spec_tokens(0.7, 4)
    assert 1.0 < mid < 5.0
    assert expected_spec_tokens(0.7, 8) > mid           # deeper drafts help


def test_sim_engine_spec_decode_mirror():
    from repro.configs import REGISTRY
    from repro.core.clock import EventLoop, VirtualClock
    from repro.core.instances import SimEngine, SimRequest
    from repro.serving.costmodel import InstanceCost

    target = InstanceCost(cfg=REGISTRY["yi-34b"], chips=8)
    draft = InstanceCost(cfg=REGISTRY["llama3.2-3b"], chips=8)

    def run(spec_k, accept=0.8):
        loop = EventLoop(VirtualClock())
        done = []
        eng = SimEngine(loop, target, max_slots=4, spec_tokens=spec_k,
                        spec_accept_rate=accept, draft_cost=draft)
        for i in range(4):
            eng.submit(SimRequest(f"r{i}", 64, 48), None, done.append)
        loop.run_until_idle()
        assert len(done) == 4
        return loop.now(), sorted((d["request_id"], d["output_tokens"])
                                  for d in done)

    t0, done0 = run(0)
    t_spec, done_spec = run(4, accept=0.8)
    assert done0 == done_spec            # same tokens per request
    assert t_spec < t0                   # accept-heavy rounds win
    t_cold, _ = run(4, accept=0.0)
    assert t_cold > t_spec               # nothing accepted: rounds cost more
    # and the closed-form throughput agrees on direction
    assert target.spec_decode_tok_per_s(4, draft, 4, 0.8) > \
        target.decode_tok_per_s(4)
    with pytest.raises(ValueError):
        SimEngine(EventLoop(), target, spec_tokens=4)    # draft required


def test_spec_requires_draft_and_attention_family(llama, mamba):
    from repro.serving.engine import ContinuousBatchingEngine, EngineConfig
    _, model, params = llama
    with pytest.raises(ValueError, match="draft"):
        ContinuousBatchingEngine(model, params,
                                 EngineConfig(spec_tokens=4))
    _, smodel, sparams = mamba
    with pytest.raises(ValueError, match="attention"):
        ContinuousBatchingEngine(smodel, sparams,
                                 EngineConfig(spec_tokens=4),
                                 draft_model=smodel, draft_params=sparams)