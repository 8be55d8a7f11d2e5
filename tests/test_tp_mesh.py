"""Tensor-parallel serving on a simulated mesh.

Four layers of proof on top of the mesh axis of test_parity_matrix.py:

* ``make_local_mesh`` validates its request against the visible device
  count up front (a too-large mesh would otherwise die as an opaque shape
  error inside the first jit).
* **Placement invariants** — after a real sharded engine run, params are
  TP-sharded over ``model``, paged KV pools shard the kv-head axis (MHA)
  or fall back to head_dim (GQA whose 2 kv heads don't divide 4 shards),
  while everything the sampler touches (decode state, device tables,
  lens) is fully replicated and no logits ever cross to the host.
* **MoE expert parallelism** — a reduced phi3.5-moe (4 experts = one per
  shard) decodes token-identically to single-device with its expert
  stacks sharded over ``model``.
* **Allocator replica consistency** — the paged allocator is host-side
  and replicated per shard by construction; a hypothesis churn property
  drives 4 replicas through one random admit/COW/rollback/free sequence
  and requires bit-identical snapshots plus conservation at every step.
"""
from collections import Counter

import jax
import numpy as np
import pytest

from repro.launch.mesh import make_local_mesh
from repro.serving import backends
from repro.serving.kv_cache import OutOfPages, PagedKVCache

try:        # the property test widens the seed space when hypothesis exists;
    # the fixed-seed churn tests below always run (hypothesis is a dev-only
    # dependency, see test_property.py)
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

N_SHARDS = 4


@pytest.fixture(scope="module")
def mesh4():
    if jax.device_count() < N_SHARDS:
        pytest.skip("needs >= 4 devices; run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return make_local_mesh(1, N_SHARDS)


# -- make_local_mesh validation ----------------------------------------------

def test_make_local_mesh_shapes():
    m = make_local_mesh(1, 4)
    assert m.axis_names == ("data", "model")
    assert m.shape["data"] == 1 and m.shape["model"] == 4
    m2 = make_local_mesh(2, 4)
    assert m2.shape["data"] == 2


def test_make_local_mesh_rejects_oversize():
    with pytest.raises(ValueError, match="visible"):
        make_local_mesh(1, jax.device_count() + 1)


def test_make_local_mesh_rejects_nonpositive():
    with pytest.raises(ValueError, match="positive"):
        make_local_mesh(0, 4)
    with pytest.raises(ValueError, match="positive"):
        make_local_mesh(1, -2)


def test_kernel_backend_serves_under_mesh(qwen, engine_factory, mesh4,
                                          request_factory, run_engine):
    """use_kernel under a mesh is no longer rejected: the decode kernels
    run per-shard via shard_map over the kv-head axis (qwen's 4 MHA kv
    heads divide 4 shards), token-identical to the unsharded non-kernel
    engine."""
    cfg, model, params = qwen
    reqs = request_factory(cfg.vocab_size, n=3, plen=12, max_tokens=10)
    ref_eng = engine_factory(model, params, backend="paged",
                             max_seq_len=64, page_size=16)
    ref, _ = run_engine(ref_eng, reqs)
    backends.reset_transfer_stats()
    eng = engine_factory(model, params, backend="paged", use_kernel=True,
                         mesh=mesh4, max_seq_len=64, page_size=16)
    got, eng = run_engine(eng, reqs)
    assert got == ref
    assert backends.TRANSFER_STATS["decode_logits_transfers"] == 0
    assert eng.backend._kernel_sharded


def test_kernel_backend_mesh_gqa_fallback(llama, engine_factory, mesh4,
                                          request_factory, run_engine):
    """GQA head counts that don't divide the model axis (llama's 2 kv
    heads on 4 shards) can't shard_map the kernel — the backend must fall
    back to the sharded reference path, still token-identical."""
    cfg, model, params = llama
    assert cfg.num_kv_heads % N_SHARDS != 0
    reqs = request_factory(cfg.vocab_size, n=3, plen=12, max_tokens=10)
    ref_eng = engine_factory(model, params, backend="paged",
                             max_seq_len=64, page_size=16)
    ref, _ = run_engine(ref_eng, reqs)
    eng = engine_factory(model, params, backend="paged", use_kernel=True,
                         mesh=mesh4, max_seq_len=64, page_size=16)
    got, eng = run_engine(eng, reqs)
    assert got == ref
    assert not eng.backend._kernel_sharded


# -- placement invariants ----------------------------------------------------

def _spec(arr, nd):
    """PartitionSpec padded to ``nd`` entries (trailing Nones explicit)."""
    s = tuple(arr.sharding.spec)
    return s + (None,) * (nd - len(s))


def _run_sharded(lm, mesh4, engine_factory, request_factory, run_engine):
    cfg, model, params = lm
    reqs = request_factory(cfg.vocab_size, n=3, plen=12, max_tokens=10)
    backends.reset_transfer_stats()
    eng = engine_factory(model, params, backend="paged", mesh=mesh4,
                        max_seq_len=64, page_size=16)
    got, eng = run_engine(eng, reqs)
    assert backends.TRANSFER_STATS["decode_logits_transfers"] == 0
    return got, eng.backend


def test_paged_placement_mha_heads_sharded(qwen, mesh4, engine_factory,
                                           request_factory, run_engine):
    _, be = _run_sharded(qwen, mesh4, engine_factory, request_factory,
                         run_engine)
    # attention/MLP columns over model (Megatron TP)
    layers = be.params["layers"]
    assert _spec(layers["attn"]["wq"], 3)[-1] == "model"
    assert _spec(layers["mlp"]["w1"], 3)[-1] == "model"
    assert _spec(layers["attn"]["wo"], 3)[-2] == "model"
    # 4 kv heads / 4 shards: the pool (L, NP, KH, page, hd) splits on KH
    assert _spec(be.pools["k"], 5) == (None, None, "model", None, None)
    assert _spec(be.pools["v"], 5) == (None, None, "model", None, None)
    _assert_sampler_state_replicated(be)


def test_paged_placement_gqa_head_dim_fallback(llama, mesh4, engine_factory,
                                               request_factory, run_engine):
    _, be = _run_sharded(llama, mesh4, engine_factory, request_factory,
                         run_engine)
    # 2 kv heads don't divide 4 shards -> head_dim shards instead
    assert _spec(be.pools["k"], 5) == (None, None, None, None, "model")
    assert _spec(be.pools["v"], 5) == (None, None, None, None, "model")
    _assert_sampler_state_replicated(be)


def _assert_sampler_state_replicated(be):
    """The zero-logits-transfer contract: everything the fused sampler
    carries — decode state, block tables, lens — lives replicated, so each
    shard samples the same token from full logits."""
    assert be._dec_st is not None, "fused decode never ran"
    for name, leaf in be._dec_st.items():
        assert leaf.sharding.is_fully_replicated, f"_dec_st[{name}] sharded"
    tables_d, lens_d = be._dev_tables
    assert tables_d.sharding.is_fully_replicated
    assert lens_d.sharding.is_fully_replicated


# -- MoE expert parallelism --------------------------------------------------

def test_moe_expert_parallel_decode(lm_factory, mesh4, engine_factory,
                                    request_factory, run_engine):
    cfg, model, params = lm_factory("phi3.5-moe-42b-a6.6b")
    reqs = request_factory(cfg.vocab_size, n=2, plen=10, max_tokens=8)
    ref_eng = engine_factory(model, params, backend="slots",
                             fused_decode=False, max_seq_len=64)
    ref, _ = run_engine(ref_eng, reqs)

    backends.reset_transfer_stats()
    eng = engine_factory(model, params, backend="paged", mesh=mesh4,
                         max_seq_len=64, page_size=16)
    got, eng = run_engine(eng, reqs)
    assert got == ref, "expert-parallel decode diverged from single-device"
    assert backends.TRANSFER_STATS["decode_logits_transfers"] == 0
    # expert stacks (L, E, d, f) put one expert per shard; the router
    # stays replicated so every shard computes the same top-k gates
    moe_p = eng.backend.params["layers"]["moe"]
    for w in ("w1", "w2", "w3"):
        assert _spec(moe_p[w], 4)[1] == "model", w
    assert moe_p["router"].sharding.is_fully_replicated


# -- sharded engine churn: prefix cache + COW stay consistent ---------------

def test_sharded_prefix_cache_cow_parity(qwen, mesh4, engine_factory,
                                         request_factory, run_engine,
                                         shared_prefix_prompts):
    """Shared-prefix admission (COW on the recomputed tail page) produces
    the same streams sharded as on one device, and actually hits."""
    cfg, model, params = qwen
    prompts = shared_prefix_prompts(cfg.vocab_size, 4, n_shared=32,
                                    n_tail=11)
    reqs = request_factory(cfg.vocab_size, prompts=prompts, max_tokens=8)
    kw = dict(backend="paged", max_seq_len=96, page_size=16,
              enable_prefix_cache=True)
    ref, ref_eng = run_engine(
        engine_factory(model, params, **kw), reqs)
    got, eng = run_engine(
        engine_factory(model, params, mesh=mesh4, **kw), reqs)
    assert got == ref
    assert eng.backend.kv.stats["hit_tokens"] > 0
    assert eng.backend.kv.stats["hit_tokens"] == \
        ref_eng.backend.kv.stats["hit_tokens"]


# -- costmodel: the DES mirror of tensor parallelism -------------------------

def test_costmodel_model_shards():
    from repro.configs import REGISTRY
    from repro.serving.costmodel import InstanceCost

    cfg = REGISTRY["llama3.2-3b"]
    c1 = InstanceCost(cfg=cfg, chips=8)
    c4 = InstanceCost(cfg=cfg, chips=8, model_shards=4)
    # shards=1 must be a bit-exact no-op (every existing DES output holds)
    assert c1._collective_time(8) == 0.0
    # sharding adds all-reduce time on the same chip count...
    assert c4.decode_step_time(8) > c1.decode_step_time(8)
    assert c4.prefill_time(256) > c1.prefill_time(256)
    # ...and buys per-shard HBM headroom in exchange
    assert c4.hbm_bytes_per_shard() == pytest.approx(
        c1.hbm_bytes_per_shard() / 4)
    with pytest.raises(ValueError, match="divide"):
        InstanceCost(cfg=cfg, chips=8, model_shards=3)
    with pytest.raises(ValueError, match=">= 1"):
        InstanceCost(cfg=cfg, chips=8, model_shards=0)


def test_deployment_mirrors_model_shards():
    from repro.configs import REGISTRY
    from repro.core.testbed import default_deployment

    dep = default_deployment(REGISTRY["llama3.2-3b"], model_shards=4)
    assert dep.model_shards == 4
    assert dep.cost.model_shards == 4


# -- allocator replica consistency (hypothesis) ------------------------------

def _check_conservation(c: PagedKVCache):
    """Refcounts partition exactly the pages held by block tables, and
    every non-trash page is in exactly one of {referenced, LRU, free}."""
    held = Counter(p for t in c._tables.values() for p in t)
    assert dict(held) == c._ref, "refcounts out of sync with block tables"
    free, lru, ref = set(c._free), set(c._lru), set(c._ref)
    assert not (free & lru) and not (free & ref) and not (lru & ref)
    assert free | lru | ref == set(range(1, c.num_pages))


def _drive_replicas(seed: int, n_ops: int):
    """Drive one allocator replica per simulated shard through the SAME
    random op sequence (admit with prefix reuse, COW'd appends,
    speculative rollback, free). Per-shard page tables must stay
    bit-identical at every step — this is the contract that lets
    tensor-parallel serving keep ONE host-side allocator (or one per
    shard process) without any cross-shard sync."""
    caches = [PagedKVCache(20, 4, enable_prefix_cache=True)
              for _ in range(N_SHARDS)]
    rng = np.random.default_rng(seed)
    live: set[str] = set()
    next_id = 0

    def on_all(fn):
        """Apply one op to every replica; outcomes (result or OutOfPages)
        must agree, like shard processes seeing the same request stream."""
        outs = []
        for c in caches:
            try:
                outs.append(("ok", fn(c)))
            except OutOfPages:
                outs.append(("oom", None))
        assert all(o == outs[0] for o in outs[1:]), "replicas diverged"
        return outs[0]

    for _ in range(n_ops):
        op = ["admit", "append", "rollback", "free"][
            int(rng.integers(0, 4))]
        if op == "admit":
            # half the prompts share a leading page chain -> prefix hits
            base = int(rng.integers(0, 2)) * 1000
            n_tok = int(rng.integers(3, 14))
            toks = [base + t for t in range(n_tok)]
            sid = f"s{next_id}"
            next_id += 1
            status, _ = on_all(
                lambda c: c.allocate_with_prefix(sid, list(toks)))
            if status == "ok":
                on_all(lambda c: c.commit_prefix(sid, list(toks)))
                live.add(sid)
        elif op == "append" and live:
            sid = sorted(live)[int(rng.integers(0, len(live)))]
            # COW before the write, exactly as the decode step does
            on_all(lambda c: (c.writable_page(sid, c.length(sid)),
                              c.append_token(sid))[0] is not None)
        elif op == "rollback" and live:
            sid = sorted(live)[int(rng.integers(0, len(live)))]
            cur = caches[0].length(sid)
            tgt = int(rng.integers(max(cur - 3, 0), cur + 1))
            on_all(lambda c: c.rollback_to(sid, tgt))
        elif op == "free" and live:
            sid = sorted(live)[int(rng.integers(0, len(live)))]
            on_all(lambda c: c.free(sid))
            live.discard(sid)
        snaps = [c.snapshot() for c in caches]
        assert all(s == snaps[0] for s in snaps[1:]), \
            "allocator replicas drifted apart"
        _check_conservation(caches[0])


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 7])
def test_allocator_replicas_never_diverge(seed):
    _drive_replicas(seed, 40)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**32 - 1), n_ops=st.integers(8, 60))
    def test_allocator_replicas_never_diverge_property(seed, n_ops):
        _drive_replicas(seed, n_ops)
