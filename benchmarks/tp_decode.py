"""Tensor-parallel decode benchmark (real engine, simulated mesh, CPU).

Steady-state fused decode on a single device vs the same engine sharded
4-way over a simulated ``(data=1, model=4)`` mesh (qwen MHA reduced, so
the KV pool genuinely splits along its head axis). On real accelerators
the sharded path buys HBM headroom and per-chip FLOP reduction; on a
simulated CPU mesh every "device" shares the same cores plus all-reduce
overhead, so the interesting outputs are CORRECTNESS ratios, not a
speedup:

* token streams must be byte-identical across the two placements (the
  mesh-axis parity contract of tests/test_parity_matrix.py, here at
  benchmark batch/length scale);
* neither placement may ship a single logits tensor to the host
  (sampling stays replicated on the mesh);
* the sharded/single throughput ratio is recorded as an artifact trend
  line — no floor is enforced.

Needs >= 4 visible devices: run directly (``python -m
benchmarks.tp_decode`` fakes 8 CPU devices before jax initializes), or via
``benchmarks.run`` with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
in the environment. With fewer devices it stops and says so.

Writes ``results/benchmarks/tp_decode.json`` (``.fast.json`` under
--fast/--smoke).
"""
from __future__ import annotations

import copy
import json
import os
import sys
import time

# when executed directly, fake the mesh devices before jax initializes
if __name__ == "__main__":
    _x = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _x:
        os.environ["XLA_FLAGS"] = \
            (_x + " --xla_force_host_platform_device_count=8").strip()

import jax
import numpy as np

from benchmarks.common import csv_line, print_table
from repro.configs import REGISTRY, reduced
from repro.models import make_model
from repro.serving import backends
from repro.serving.engine import ContinuousBatchingEngine, EngineConfig
from repro.serving.request import InferenceRequest, SamplingParams

ARCH = "qwen1.5-4b"        # MHA: 4 kv heads / 4 shards -> true head split
SHARDS = 4
PAGE = 16
PROMPT_LEN = 24
SLOTS = 4
K = 4                      # fused decode steps per host sync
OUT_PATH = os.path.join("results", "benchmarks", "tp_decode.json")


def _requests(vocab, n, gen, seed=0):
    rng = np.random.default_rng(seed)
    return [InferenceRequest(
        model=ARCH,
        prompt_tokens=rng.integers(2, vocab, size=PROMPT_LEN).tolist(),
        request_id=f"r{i}",
        sampling=SamplingParams(max_tokens=gen, temperature=0.0))
        for i in range(n)]


def _mk_engine(model, params, gen, mesh):
    cfg = EngineConfig(
        max_slots=SLOTS, max_seq_len=PROMPT_LEN + gen + PAGE,
        backend="paged", page_size=PAGE, fused_decode=True,
        decode_steps_per_sync=K, mesh=mesh)
    return ContinuousBatchingEngine(model, params, cfg)


def _timed_pass(eng, reqs):
    for r in copy.deepcopy(reqs):
        eng.add_request(r)
    dec0 = eng.stats["decode_tokens"]
    rates = []
    outputs = {}
    t0 = time.perf_counter()
    prev = t0
    while eng.has_work():
        tok0 = eng.stats["decode_tokens"]
        for o in eng.step():
            outputs[o.request_id] = list(o.output_tokens)
        now = time.perf_counter()
        if eng.stats["decode_tokens"] > tok0:
            rates.append((eng.stats["decode_tokens"] - tok0) / (now - prev))
        prev = now
    wall = time.perf_counter() - t0
    return {
        "wall_s": wall,
        "decode_tokens": eng.stats["decode_tokens"] - dec0,
        "tok_per_s": (eng.stats["decode_tokens"] - dec0) / wall,
        "steady_tok_per_s": float(np.median(rates)),
        "outputs": outputs,
    }


def bench(gen: int) -> dict:
    from repro.launch.mesh import make_local_mesh

    cfg = reduced(REGISTRY[ARCH])
    model = make_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    reqs = _requests(cfg.vocab_size, SLOTS, gen, seed=2)
    modes = [("single", None), (f"tp{SHARDS}", make_local_mesh(1, SHARDS))]
    results, rows = [], []
    for name, mesh in modes:
        eng = _mk_engine(model, params, gen, mesh)
        _timed_pass(eng, _requests(cfg.vocab_size, SLOTS, gen, seed=1))
        backends.reset_transfer_stats()
        r = _timed_pass(eng, reqs)
        transfers = backends.TRANSFER_STATS["decode_logits_transfers"]
        for _ in range(2):     # best-of-3 vs shared-host contention
            r2 = _timed_pass(eng, reqs)
            if r2["steady_tok_per_s"] > r["steady_tok_per_s"]:
                r2["outputs"] = r["outputs"]
                r = r2
        r["mode"] = name
        r["logits_transfers"] = transfers
        assert transfers == 0, f"{name}: logits crossed to the host"
        results.append(r)
        rows.append([name, f"{r['steady_tok_per_s']:.0f}",
                     f"{r['wall_s']:.2f}", r["decode_tokens"], transfers])
        csv_line(f"tp_decode/{name}", r["wall_s"] * 1e6 / max(
            r["decode_tokens"], 1), f"tok_s={r['steady_tok_per_s']:.0f}")
    single, tp = results
    assert tp["outputs"] == single["outputs"], \
        "sharded decode diverged from single-device (token parity broken)"
    ratio = tp["steady_tok_per_s"] / single["steady_tok_per_s"]
    print_table(
        f"TP decode ({ARCH} reduced, B={SLOTS}, {gen} gen, K={K}, "
        f"{SHARDS} simulated shards)",
        ["mode", "steady tok/s", "wall s", "tokens", "logits->host"],
        rows, widths=[8, 12, 8, 8, 12])
    print(f"\nsharded/single throughput ratio: {ratio:.2f}x "
          f"(simulated mesh: collectives are pure overhead on CPU)")
    return {"modes": [{k: v for k, v in r.items() if k != "outputs"}
                      for r in results],
            "ratio_tp_vs_single": ratio,
            "tokens_identical": True}


def main(fast: bool = False, smoke: bool = False) -> dict:
    if jax.device_count() < SHARDS:
        raise SystemExit(
            f"tp_decode needs >= {SHARDS} devices, {jax.device_count()} "
            f"visible: set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"=8 before jax initializes (CPU), or run on {SHARDS} chips")
    gen = 32 if (smoke or fast) else 96
    out = {"arch": ARCH, "batch": SLOTS, "prompt_len": PROMPT_LEN,
           "gen_tokens": gen, "page_size": PAGE, "K": K,
           "model_shards": SHARDS, **bench(gen)}
    path = OUT_PATH.replace(".json", ".fast.json") if (fast or smoke) \
        else OUT_PATH
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {path}")
    return out


if __name__ == "__main__":
    main(fast="--fast" in sys.argv, smoke="--smoke" in sys.argv)
