"""Benchmark orchestrator: one harness per paper table/figure.

  rate_sweep    Fig. 3  FIRST vs vLLM-Direct across request rates
  autoscale     Fig. 4  1->4 instance scaling under saturation
  external_api  Fig. 5  FIRST (8B) vs rate-limited external API
  concurrency   Tbl. 1  WebUI closed-loop session sweep
  batch_mode    §5.3.1  online vs dedicated offline batch job
  engine_step   (real)  CPU wall-clock of the JAX engine, reduced configs
  prefix_cache  (real)  KV prefix reuse + chunked-prefill ITL, JSON output
  decode_loop   (real)  fused decode fast path vs legacy, JSON output
  spec_decode   (real)  draft-and-verify speculative decoding, JSON output
  qos_preemption (real) interactive TTFT under a batch flood: FCFS vs
                        priority vs priority+preemption, JSON output
  api_stream    (DES)   /v1 token streaming at the gateway: parity,
                        TTFT/ITL, cancel propagation, JSON output
  tp_decode     (real)  tensor-parallel fused decode on a simulated
                        4-shard mesh: token parity + throughput ratio,
                        JSON output
  chaos_soak    (DES)   seeded fault schedule against the federation:
                        exactly-once conservation, mid-stream failover
                        resume, bounded TTFT inflation, JSON output
  hot_pool      (DES)   hot-node pool vs cold-start-on-demand on a bursty
                        replay trace, plus disaggregated prefill/decode
                        handoff token conservation, JSON output
  roofline      §Roofline  achieved-vs-peak bandwidth for the serving
                        attention ops (JSON output), plus derived terms
                        from results/dryrun/*.json when present

``python -m benchmarks.run [--fast] [--smoke] [--only NAME]``.
``--smoke`` runs only the real-engine perf-path suites at minimal sizes
with their acceptance gates on — the CI regression check.  Machine-readable
lines are prefixed ``CSV,name,us_per_call,derived``.
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import (api_stream, autoscale, batch_mode, chaos_soak,
                        concurrency, decode_loop, engine_step, external_api,
                        hot_pool, prefix_cache, qos_preemption, rate_sweep,
                        roofline, spec_decode, tp_decode)
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "rate_sweep": rate_sweep.main,
    "autoscale": autoscale.main,
    "external_api": external_api.main,
    "concurrency": concurrency.main,
    "batch_mode": batch_mode.main,
    "engine_step": engine_step.main,
    "prefix_cache": prefix_cache.main,
    "decode_loop": decode_loop.main,
    "spec_decode": spec_decode.main,
    "qos_preemption": qos_preemption.main,
    "api_stream": api_stream.main,
    "tp_decode": tp_decode.main,
    "chaos_soak": chaos_soak.main,
    "hot_pool": hot_pool.main,
    "roofline": roofline.main,
}

# real-engine suites with self-enforced acceptance thresholds: these are
# the ones a perf-path regression breaks, so CI runs exactly these
SMOKE_SUITES = ["engine_step", "prefix_cache", "decode_loop", "spec_decode",
                "qos_preemption", "api_stream", "tp_decode", "chaos_soak",
                "hot_pool", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced request counts / fewer cells")
    ap.add_argument("--smoke", action="store_true",
                    help="perf-path regression check: real-engine suites "
                         "only, minimal sizes (implies --fast)")
    ap.add_argument("--only", default=None, choices=[*SUITES, None])
    args = ap.parse_args()
    enable_compile_cache()

    if args.only:
        names = [args.only]
    elif args.smoke:
        names = list(SMOKE_SUITES)
    else:
        names = list(SUITES)
    failures = []
    for name in names:
        print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}")
        t0 = time.time()
        kw = {"fast": args.fast or args.smoke}
        if args.smoke and name in ("decode_loop", "spec_decode",
                                   "qos_preemption", "api_stream",
                                   "tp_decode", "chaos_soak", "hot_pool",
                                   "roofline"):
            kw["smoke"] = True
        if args.smoke and name == "prefix_cache":
            kw["min_speedup"] = 1.5     # shared-runner wall-clock headroom
        try:
            SUITES[name](**kw)
            print(f"[{name}] done in {time.time() - t0:.1f}s")
        except (Exception, SystemExit):         # noqa: BLE001
            # acceptance gates signal via SystemExit — catch it so one
            # failed gate still lets the remaining suites run and the
            # failure summary aggregate
            failures.append(name)
            print(f"[{name}] FAILED:\n{traceback.format_exc()}")
    if failures:
        raise SystemExit(f"benchmark suites failed: {failures}")
    print("\nall benchmark suites passed")


if __name__ == "__main__":
    main()
