"""One traced run of a cell, with what the program's own instrumentation
gives beside the result line (``program_trace.py``).

    python3 benchmarks/chip/tools/engine_trace.py --workload <cell> \\
        --seed <n> --seconds <s>

The run is ``run.py --trace 1``'s (``harness.run``: the same set-up,
window, trace and check); its result line gains one key, ``engine_trace``:

- ``clamp_share``: the share (%) of the window's fused decode calls that
  ran below K, by the engine's reason counter (``k1_prefill``,
  ``k1_batch``, ``k_pool``), and ``clamps``, those counts;
- ``decode_sample_ms``: device ms of the ``sample`` scope in
  ``fused_decode`` programs per decode step in the trace; ``sample_s``
  and ``fused_decode_s``, that scope's and those programs' device
  seconds; ``decode_attention_s`` and ``fused_decode_kernel_s``, the
  ``decode_attention`` scope's and the decode-tail kernel's;
- ``decode_host_ms``: host ms in ``engine.decode.prep`` and
  ``engine.decode.unpack`` per ``engine.decode`` span in the trace;
- ``modules``: the names of the programs the trace holds; ``spans``:
  how many ``engine.`` spans of each name it holds;
- ``idle_gaps``: the longest device gaps, named by the innermost span of
  either kind;
- ``clock_offset_ms``: quartiles of (``engine.decode.wait``'s end - the
  device end of the nearest ``fused_decode`` run);
- ``reduce_s``, ``read_s``: seconds ``trace_reduce.reduce_trace`` and
  ``program_trace.read`` took; ``tpot_p90_ms`` of the traced run.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import program_trace as PT  # noqa: E402
import trace_reduce as TR  # noqa: E402


def _quartiles(v: list) -> list:
    return statistics.quantiles(v, n=4) if len(v) > 1 else list(v)


def summarize(got: dict) -> dict:
    """``engine_trace`` from what one traced run recorded."""
    rec = got["rec"]
    w0, w1 = rec.window
    snaps = got["snaps"]
    opened = next((s for t, s in snaps if t >= w0), got["final"])
    closed = next((s for t, s in snaps if t >= w1), got["final"])
    out = {"clamp_share": PT.clamp_shares(opened, closed),
           "clamps": {k: closed[k] - opened[k] for k in
                      ("k1_prefill", "k1_batch", "k_pool", "decode_syncs")},
           "tpot_p90_ms": harness.spec_mod.Spec(got["root"]).reader(
               "tpot_p90_ms")(rec)}
    tr, pt = rec.trace, got.get("pt")
    if tr is None or pt is None:
        return out
    out.update(
        decode_sample_ms=PT.decode_sample_ms(pt, rec),
        sample_s=PT.scope_seconds(pt, PT.SAMPLE_IN_DECODE),
        fused_decode_s=TR.module_seconds(tr, r"^jit_fused_decode$"),
        decode_attention_s=PT.scope_seconds(pt, r"/decode_attention/"),
        fused_decode_kernel_s=TR.op_seconds(tr, r"^_fused_decode"),
        decode_host_ms=PT.decode_host_ms(pt),
        modules=sorted({name for name, _, _ in tr.modules}),
        spans=dict(collections.Counter(sp[0] for sp in pt.spans)),
        idle_gaps=PT.idle_gaps(tr, pt),
        clock_offset_ms=_quartiles(PT.clock_offset_ms(pt, tr.modules)),
        scope_conflicts=pt.conflicts,
        reduce_s=got["reduce_s"], read_s=got["read_s"])
    return out


def traced_run(root: Path, workload: str, seed: int, seconds: float,
               **run_kw) -> dict:
    """``harness.run`` with the trace on, recording the engine's stats
    before each step and reading the program's trace beside the
    harness's reduction; returns the result line with ``engine_trace``."""
    got = {"root": root}
    reduce_trace, window = TR.reduce_trace, harness.Session.window

    def reduce_and_read(path):
        t = time.perf_counter()
        tr = reduce_trace(path)
        got["reduce_s"] = time.perf_counter() - t
        t = time.perf_counter()
        got["pt"] = PT.read(path, tr.window)
        got["read_s"] = time.perf_counter() - t
        return tr

    def observed_window(ses, mix, seed, seconds, trace, *, fault=None):
        snaps = []

        def observe(eng):
            if fault is not None:
                fault(eng)
            step = eng.step

            def observed_step():
                snaps.append((time.perf_counter(), dict(eng.stats)))
                return step()
            eng.step = observed_step

        rec = window(ses, mix, seed, seconds, trace, fault=observe)
        got.update(rec=rec, snaps=snaps, final=dict(ses.eng.stats))
        return rec

    TR.reduce_trace, harness.Session.window = reduce_and_read, \
        observed_window
    try:
        res = harness.run(root, workload, seed, seconds, True, **run_kw)
    finally:
        TR.reduce_trace, harness.Session.window = reduce_trace, window
    check = res.pop("check")            # the line ends with it, as run.py's
    return {**res, "engine_trace": summarize(got), "check": check}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    res = traced_run(ROOT, args.workload, args.seed, args.seconds,
                     t_start=T_START)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
