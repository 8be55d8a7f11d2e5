"""One untraced run of a cell, with where its window's time went beside the
result line: for runs whose end-to-end metric reads far apart, whether the
engine did other work or the same work took longer, and where.

    python3 benchmarks/chip/tools/timeline.py --workload <cell> \\
        --seed <n> --seconds <s> [--control 0|1]

The run is ``run.py``'s (``harness.run``: the same set-up, window and
check, untraced); its result line gains one key, ``timeline``, before
``check``:

- ``stats``: the engine's counters from the window's opening to the end
  of the drain (preemptions, restores, prefill tokens computed and found
  in the cache, steps, decode syncs, clamps);
- ``makespan_s``: the last final frame after the window's opening;
- ``prefill_s``, ``decode_s``: host seconds in ``prefill_chunk`` (it
  returns once its program is dispatched) and in ``fused_decode`` (it
  waits for the tokens, and so for the chunks queued before it);
  ``outside_s``: the rest of the makespan, the harness's and the
  engine's host work between those calls;
- ``decode_ms_per_step``: quartiles of a fused call's host time per step
  it ran, by K;
- ``gaps``: the longest host intervals between two backend calls, and
  ``slow_decodes``, the longest fused calls, each ``[start s after the
  opening, seconds]``;
- ``gc``: Python's collections between the opening and the drain's end,
  by generation: how many and their seconds, and the longest;
- ``rusage``: the process's CPU seconds and context switches over the
  same time, and the load average at both ends; ``steal``: the share of
  the machine's CPU time that its hypervisor gave to others over the same
  time (Linux's ``/proc/stat``). A slower host shows as more CPU seconds
  for the same work: in ``nivcsw`` and the load where other processes
  take the cores, in ``steal`` where other machines do.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

TOP = 8


def _load() -> list:
    try:
        return list(os.getloadavg())
    except OSError:
        return []


def _cpu_ticks() -> list:
    """The machine's CPU time by kind (user, nice, system, idle, iowait,
    irq, softirq, steal), in clock ticks; empty where Linux's counters are
    not there."""
    try:
        with open("/proc/stat") as f:
            return [int(x) for x in f.readline().split()[1:9]]
    except (OSError, ValueError):
        return []


def _usage() -> dict:
    u = resource.getrusage(resource.RUSAGE_SELF)
    return {"utime": u.ru_utime, "stime": u.ru_stime, "nvcsw": u.ru_nvcsw,
            "nivcsw": u.ru_nivcsw, "majflt": u.ru_majflt}


def _top(pairs: list) -> list:
    return sorted(pairs, key=lambda p: -p[1])[:TOP]


def summarize(got: dict) -> dict:
    """``timeline`` from what one run recorded."""
    rec = got["rec"]
    w0 = rec.window[0]
    ends = [r.finished for r in rec.requests if r.finished is not None]
    makespan = (max(ends) - w0) if ends else None
    pre = [(t0, t1) for t0, t1, _, _ in rec.prefill_calls if t0 >= w0]
    dec = [c for c in rec.decode_calls if c[0] >= w0]
    calls = sorted(pre + [(c[0], c[1]) for c in dec])
    gaps = [(round(a1 - w0, 4), b0 - a1)
            for (_, a1), (b0, _) in zip(calls, calls[1:])]
    per_k = collections.defaultdict(list)
    for t0, t1, _, k_run, _ in dec:
        per_k[k_run].append(1e3 * (t1 - t0) / max(k_run, 1))
    in_calls = sum(t1 - t0 for t0, t1 in calls)
    gcs = [g for g in got["gc"] if w0 <= g[0] <= rec.drain_end]
    by_gen = collections.defaultdict(lambda: [0, 0.0])
    for _, gen, sec in gcs:
        by_gen[gen][0] += 1
        by_gen[gen][1] += sec
    u0, u1 = got["usage"]
    ticks = [b - a for a, b in zip(got["ticks0"], got["ticks1"])]
    return {
        "stats": {k: v - got["stats0"].get(k, 0)
                  for k, v in got["stats1"].items()
                  if v - got["stats0"].get(k, 0)},
        "makespan_s": makespan,
        "prefill_s": sum(t1 - t0 for t0, t1 in pre),
        "decode_s": sum(c[1] - c[0] for c in dec),
        "outside_s": (makespan - in_calls) if makespan else None,
        "calls": {"prefill": len(pre), "decode": len(dec)},
        "decode_ms_per_step": {
            str(k): statistics.quantiles(v, n=4) if len(v) > 1 else v
            for k, v in sorted(per_k.items())},
        "gaps": _top(gaps),
        "slow_decodes": _top([(round(c[0] - w0, 4), c[1] - c[0])
                              for c in dec]),
        "gc": {str(g): v for g, v in sorted(by_gen.items())},
        "gc_longest": max((s for _, _, s in gcs), default=0.0),
        "rusage": {k: u1[k] - u0[k] for k in u0},
        "loadavg": [got["load0"], got["load1"]],
        "steal": ticks[7] / sum(ticks) if len(ticks) == 8 and sum(ticks)
        else None}


def timed_run(root: Path, workload: str, seed: int, seconds: float,
              **run_kw) -> dict:
    """``harness.run`` untraced, recording the engine's counters, Python's
    collections and the process's usage over the window and the drain;
    returns the result line with ``timeline``."""
    got = {"gc": []}
    window = harness.Session.window
    started = {}

    def on_gc(phase, info):
        if phase == "start":
            started["t"] = time.perf_counter()
        elif "t" in started:
            t = started.pop("t")
            got["gc"].append((t, info["generation"],
                              time.perf_counter() - t))

    def observed_window(ses, mix, seed, seconds, trace, *, fault=None):
        def observe(eng):
            if fault is not None:
                fault(eng)
            got.update(stats0=dict(eng.stats), usage0=_usage(),
                       load0=_load(), ticks0=_cpu_ticks())

        gc.callbacks.append(on_gc)
        try:
            rec = window(ses, mix, seed, seconds, trace, fault=observe)
        finally:
            gc.callbacks.remove(on_gc)
        got.update(rec=rec, stats1=dict(ses.eng.stats),
                   usage=(got.pop("usage0"), _usage()), load1=_load(),
                   ticks1=_cpu_ticks())
        return rec

    harness.Session.window = observed_window
    try:
        res = harness.run(root, workload, seed, seconds, False, **run_kw)
    finally:
        harness.Session.window = window
    check = res.pop("check")            # the line ends with it, as run.py's
    return {**res, "timeline": summarize(got), "check": check}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    res = timed_run(ROOT, args.workload, args.seed, args.seconds,
                    control=bool(args.control), t_start=T_START)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
