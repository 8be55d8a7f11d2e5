"""Summarize result lines of repeated runs: per file set, each metric's
median and quartile spread, and each compared number against its limit.

    python3 benchmarks/chip/tools/spread.py 'out/a_*.out' 'out/b_*.out'

Each argument is a glob of files whose last line is a run's result. The
spread is (Q3 - Q1) / median with Python's ``statistics.quantiles(n=4)``,
the measure the benchmark's bounds are set from (about five times the
widest spread of a metric over the cells, and never under 1%).
"""
from __future__ import annotations

import glob
import json
import statistics
import sys


def last_json(path: str):
    try:
        lines = open(path).read().strip().splitlines()
        return json.loads(lines[-1]) if lines else None
    except (OSError, ValueError):
        return None


def main() -> int:
    for pattern in sys.argv[1:]:
        runs = [(p, last_json(p)) for p in sorted(glob.glob(pattern))]
        ok = [(p, r) for p, r in runs if r]
        print(f"== {pattern}: {len(ok)} results of {len(runs)} runs")
        for p, r in ok:
            chk = {k: round(v["value"], 5) for k, v in r["check"].items()}
            ctl = r.get("control")
            print(f"  {p.rsplit('/', 1)[-1]}: correct={r['correct']} "
                  f"attempted={r['attempted']} failed={r['failed']} "
                  f"check={chk} control={ctl} "
                  f"peak={r['device']['memory_peak_bytes']} "
                  + " ".join(f"{k}={v['value']:.6g}"
                             for k, v in r["metrics"].items()))
        names = sorted({k for _, r in ok for k in r["metrics"]})
        for k in names:
            vals = [r["metrics"][k]["value"] for _, r in ok
                    if k in r["metrics"]]
            if len(vals) >= 2:
                q1, med, q3 = statistics.quantiles(vals, n=4)
                print(f"  {k}: n={len(vals)} median={med:.6g} "
                      f"q1={q1:.6g} q3={q3:.6g} "
                      f"spread={(q3 - q1) / med:.4%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
