"""Find an open-loop cell's knee: serve its traffic at several fixed rates
on one engine (one set-up), one window per rate, and print for each rate
the latencies and whether the backlog grew.

    python3 benchmarks/chip/tools/sweep.py --workload qwen4b-chat \\
        --seed 5 --seconds 30 --rates 1.5,2,2.5,3

A rate is sustained when the queue wait of the window's last quarter of
requests is not far above that of its first quarter and the drain after
the window is short. The cell's rate is then fixed at about 0.8 x the
highest sustained rate, in its traffic file.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def summary(rec) -> dict:
    rs = sorted((r for r in rec.requests if r.sent is not None),
                key=lambda r: r.due)
    q = max(1, len(rs) // 4)

    def wait(sub):
        w = [(r.admitted if r.admitted else rec.drain_end) - r.due
             for r in sub]
        return float(np.mean(w)) * 1e3

    ttft = [((r.frames[0][0] if r.frames else rec.drain_end) - r.due) * 1e3
            for r in rs]
    out = sum(k for r in rs for t, k in r.frames if rec.in_window(t))
    return {"requests": len(rs),
            "unfinished": sum(r.finished is None for r in rs),
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p90_ms": float(np.percentile(ttft, 90)),
            "wait_first_q_ms": wait(rs[:q]), "wait_last_q_ms": wait(rs[-q:]),
            "drain_s": rec.drain_end - rec.window[1],
            "output_tok_s_in_window": out / rec.seconds,
            "k1_share": sum(c[3] == 1 for c in rec.decode_calls)
            / max(1, len(rec.decode_calls))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    ses = harness.Session(HERE.parents[1], args.workload, args.seed)
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        mix = dict(ses.cell.traffic,
                   arrivals={"kind": "poisson", "rate_rps": rate})
        rec = ses.window(mix, args.seed + i + 1, args.seconds, False)
        print(json.dumps({"rate_rps": rate, **summary(rec)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
