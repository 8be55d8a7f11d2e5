"""One cell of the on-chip benchmark, run once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout and the cell's files
under this directory (see ``spec.py``), runs the cell on the chips of this
machine (see ``harness.py``) and prints one JSON object as the last line
of standard output: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device`` and, traced, ``breakdown``; last of all ``check``,
each number compared with its limit, which also end standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits with
a nonzero code and prints no result. ``--control 1`` also judges the
int8 control by the same check, on the same sample, and prints its
verdict and gaps under ``control``; the benchmark's own runs leave it
off.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import harness

    res = harness.run(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), control=bool(args.control),
                      t_start=T_START)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
