"""Print what a profiler trace holds: its planes, their lines, how many
events each line has, and the names and stats of a few events of each --
for reading a trace by hand before writing a reader against it.

    python3 benchmarks/chip/tools/dump_trace.py <file.xplane.pb> [n]
"""
from __future__ import annotations

import collections
import sys


def main() -> int:
    from jax.profiler import ProfileData

    path = sys.argv[1]
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events"
                  + (f", {evs[0].start_ns:.0f}..{evs[-1].end_ns:.0f} ns"
                     if evs else ""))
            names = collections.Counter(ev.name for ev in evs)
            for name, k in names.most_common(n):
                ev = next(e for e in evs if e.name == name)
                stats = {a: str(b)[:120] for a, b in ev.stats}
                print(f"    {k:7d} x {name[:100]!r} dur {ev.duration_ns:.0f}"
                      f" {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
