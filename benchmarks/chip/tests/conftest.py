"""The benchmark's own tests run on the CPU, at sizes a test run holds.
They put the benchmark's directory on the path, as ``run.py`` does."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
