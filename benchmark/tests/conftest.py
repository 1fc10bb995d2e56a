"""Tests of the benchmark's own yardstick: `python3 -m pytest benchmark/tests`.
None needs a chip, and none imports jax."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
