"""``pytest benchmarks/tests -q`` — by hand, on the CPU, in seconds.
Tier-1 (``pytest tests/``) does not collect these."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)
