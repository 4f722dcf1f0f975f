"""Median over statements of the ``wlm.admit`` phase."""
from harness import stats

LAYER = "admission and coalescing (wlm/, parallel/sharedscan.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    return stats.median(stats.phase_sum(r, ("wlm.admit",))
                        for r in run["records"])
