"""Median over statements of ``parse`` plus every ``plan.*`` phase."""
from harness import stats

LAYER = "SQL session and planner (sql/session.py, planner/)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    return stats.median(stats.phase_sum(r, ("parse",), ("plan.",))
                        for r in run["records"])
