"""Median over statements of ``total_ms`` minus the sum of all phases:
engine host code no phase covers (in a storm, the coalescer's hold).
Phases are inclusive, so a nested phase is subtracted twice."""
from harness import stats

LAYER = "SQL session and planner (sql/session.py, planner/)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    return stats.median(
        r["total_ms"] - sum((r.get("phases") or {}).values())
        for r in run["records"])
