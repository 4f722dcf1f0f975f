"""Median over statements of the root span's self time: its duration
minus the union of its children — what no span covers between the
handler's first line and its last. A record whose root was still open
when it was read is left out."""
from harness import spans, stats

LAYER = "SQL session and planner (sql/session.py, planner/)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    own = (spans.self_ms(tree) for tree in spans.trees(run["records"]))
    return stats.median(v for v in own if v is not None)
