"""Median over statements of the ``coalesce.hold`` span: from joining a
shared-scan group to the group's close — the leader in its hold window,
a follower parked. Arrival skew of a burst: the first tile to arrive
holds longest, the one that fills the group not at all."""
from harness import spans

LAYER = "admission and coalescing (wlm/, parallel/sharedscan.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    return spans.median_per_statement(run["records"], "coalesce.hold")
