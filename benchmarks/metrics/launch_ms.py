"""Median over dispatches of the ``dispatch.launch`` span: the call of
the compiled program, which enqueues it (host clock)."""
from harness import spans

LAYER = "dispatch and demux (_run_agg*, sharedscan._dispatch)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    return spans.median_per_span(run["records"], "dispatch.launch")
