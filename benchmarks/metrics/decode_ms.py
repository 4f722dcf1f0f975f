"""Median over statements of the ``decode`` span: a solo statement's
finals -> ``QueryResult`` (group selection, dictionary decode, sketch
estimates, post-aggregations, HAVING, limit)."""
from harness import spans

LAYER = "dispatch and demux (_run_agg*, sharedscan._dispatch)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    return spans.median_per_statement(run["records"], "decode")
