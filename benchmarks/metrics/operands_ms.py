"""Median over statements of the ``bind.operands`` span: resolving the
statement's filter literals on the host (dictionary value -> code, date
-> days, number -> the column's compare type) and packing them into the
program's one small operand (its upload rides the program's launch on
one device). None where no record has the span."""
from harness import spans

LAYER = "bind (QueryEngine._bind_arrays)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    return spans.median_per_statement(run["records"], "bind.operands")
