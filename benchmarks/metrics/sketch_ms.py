"""Mean over the statements that have one of the summed durations of
their ``sketch`` spans: whatever of a sketch aggregation still runs on
the host after the fetch, inside ``decode`` (or a fused lane's
``demux``) — a dense register block's estimate in float64 and its
rounding, or, for a program that took its HLL registers in the sparse
form and estimated them on the device, the cast of one integer a group
(PR 35). One span a sketch column, summed per statement. A mean, not a
median: the classes that carry the span differ by a factor (10,000
groups, 20 groups), and a mean moves by what any of them gains. None
where no record has the span: a program older than it, or a slice
without a sketch statement."""
from harness import spans

LAYER = "dispatch and demux (_run_agg*, sharedscan._dispatch)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    per_statement = spans.per_statement(run["records"], "sketch")
    if not per_statement:
        return None
    return sum(per_statement) / len(per_statement)
