"""Mean over the statements that have one of the summed durations of
their ``subquery`` spans: each execution, inside the statement, of an
inlined subquery or an engine-assisted derived table — its own planning,
admission, bind, dispatch, decode and result (PR 33). The span's parent
is the statement's root wherever it ran, so only the root's are summed
(a subquery's own subquery nests inside it). A mean, not a median: the
classes that carry the span differ by a factor (q15's two short ones,
q18's 1.5 M-group inner), a median over them sits on one class or the
other by the slice's parity, and a mean moves by what any of them gains.
None where no record has the span: a program older than the span, or a
slice without a subquery statement."""
from harness import spans

LAYER = "SQL session and planner (sql/session.py, planner/)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    per_statement = []
    for tree in spans.trees(run["records"]):
        found = [s for s in spans.closed(tree, "subquery") if s.parent == 0]
        if found:
            per_statement.append(sum(spans.ms(s) for s in found))
    if not per_statement:
        return None
    return sum(per_statement) / len(per_statement)
