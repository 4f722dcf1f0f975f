"""Mean over the records that carry ``subquery_fetch_bytes`` of that
counter in KB (1,000 bytes): what a statement's subqueries copied back
from the device — the part of its ``fetch_bytes`` under ``subquery``
spans (PR 33). It tells which HAVING site an inner took: a HAVING
applied on the device fetches the survivors (KB), one applied on the
host fetches every group (16 bytes a group: ~24 MB for q18's inner at
SF1, which lifts the mean of a slice a hundredfold). A mean, so that it
moves by what any class's fetch loses or gains (a median over two
classes sits on one of them by the slice's parity). None where no
record carries the counter."""

LAYER = "dispatch and demux (_run_agg*, sharedscan._dispatch)"
UNIT = "KB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "stmt_p95_ms"


def compute(run):
    kb = [r["subquery_fetch_bytes"] / 1000.0 for r in run["records"]
          if r.get("subquery_fetch_bytes") is not None]
    return sum(kb) / len(kb) if kb else None
