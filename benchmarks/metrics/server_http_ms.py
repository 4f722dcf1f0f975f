"""Median over statements of ``http.read`` + ``http.encode`` +
``http.write``: what the server's handler does around the SQL session.
``frontend_ms`` minus this is client, socket and thread hand-off. A
record read before its ``http.write`` closed is left out."""
from harness import spans, stats

LAYER = "HTTP front end (server/http.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"

PARTS = ("http.read", "http.encode", "http.write")


def compute(run):
    out = []
    for tree in spans.trees(run["records"]):
        found = spans.closed(tree, *PARTS)
        if {s.name for s in found} == set(PARTS):
            out.append(sum(spans.ms(s) for s in found))
    return stats.median(out)
