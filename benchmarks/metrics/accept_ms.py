"""Median over statements of the ``http.accept`` span: from the instant
the server's accept returned the connection to the first line of the
handler's body — the connection's thread created and started, the
request line and headers read. The hand-off queue ahead of the handler,
part of ``frontend_ms`` that no span named before. The root
``http.request`` starts at the accept since this span exists, and the
span covers exactly what the root gained, so ``uncovered_ms`` keeps its
meaning. None where no record has the span: a program older than it."""
from harness import spans

LAYER = "HTTP front end (server/http.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    return spans.median_per_span(run["records"], "http.accept")
