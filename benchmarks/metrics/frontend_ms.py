"""Median of client wall time minus the statement's ``total_ms`` in
``/history`` (same process, same ``perf_counter``): HTTP, JSON encode,
the handler thread — what the server adds around the SQL session."""
from harness import stats

LAYER = "HTTP front end (server/http.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    return stats.median(s["ms"] - r["total_ms"] for s, r in run["pairs"])
