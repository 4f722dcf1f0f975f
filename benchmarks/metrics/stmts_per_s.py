"""Statements answered correctly per second of window (window: first
send to last answer)."""

LAYER = "end to end"
UNIT = "stmts/s"
BETTER = "higher"
SOURCE = "host_clock"


def compute(run):
    w = run["window"]
    n = sum(1 for s in w["samples"] if s["ok"])
    return n / w["seconds"] if n and w["seconds"] else None
