"""Statements completed per device dispatch over the slice's history
records: 1.0 solo, 8.0 when every refresh of eight tiles fuses."""

LAYER = "admission and coalescing (wlm/, parallel/sharedscan.py)"
UNIT = "stmts"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "stmts_per_s"


def compute(run):
    n = sum(int(r.get("n_dispatch") or 0) for r in run["records"])
    return len(run["records"]) / n if n else None
