"""Device busy time in the traced slice (union of the op intervals in
the profiler trace) divided by the statements completed in it."""

LAYER = "kernels (ops/pallas_groupby.py, ops/pallas_wave.py, XLA tiers)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "stmts_per_s"


def compute(run):
    n = sum(1 for s in run["samples"] if s["ok"])
    if not run["trace"] or not n:
        return None
    return run["trace"]["busy_s"] * 1000.0 / n
