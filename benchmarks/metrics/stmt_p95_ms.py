"""95th percentile of client latency over every answered statement of
the window (a failed statement counts in ``failed``, not here)."""
from harness import stats

LAYER = "end to end"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def compute(run):
    return stats.percentile(stats.latencies(run["window"]["samples"]), 95)[0]
