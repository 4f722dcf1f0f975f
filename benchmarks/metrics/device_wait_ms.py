"""Median over dispatches of the ``dispatch.wait`` span:
``block_until_ready`` on what the launch returned. HOST clock — the
host's view of device time, wake-up included; the device's own busy time
is ``device_ms_per_stmt``."""
from harness import spans

LAYER = "dispatch and demux (_run_agg*, sharedscan._dispatch)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    return spans.median_per_span(run["records"], "dispatch.wait")
