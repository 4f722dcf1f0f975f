"""Bytes the slice's statements must read (``store.scan_bytes``: padded
rows x stored item sizes of the columns each class lists) divided by
device busy time and by the device kind's HBM peak. The whole slice's
achieved share of the peak — NOT a per-kernel roofline share."""

LAYER = "kernels (ops/pallas_groupby.py, ops/pallas_wave.py, XLA tiers)"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "stmts_per_s"


def compute(run):
    if not run["trace"] or not run["peaks"] or not run["scan_bytes"]:
        return None
    nbytes = sum(run["scan_bytes"][s["cls"]]
                 for s in run["samples"] if s["ok"])
    return 100.0 * nbytes / run["trace"]["busy_s"] \
        / (run["peaks"]["hbm_gbps"] * 1e9)
