"""Share of the traced slice in which no op ran on the device: 1 minus
the union of device-op intervals over the slice's length."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "stmts_per_s"


def compute(run):
    if not run["trace"] or not run["slice_s"]:
        return None
    return 100.0 * (1.0 - run["trace"]["busy_s"] / run["slice_s"])
