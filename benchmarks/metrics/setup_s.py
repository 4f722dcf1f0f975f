"""Process start to the first measured statement: imports, chip start,
store build or recover, upload, warm-up and its compiles."""

LAYER = "end to end"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def compute(run):
    return run["ready_s"]
