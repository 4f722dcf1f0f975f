"""Share of the slice in which some launch was in flight: the union over
all statements of [``dispatch.launch`` start, ``dispatch.wait`` end],
over first sample sent -> last sample answered. The device can only be
busy inside it, so 100 - ``device_idle_pct`` <= this, and the difference
is time a launch was in flight with the chip idle."""
from harness import spans

LAYER = "device"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"
MOVES = "stmts_per_s"


def compute(run):
    trees = spans.trees(run["records"])
    if not trees or not run["samples"]:
        return None
    t0 = min(s["t0"] for s in run["samples"])
    t1 = max(s["t1"] for s in run["samples"])
    if t1 <= t0:
        return None
    flown = spans.union((max(a, t0), min(b, t1))
                        for tree in trees for a, b in spans.in_flight(tree))
    return 100.0 * sum(b - a for a, b in flown if b > a) / (t1 - t0)
