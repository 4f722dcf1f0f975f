"""Geometric mean, over the cell's statement classes, of each class's
median client latency in the window (the TPC-H power metric's shape:
every class weighs the same)."""
from harness import stats

LAYER = "end to end"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def compute(run):
    return stats.geomean_of_class_medians(run["window"]["samples"])
