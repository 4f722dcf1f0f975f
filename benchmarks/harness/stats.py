"""Metric arithmetic over samples. A sample is a dict with at least
``cls``, ``t0``, ``t1`` (seconds on ``time.perf_counter``), ``ms`` and
``ok``; only ``ok`` samples carry a latency."""

import math
import statistics


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def percentile(values, q):
    """(value, sample count) — linear interpolation between order
    statistics (numpy's default); (None, 0) on no samples."""
    xs = sorted(values)
    if not xs:
        return None, 0
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo), len(xs)


def geomean(values):
    values = list(values)
    if not values or any(v <= 0 for v in values):
        return None
    return math.exp(sum(math.log(v) for v in values) / len(values))


def latencies(samples, cls=None):
    return [s["ms"] for s in samples
            if s["ok"] and (cls is None or s["cls"] == cls)]


def class_medians(samples):
    """{class: (median ms, sample count)} over the answered samples."""
    by = {}
    for s in samples:
        if s["ok"]:
            by.setdefault(s["cls"], []).append(s["ms"])
    return {c: (statistics.median(v), len(v)) for c, v in sorted(by.items())}


def geomean_of_class_medians(samples):
    """Geometric mean over classes of each class's median latency."""
    return geomean(m for m, _ in class_medians(samples).values())


def phase_sum(rec, names=(), prefixes=()):
    """Sum in ms of a history record's phases picked by exact name or
    by prefix."""
    ph = rec.get("phases") or {}
    return sum(v for k, v in ph.items()
               if k in names or any(k.startswith(p) for p in prefixes))
