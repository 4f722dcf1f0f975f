"""A history record's span tree, read: ``stats["spans"]`` rows
``[name, start_us, dur_us, parent]`` from the root's start ``t0_ns``
(``time.perf_counter_ns``, the clock of the samples' ``t0``/``t1``)
become spans with absolute times in seconds. A span still open when the
record was read (``dur_us`` null: the handler's last spans, the root)
stays in the list, so that parents keep their index, with ``end`` None;
every reader here skips it. A record without ``spans`` — a program
older than the span tree — reads as None, never as an exception."""

import collections

from . import stats

Span = collections.namedtuple("Span", "name start end parent")


def of(rec):
    """[Span] of one record in the record's order (index = row), or
    None when the record carries no tree."""
    rows, t0 = rec.get("spans"), rec.get("t0_ns")
    if not rows or t0 is None:
        return None
    t0 = t0 / 1e9
    return [Span(name, t0 + start / 1e6,
                 None if dur is None else t0 + (start + dur) / 1e6, parent)
            for name, start, dur, parent in rows]


def trees(records):
    """The records' trees, those without one left out."""
    return [t for t in map(of, records) if t is not None]


def ms(span):
    return (span.end - span.start) * 1000.0


def closed(tree, *names):
    """The closed spans of ``tree`` called one of ``names``."""
    return [s for s in tree if s.name in names and s.end is not None]


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def self_ms(tree, i=0):
    """Duration of span ``i`` minus the union of its closed children,
    in ms: the time in it that no span covers. None while it is open."""
    me = tree[i]
    if me.end is None:
        return None
    kids = union((max(s.start, me.start), min(s.end, me.end))
                 for s in tree if s.parent == i and s.end is not None)
    return ms(me) - sum(b - a for a, b in kids if b > a) * 1000.0


def per_statement(records, *names):
    """Per record that has a closed span of these names: the sum of
    their durations in ms."""
    out = []
    for tree in trees(records):
        found = closed(tree, *names)
        if found:
            out.append(sum(ms(s) for s in found))
    return out


def median_per_statement(records, *names):
    return stats.median(per_statement(records, *names))


def median_per_span(records, name):
    """Median over every closed span called ``name`` (one dispatch has
    one launch, one wait, one fetch; a statement may dispatch twice)."""
    return stats.median(ms(s) for tree in trees(records)
                        for s in closed(tree, name))


def in_flight(tree):
    """[(launch start, wait end)] per dispatch of one statement: the
    only intervals in which the device can be busy for it."""
    out = []
    for i, s in enumerate(tree):
        if s.name != "dispatch.launch":
            continue
        wait = next((w for w in tree[i + 1:] if w.name == "dispatch.wait"
                     and w.parent == s.parent and w.end is not None), None)
        if wait is not None:
            out.append((s.start, wait.end))
    return out
