"""From a profiler trace to device busy time, idle gaps and their labels.

Two halves, so the arithmetic is tested without a trace file:

1. ``read_events(path)``: ``.xplane.pb`` -> [(plane, line, name,
   start_ns, duration_ns)], with nothing but ``jax.profiler.ProfileData``.
2. ``reduce_events(events, labels)``: that list -> per-device busy
   union, the ops with most time, and the longest gaps between device
   ops, each labelled with the harness's own host annotation (a
   ``TraceAnnotation`` whose name starts with one of ``labels``) that
   covers most of it.

What a TPU trace looks like (read by hand before this was written;
PERF.md section 5): one plane per chip named ``/device:TPU:<n>`` whose
line ``XLA Ops`` holds one event per executed HLO op, named by its whole
HLO line (``%fusion.53 = s32[...] fusion(...)``; a ``%while`` encloses
its body's ops, hence the union), beside ``XLA Modules`` (one event per
program), ``Async XLA Ops`` and others. Host threads are lines of
``/host:CPU``; a ``TraceAnnotation`` is an event on its thread's line
(``python3``), on the same clock as the device lines (nanoseconds from
the trace's start). Busy time is the union of the ``XLA Ops`` intervals.

``python benchmarks/harness/reduce_trace.py <file.xplane.pb>`` prints
the planes, lines and first events of a trace."""

import glob
import os
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def read_events(path):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


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


def op_name(name):
    """A TPU trace names an op by its whole HLO line (``%fusion.53 =
    s32[2097152]{...} fusion(...)``); keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def _label(gap, spans):
    """Name of the span that overlaps ``gap`` most (the shorter span
    wins a tie: the innermost annotation); ``unlabelled`` when none
    covers half of it."""
    a, b = gap
    best, best_key = "unlabelled", ((b - a) / 2, float("-inf"))
    for name, s, e in spans:
        ov = min(b, e) - max(a, s)
        if (ov, -(e - s)) > best_key:
            best, best_key = name, (ov, -(e - s))
    return best


def reduce_events(events, labels=(), top=10):
    """Returns None when no device op is in the list (a CPU rehearsal),
    else a dict: ``chips``, ``busy_s`` (mean over chips of the union of
    op intervals), ``span_s`` (mean first-op-start to last-op-end),
    ``device_ops`` [[name, seconds]] and ``idle_gaps`` [[label, seconds]]
    (``top`` longest of each), ``n_ops``, ``n_gaps``."""
    per_chip, spans = {}, []
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE_PREFIX) and line in OP_LINES:
            per_chip.setdefault(plane, []).append(
                (op_name(name), start, start + dur))
        elif plane == HOST_PLANE and name.startswith(tuple(labels)):
            spans.append((name, start, start + dur))
    if not per_chip:
        return None
    busy, span, ops, gaps, n_ops = [], [], {}, [], 0
    for evs in per_chip.values():
        merged = union((s, e) for _, s, e in evs)
        busy.append(sum(e - s for s, e in merged))
        span.append(merged[-1][1] - merged[0][0])
        gaps.extend((b[0] - a[1], (a[1], b[0]))
                    for a, b in zip(merged, merged[1:]))
        for name, s, e in evs:
            ops[name] = ops.get(name, 0.0) + (e - s)
        n_ops += len(evs)
    gaps.sort(reverse=True)
    n = len(per_chip)
    return {
        "chips": n,
        "busy_s": sum(busy) / n / 1e9,
        "span_s": sum(span) / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(g, spans), d / 1e9] for d, g in gaps[:top]],
        "n_ops": n_ops, "n_gaps": len(gaps),
    }


def dump(path, first=4):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            ends = [e.start_ns + e.duration_ns for e in evs]
            print(f"  LINE {line.name!r} events={len(evs)}"
                  + (f" first_start_ns={evs[0].start_ns}"
                     f" last_end_ns={max(ends)}" if evs else ""))
            for e in evs[:first]:
                print(f"      {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns}")


if __name__ == "__main__":
    dump(sys.argv[1])
