"""Median over group leaders of group close -> last outcome decoded:
from the end of the leader's ``coalesce.hold`` to the end of the last of
its ``coalesce.plan``, ``compile``, ``bind``, ``dispatch`` and ``demux``
spans. What every member of the group waits for after the hold."""
from harness import spans, stats

LAYER = "admission and coalescing (wlm/, parallel/sharedscan.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"

SERVICE = ("coalesce.plan", "compile", "bind", "dispatch", "demux")


def compute(run):
    out = []
    for tree in spans.trees(run["records"]):
        plan = spans.closed(tree, "coalesce.plan")
        if not plan:
            continue                    # a follower, or a solo statement
        top = plan[0].parent
        work = [s for s in spans.closed(tree, *SERVICE) if s.parent == top]
        hold = [s for s in spans.closed(tree, "coalesce.hold")
                if s.parent == top and s.end <= plan[0].start]
        start = hold[-1].end if hold else plan[0].start
        out.append((max(s.end for s in work) - start) * 1000.0)
    return stats.median(out)
