"""Mean over statements of the time the handler thread lost: the root's
wall minus its CPU (``cpu_us``), less the wall minus the CPU
(``wait_cpu_us``) of the spans that wait by design — the device
(``dispatch.wait``, the copy in ``dispatch.fetch``), the coalescer
(``coalesce.hold``, ``coalesce.ride``) and admission (``wlm.admit``),
wherever they lie in the tree, one inside another counted once. What is
left is time the thread was runnable but not running: GIL turns, locks,
the OS scheduler, a collection by another thread. A follower's hold and
ride are cut after the fact and carry no CPU of their own, so the CPU of
its polls while parked is taken off twice (a few polls a statement). A
mean, as ``handler_cpu_ms``: the CPU clock ticks in 10 ms on a TPU v5e
host. None where no record carries the keys with its root closed."""
from harness import spans

LAYER = "host threads (utils/phases.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"

WAITS = ("dispatch.wait", "dispatch.fetch", "coalesce.hold",
         "coalesce.ride", "wlm.admit")


def under_a_wait(tree, i):
    p = tree[i].parent
    while p > 0:
        if tree[p].name in WAITS:
            return True
        p = tree[p].parent
    return False


def lost_ms(rec):
    """The statement's off-CPU time outside its designed waits, or None
    while its root is open."""
    tree = spans.of(rec)
    cpu, wait_cpu = rec.get("cpu_us"), rec.get("wait_cpu_us")
    if tree is None or cpu is None or wait_cpu is None \
            or tree[0].end is None:
        return None
    waited = sum(spans.ms(s) for i, s in enumerate(tree)
                 if s.name in WAITS and s.end is not None
                 and not under_a_wait(tree, i))
    return spans.ms(tree[0]) - cpu / 1000.0 - (waited - wait_cpu / 1000.0)


def compute(run):
    lost = [v for v in map(lost_ms, run["records"]) if v is not None]
    return sum(lost) / len(lost) if lost else None
