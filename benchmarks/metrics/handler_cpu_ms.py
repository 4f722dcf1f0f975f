"""Mean over statements of the record's ``cpu_us``: the CPU time of the
handler thread for the statement, from the thread's start (one thread a
connection, so that is the accept) to the root's close — the host work
a statement costs, whatever it waited for. A mean, not a median: on a
TPU v5e host the thread CPU clock advances in 10 ms ticks, so one
statement reads 0, 10 or 20 ms, and only a mean over many of them
estimates the work (a tick charges the thread that runs at it). None
where no record carries the key with its root closed: a program older
than the key."""

LAYER = "host threads (utils/phases.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "class_geomean_ms"


def compute(run):
    cpu = [r["cpu_us"] / 1000.0 for r in run["records"]
           if r.get("cpu_us") is not None]
    return sum(cpu) / len(cpu) if cpu else None
