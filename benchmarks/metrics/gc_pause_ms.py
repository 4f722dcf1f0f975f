"""Mean over statements of the record's ``gc.ms``: the time the
process's garbage collections, started on any thread, overlapped the
statement's root (a collection holds the GIL, so it stalls every Python
thread). A mean, not a median: most statements see none or a few
short ones, and a mean moves with a rare long pause, which is the stall
to find. None where no record carries ``gc`` with its root closed: a
program older than the key."""

LAYER = "host threads (utils/phases.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "stmt_p95_ms"


def compute(run):
    ms = [r["gc"]["ms"] for r in run["records"]
          if (r.get("gc") or {}).get("ms") is not None]
    return sum(ms) / len(ms) if ms else None
