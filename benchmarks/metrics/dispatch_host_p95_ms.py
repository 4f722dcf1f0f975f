"""95th percentile over statements of the ``dispatch`` + ``demux``
phases: HOST clock around launch, wait and fetch — not device time."""
from harness import stats

LAYER = "dispatch and demux (_run_agg*, sharedscan._dispatch)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "stmt_p95_ms"


def compute(run):
    return stats.percentile(
        (stats.phase_sum(r, ("dispatch", "demux")) for r in run["records"]),
        95)[0]
