"""Mean over the records that carry ``sketch_fetch_bytes`` of that
counter in KB (1,000 bytes): the sketch state a statement copied back
from the device, the part of its ``fetch_bytes`` that is registers or
estimates (PR 35). It tells which form the program took: a dense
``[groups, 2^log2m]`` register block is 4 x 2^log2m bytes a group and
sketch column (655,000 KB for 10,000 groups at 2^14, the host's numpy
estimate over it besides), the sparse form's finished estimates 4 bytes
(40 KB). A mean, so that it moves by what any class's fetch loses or
gains. None where no record carries the counter."""

LAYER = "dispatch and demux (_run_agg*, sharedscan._dispatch)"
UNIT = "KB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "stmt_p95_ms"


def compute(run):
    kb = [r["sketch_fetch_bytes"] / 1000.0 for r in run["records"]
          if r.get("sketch_fetch_bytes") is not None]
    return sum(kb) / len(kb) if kb else None
