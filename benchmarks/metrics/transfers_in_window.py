"""Host-to-device transfers (``n_transfer``) summed over the slice's
history records. Expected 0: everything is resident after warm-up."""

LAYER = "bind (QueryEngine._bind_arrays)"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "stmt_p95_ms"


def compute(run):
    if not run["records"]:
        return None
    return sum(int(r.get("n_transfer") or 0) for r in run["records"])
