"""Programs built inside the window: the larger of (history records of
the slice with a ``compile`` phase above 0) and (entries the persistent
compile cache gained over the whole window). Expected 0."""

LAYER = "compile (QueryEngine._cached_program, utils/compile_cache.py)"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "stmt_p95_ms"


def compute(run):
    built = sum(1 for r in run["records"]
                if (r.get("phases") or {}).get("compile", 0) > 0)
    c = run["counters"]
    return max(built, c["after"]["cache_entries"]
               - c["before"]["cache_entries"])
