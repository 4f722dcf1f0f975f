#!/usr/bin/env python
"""How many groups of a sketch statement leave the deployment's 5 % at a
given HLL precision: the statements of ``benchmarks/statements/
tpch_sketch4.json`` against their stored pandas references, over a store
the benchmark built, at each ``sdot.engine.hll.log2m`` asked for.

    python scripts/sketch_precision.py STORE_DIR OUT.json CLASS:LOG2M[,LOG2M...] ...

``STORE_DIR`` is ``benchmarks/.store/sf<sf>-seed<seed>`` after a run of
the cell ``sketch_highcard`` with that seed (its ``snapshot/`` and
``references/tpch_sketch4/``). One Context a precision, recovered from
the snapshot with the deployment's other settings; one send a statement;
by hand, outside the benchmark (``PERF.md`` section 4, PR 35: what
each precision from 2^11 to the deployment's 2^14 misses on the chip;
``scripts/sketch_sim.py`` counts the same over many simulated data
sets on the host).
"""

import json
import os
import sys
import time

import numpy as np
import pandas as pd

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)


def leaves(got, want, keys, approx, rtol):
    """{column: [groups outside rtol, the worst relative error]}."""
    j = want.merge(got, on=keys, suffixes=("", "_got"))
    assert len(j) == len(want) == len(got), (len(j), len(want), len(got))
    out = {}
    for c in approx:
        err = (j[c + "_got"] - j[c]).abs()
        # numpy.allclose's own inequality, as harness/compare.py applies it
        out[c] = [int((err > 1e-8 + rtol * j[c].abs()).sum()),
                  float((err / j[c].abs().clip(lower=1)).max())]
    return out


def main():
    import spark_druid_olap_tpu as sdot
    store, out_path = sys.argv[1], sys.argv[2]
    bench = os.path.join(REPO, "benchmarks")
    with open(os.path.join(bench, "statements", "tpch_sketch4.json")) as f:
        classes = json.load(f)["classes"]
    with open(os.path.join(bench, "configs", "tpch_sf1_sketch.json")) as f:
        config = json.load(f)
    rtol = config["guarantees"]["approx_count_distinct_rtol"]
    asked = {}
    for arg in sys.argv[3:]:
        cls, ps = arg.split(":")
        for p in ps.split(","):
            asked.setdefault(int(p), []).append(cls)
    doc = {"store": store, "rtol": rtol, "runs": []}
    for log2m, names in sorted(asked.items()):
        ctx = sdot.Context({**config["settings"],
                            "sdot.engine.hll.log2m": log2m,
                            "sdot.persist.path":
                                os.path.join(store, "snapshot")})
        for cls in names:
            st = classes[cls]
            want = pd.read_parquet(os.path.join(
                store, "references", "tpch_sketch4", cls + ".parquet"))
            t0 = time.perf_counter()
            got = ctx.sql(st["sql"]).to_pandas()
            first_s = time.perf_counter() - t0
            rec = ctx.history.entries()[-1].stats
            keys = [c for c in want.columns if c not in st["approx"]
                    and want[c].dtype.kind != "f"]
            doc["runs"].append({
                "class": cls, "log2m": log2m, "groups": len(want),
                "leave": leaves(got, want, keys, st["approx"], rtol),
                "mode": rec.get("mode"), "hll_form": rec.get("hll_form"),
                "sketch_fetch_bytes": rec.get("sketch_fetch_bytes"),
                "first_send_s": first_s})
            print(json.dumps(doc["runs"][-1]), flush=True)
        ctx.close()
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
