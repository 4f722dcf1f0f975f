#!/usr/bin/env python3
"""Which compiled programs each statement of the benchmark's cells needs
that a compile cache does not hold yet.

    python3 scripts/program_keys.py --cache DIR --seed N --out OUT.json
        [--workloads a,b,...] [--store-root DIR] [--sf F] [--sends 2]
        [--set key=value ...]

Points JAX's persistent compile cache at ``DIR`` (through
``JAX_COMPILATION_CACHE_DIR``, which ``utils/compile_cache.py`` honours),
keeps every program there whatever its compile time (as
``benchmarks/run.py`` does), then for each cell of ``BENCHMARK.json``
(all, in order, or those named) recovers the store of ``(sf, seed)`` —
building it first where it is missing, as the benchmark does — with the
cell's configuration, serves it on a local port and drives the rounds
of the cell's own traffic until every statement has been sent
``--sends`` times, as the benchmark's warm-up does (a round is one
statement in the sequential cells, a dashboard's tiles together in the
burst cell). Per round it lists the cache entries the round added, the
wall seconds it took and what each statement's record says of its scan
program (``program.sig`` and the late materialization keys).

Run it twice over the same store, from the same directory and over the
same ``DIR`` — on the chip the cache's own path is part of every key (a
copy elsewhere misses on everything), and the key of a program holding
a Pallas kernel carries its source paths and lines — first with one
commit's code, then with the other's: a statement whose programs lower
to the same HLO on both adds no entry the second time. ``--set`` applies configuration keys over every cell's settings
(e.g. ``sdot.engine.scan.compact.min.rows=0`` for a CPU rehearsal at
``--sf 0.01``, where no scan is large enough to compact otherwise).
"""

import argparse
import json
import os
import random
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)                              # the program
sys.path.insert(1, os.path.join(ROOT, "benchmarks"))  # its harness

RECORD_KEYS = ("mode", "n_dispatch", "compact_m", "compact_mask",
               "compact_from", "compact_live", "compact_carry",
               "hash_slots", "hash_rows", "sorted_run")


def emit(event, **fields):
    print(json.dumps({"event": event, **fields}, default=str), flush=True)


def entries(cache):
    try:
        return {n for n in os.listdir(cache) if n.endswith("-cache")}
    except FileNotFoundError:
        return set()


def _value(text):
    try:
        return json.loads(text)
    except ValueError:
        return text


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cache", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--workloads", default=None)
    ap.add_argument("--store-root",
                    default=os.path.join(ROOT, "benchmarks", ".store"))
    ap.add_argument("--sf", type=float, default=None)
    ap.add_argument("--sends", type=int, default=2)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE")
    args = ap.parse_args()
    cache = os.path.abspath(args.cache)
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    extra = dict(kv.split("=", 1) for kv in args.set)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from harness import client, driver, registry, store
    from spark_druid_olap_tpu.server.http import SqlServer
    from spark_druid_olap_tpu.utils import compile_cache
    assert compile_cache.configure() == cache

    names = args.workloads.split(",") if args.workloads else \
        [w["name"] for w in registry.benchmark_json()["workloads"]]
    dev = jax.devices()[0]
    emit("start", platform=dev.platform, kind=dev.device_kind,
         cache=cache, entries=len(entries(cache)), workloads=names)
    report = {"platform": dev.platform, "kind": dev.device_kind,
              "seed": args.seed, "set": extra,
              "entries_before": len(entries(cache)), "workloads": {}}
    for name in names:
        cell = registry.Cell(name)
        cfg = cell.config
        sf = args.sf if args.sf is not None else cfg["scale"]["sf"]
        sdir = store.store_dir(args.store_root, sf, args.seed)
        if not os.path.exists(os.path.join(sdir, "BUILT")):
            store.build(cfg, sf, args.seed, sdir, emit)
        ctx = store.recover(cfg, sdir, emit)
        for key, text in extra.items():
            ctx.config.set(key, _value(text))
        srv = SqlServer(ctx, "127.0.0.1", 0).start(background=True)
        rounds = report["workloads"][name] = []
        try:
            drv = driver.Driver(srv.port, cell.classes,
                                lane=cell.traffic.get("lane"))
            sessions = cell.generator.schedule(
                cell.traffic, list(cell.classes), random.Random(args.seed))
            sent = {c: 0 for c in cell.classes}
            with ThreadPoolExecutor(max_workers=driver.MAX_BURST) as pool:
                for rnd in sessions[0]:
                    before = entries(cache)
                    t0 = time.perf_counter()
                    samples = drv.run_round(pool, {**rnd, "due_s": None},
                                            0.0)
                    seconds = time.perf_counter() - t0
                    new = sorted(entries(cache) - before)
                    recs = client.history(srv.port)[-len(samples):]
                    rounds.append({
                        "sends": rnd["sends"], "new_entries": new,
                        "seconds": round(seconds, 3),
                        "statuses": [x["status"] for x in samples],
                        "records": [
                            {"sig": (r.get("program") or {}).get("sig"),
                             **{k: r[k] for k in RECORD_KEYS if k in r}}
                            for r in recs]})
                    emit("round", workload=name, sends=rnd["sends"],
                         new=len(new), seconds=round(seconds, 3),
                         statuses=rounds[-1]["statuses"])
                    for cls in rnd["sends"]:
                        sent[cls] += 1
                    if min(sent.values()) >= args.sends:
                        break
        finally:
            srv.stop()
            ctx.close()
    report["entries_after"] = len(entries(cache))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, default=str)
    added, bad = {}, []
    for w, rounds in report["workloads"].items():
        for r in rounds:
            what = f"{w}.{'+'.join(r['sends'])}"
            if r["new_entries"]:
                added[what] = added.get(what, 0) + len(r["new_entries"])
            if set(r["statuses"]) != {200}:
                bad.append(what)
    emit("done", out=args.out, added=added, failed=bad,
         entries=report["entries_after"])
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
