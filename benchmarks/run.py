#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip: it builds or recovers the store,
starts ``server.http.SqlServer`` in this process on port 0, warms up the
cell's own statement classes (each answer checked), measures from the
client's side of ``POST /sql`` for ``--seconds``, checks every answer of
the window against its plain reference, prints progress as JSON lines and
the contract's one JSON object last. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics (a slice in the
middle of the window runs under ``jax.profiler``).

No TPU: exit 2 before any load. ``--rehearse --sf 0.01`` is the only way
past that check (a CPU rehearsal of the control flow) and forces
``"correct": false``. The command never selects a JAX platform, and
outside ``--rehearse`` refuses to run with ``SDOT_PALLAS`` set.

Which cell, configuration, statements, traffic and metrics exist is data
(``BENCHMARK.json`` and the files it names, see README.md); nothing in
this file or in ``harness/`` knows their names.
"""

import time

T0 = time.perf_counter()        # process start, for the set-up time

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import random                   # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))      # the program

from harness import (client, compare, driver, peaks, reduce_trace,  # noqa: E402
                     registry, stats, store)

TRACE_SLICE_S = 4.0     # ends on a round boundary, so under 5 s with q3
HISTORY_KEEPS = 500     # metadata/history.py; read well before it wraps
TRACE_LABELS = ("client:", "between_rounds", "history_read")


def emit(event, **fields):
    print(json.dumps({"event": event,
                      "t": round(time.perf_counter() - T0, 3), **fields},
                     default=str), flush=True)


def die(msg):
    print(f"benchmarks/run.py: {msg}; nothing was run", file=sys.stderr)
    sys.exit(2)


# -- guarantees ---------------------------------------------------------------

def check_answers(samples, cell, refs, errors):
    """Mark every sample ``ok`` only if it was answered AND equals its
    class's reference under the configuration's tolerances. Returns the
    largest relative error of a float column."""
    g = cell.config["guarantees"]
    worst = 0.0
    for s in samples:
        body = s.pop("body")
        if not s["ok"]:
            errors.append(f"{s['cls']}: HTTP {s['status']} {body[:200]!r}")
            continue
        try:
            worst = max(worst, compare.check_frames(
                s["cls"], client.body_frame(body), refs[s["cls"]],
                approx=cell.classes[s["cls"]].get("approx", ()),
                rtol=g["float_rtol"],
                approx_rtol=g["approx_count_distinct_rtol"]))
        except compare.Mismatch as e:
            s["ok"] = False
            errors.append(str(e))
    return worst


def check_records(records, cell, errors):
    """Every statement ran in the mode the configuration guarantees, on
    the device, and (where guaranteed) every lane by a wave kernel."""
    g = cell.config["guarantees"]
    for r in records:
        what = " ".join((r.get("sql") or "").split())[:60]
        if r.get("mode") != g["mode"]:
            errors.append(f"mode {r.get('mode')!r}, not {g['mode']!r}: "
                          f"{what}")
        if r.get("backend_lost"):
            errors.append(f"backend_lost: {what}")
        if g.get("wave_kernel_every_lane") \
                and not (r.get("sharedscan") or {}).get("pallas"):
            errors.append(f"not served by a wave kernel "
                          f"({r.get('sharedscan')}): {what}")


def check_counters(before, after, cell, errors):
    g = cell.config["guarantees"]
    a, b = after["sharedscan"], before["sharedscan"]
    if "sharedscan_fallbacks" in g:
        d = a["fallbacks"] - b["fallbacks"]
        if d != g["sharedscan_fallbacks"] or a["last_error"]:
            errors.append(f"sharedscan fallbacks {d}, last_error "
                          f"{a['last_error']!r}")
    if "pallas_fallbacks" in g:
        d = a["pallas"]["fallbacks"] - b["pallas"]["fallbacks"]
        if d != g["pallas_fallbacks"]:
            errors.append(f"pallas fallbacks {d}")
    if g.get("wave_kernel_every_lane"):
        solo = a["solo_groups"] - b["solo_groups"]
        launches = a["pallas"]["launches"] - b["pallas"]["launches"]
        if solo or launches < 1:
            errors.append(f"{solo} statements left the coalescer alone, "
                          f"{launches} wave launches")


def counters(ctx, cache_dir):
    from spark_druid_olap_tpu.utils import compile_cache
    return {"cache_entries": compile_cache.entries(cache_dir),
            "sharedscan": ctx.engine.sharedscan.stats(),
            "dispatch_counts": list(ctx.engine.dispatch_counts)}


# -- the traced slice ---------------------------------------------------------

class Slice:
    """Runs between the first session's rounds: starts the profiler at
    the slice's start, reads ``/history`` in pieces under a
    ``history_read`` annotation (it keeps 500 records), stops the
    profiler at the first round boundary after the slice's length."""

    def __init__(self, port, trace_dir, start_at, length, sessions):
        import jax
        self.jax = jax
        self.port, self.trace_dir = port, trace_dir
        self.start_at, self.length = start_at, length
        self.read_every = max(1, HISTORY_KEEPS * 2 // 5 // sessions)
        self.t0 = self.t1 = None
        self.records = {}
        self._last_read = 0

    def annotate(self, name):
        return self.jax.profiler.TraceAnnotation(name)

    def _read_history(self):
        for r in client.history(self.port):
            self.records[(r["startedAt"], r["sql"])] = r

    def between(self, n_done):
        now = time.perf_counter()
        if self.t0 is None:
            if now >= self.start_at:
                opts = self.jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                self.jax.profiler.start_trace(self.trace_dir,
                                              profiler_options=opts)
                self.t0 = time.perf_counter()
                self._last_read = n_done
        elif self.t1 is None:
            if now >= self.t0 + self.length:
                self.finish()
            elif n_done - self._last_read >= self.read_every:
                with self.annotate("history_read"):
                    self._read_history()
                self._last_read = n_done

    def finish(self):
        """Stop the profiler (once), then read the slice's last records
        outside the trace."""
        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            self.jax.profiler.stop_trace()
            self._read_history()

    def pairs(self, samples):
        """[(sample, its history record)] for the samples that lie
        inside the slice: same SQL, the earliest unmatched record written
        while the sample was in flight."""
        wall = time.time() - time.perf_counter()
        by_sql = {}
        for (at, sql), r in sorted(self.records.items()):
            by_sql.setdefault(sql, []).append([at - wall, r])
        out = []
        for s in sorted(samples, key=lambda s: s["t1"]):
            for slot in by_sql.get(s["sql"], ()):
                if slot[1] is not None \
                        and s["t0"] <= slot[0] <= s["t1"] + 0.002:
                    out.append((s, slot[1]))
                    slot[1] = None
                    break
        return out


# -- the run ------------------------------------------------------------------

def device_info(devs, chips):
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def compute_metrics(defs, run):
    out = {}
    for m in defs:
        v = registry.load_module("metrics", m["name"]).compute(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def warm_up(drv, args, cell, ctx, port, refs, cache_dir, errors):
    """The cell's own classes, each twice, each answer checked and each
    record held to the guarantees, before the window opens."""
    t0 = time.perf_counter()
    warm = drv.warm(cell.generator.schedule(
        cell.traffic, list(cell.classes), random.Random(args.seed)))
    worst = check_answers(warm, cell, refs, errors)
    check_records(client.history(port)[-len(warm):], cell, errors)
    emit("warmed", seconds=time.perf_counter() - t0, statements=len(warm),
         max_rel_err=worst, errors=errors[:5],
         cache_entries=counters(ctx, cache_dir)["cache_entries"])
    return worst


def read_slice(sl, samples, run, args, cell, ctx, kind, errors):
    """Fill ``run`` with what the traced slice gives: its samples, their
    history records, the reduced trace."""
    inside = [s for s in samples if s["t0"] >= sl.t0 and s["t1"] <= sl.t1]
    pairs = sl.pairs(inside)
    run.update(samples=inside, pairs=pairs, records=[r for _, r in pairs],
               slice_s=sl.t1 - sl.t0,
               scan_bytes=store.scan_bytes(ctx, cell.classes),
               peaks=peaks.PEAKS.get(kind) if args.rehearse
               else peaks.peaks_for(kind))
    check_records(run["records"], cell, errors)
    xplane = reduce_trace.find_xplane(sl.trace_dir)
    xplane_bytes = None
    if xplane:
        xplane_bytes = os.path.getsize(xplane)
        run["trace"] = reduce_trace.reduce_events(
            reduce_trace.read_events(xplane), TRACE_LABELS)
    if not args.keep_trace:
        shutil.rmtree(sl.trace_dir, ignore_errors=True)
    return {"slice_s": run["slice_s"], "slice_statements": len(inside),
            "slice_records": len(pairs), "trace": run["trace"],
            "xplane_bytes": xplane_bytes,
            "records": run["records"]}


def measure(args, cell, ctx, port, refs, cache_dir, devs):
    drv = driver.Driver(port, cell.classes, lane=cell.traffic.get("lane"))
    errors = []
    worst = warm_up(drv, args, cell, ctx, port, refs, cache_dir, errors)
    if errors:
        return None, errors

    sessions = cell.generator.schedule(
        cell.traffic, list(cell.classes), random.Random(args.seed))
    sl = None
    if args.trace:
        trace_dir = os.path.join(args.out, f"trace-{cell.name}-{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        length = min(TRACE_SLICE_S, args.seconds / 2)
        sl = Slice(port, trace_dir,
                   time.perf_counter() + (args.seconds - length) / 2,
                   length, len(sessions))
        drv.annotate = sl.annotate
    before = counters(ctx, cache_dir)
    ready_s = time.perf_counter() - T0     # set-up ends, window opens
    samples, w0, w1 = drv.run(sessions, args.seconds,
                              sl.between if sl else None)
    after = counters(ctx, cache_dir)
    if sl:
        sl.finish()                 # if the window ended before the slice

    # everything below is outside the timing
    worst = max(worst, check_answers(samples, cell, refs, errors))
    check_counters(before, after, cell, errors)
    check_records(client.history(port)[-max(1, len(samples)):], cell, errors)
    run = {"ready_s": ready_s, "counters": {"before": before, "after": after},
           "window": {"samples": samples, "seconds": w1 - w0},
           "samples": [], "pairs": [], "records": [], "trace": None,
           "slice_s": None, "scan_bytes": None, "peaks": None}
    device = device_info(devs, cell.chips)
    medians = stats.class_medians(samples)
    detail = {"cell": cell.name, "seed": args.seed, "trace": args.trace,
              "seconds": args.seconds, "sf": args.sf,
              "rehearse": args.rehearse, "errors": errors,
              "max_rel_err": worst, "window_s": w1 - w0,
              "class_median_ms": {c: m for c, (m, _) in medians.items()},
              "class_samples": {c: n for c, (_, n) in medians.items()},
              "p95_samples": len(stats.latencies(samples)),
              "lateness_ms": {
                  "median": stats.median(s["late_ms"] for s in samples),
                  "max": max((s["late_ms"] for s in samples), default=None)},
              "cache_entries": [before["cache_entries"],
                                after["cache_entries"]],
              "dispatch_counts": [before["dispatch_counts"],
                                  after["dispatch_counts"]],
              "sharedscan": after["sharedscan"]}
    result = {}
    if sl and sl.t1 is not None:
        detail.update(read_slice(sl, samples, run, args, cell, ctx,
                                 device["kind"], errors))
        if run["trace"]:
            device.update(busy_s=run["trace"]["busy_s"],
                          window_s=run["slice_s"])
            result["breakdown"] = {k: run["trace"][k]
                                   for k in ("device_ops", "idle_gaps")}
    result.update(
        correct=not errors and not args.rehearse, attempted=len(samples),
        failed=sum(1 for s in samples if not s["ok"]),
        metrics=compute_metrics(
            cell.per_layer if args.trace else cell.end_to_end, run),
        device=device)
    detail["metrics"] = result["metrics"]
    path = os.path.join(args.out, f"{cell.name}-{args.seed}.json")
    with open(path, "w") as f:
        json.dump(detail, f, indent=1, default=str)
    emit("detail", file=os.path.relpath(path, os.getcwd()),
         **{k: detail[k] for k in (
             "errors", "class_median_ms", "class_samples", "p95_samples",
             "lateness_ms", "window_s", "max_rel_err", "cache_entries")})
    return result, errors


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: skips ONLY the device check and "
                         "ends with \"correct\": false")
    ap.add_argument("--sf", type=float, default=None,
                    help="scale factor, with --rehearse only")
    ap.add_argument("--store-root", default=os.path.join(BENCH_DIR, ".store"),
                    help="where snapshots live (a rehearsal's: under /tmp)")
    ap.add_argument("--out", default=os.path.join(BENCH_DIR, "out"))
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args()
    if args.sf is not None and not args.rehearse:
        die("--sf is for --rehearse")
    if "SDOT_PALLAS" in os.environ and not args.rehearse:
        die("SDOT_PALLAS is set: the chip run takes the compiler's kernels")
    cell = registry.Cell(args.workload)
    cfg = cell.config
    if args.sf is None:
        args.sf = cfg["scale"]["sf"]

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not args.rehearse:
        die(f"JAX found no TPU (default device: {devs[0].platform} "
            f"{devs[0].device_kind})")
    if len(devs) < cell.chips:
        die(f"the cell asks for {cell.chips} chip(s), JAX sees {len(devs)}")
    from spark_druid_olap_tpu.server.http import SqlServer
    from spark_druid_olap_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    # JAX keeps only programs that took a second to compile: one that took
    # just under it would be compiled again by every later run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    os.makedirs(args.out, exist_ok=True)
    emit("start", cell=cell.name, config=cell.config_name, seed=args.seed,
         sf=args.sf, platform=devs[0].platform, kind=devs[0].device_kind,
         devices=len(devs), cache_dir=cache_dir,
         cache_entries=compile_cache.entries(cache_dir))

    sdir = store.store_dir(args.store_root, args.sf, args.seed)
    if not os.path.exists(os.path.join(sdir, "BUILT")):
        store.build(cfg, args.sf, args.seed, sdir, emit)
    refs = store.load_references(cfg, args.sf, args.seed, sdir,
                                 cell.statement_set, cell.classes, emit)
    ctx = store.recover(cfg, sdir, emit)
    srv = SqlServer(ctx, "127.0.0.1", 0).start(background=True)
    try:
        result, errors = measure(args, cell, ctx, srv.port, refs,
                                 cache_dir, devs)
    finally:
        srv.stop()
        ctx.close()
    if result is None:          # a wrong answer in warm-up ends the run
        result = {"correct": False, "attempted": 0, "failed": 0,
                  "metrics": {}, "device": device_info(devs, cell.chips),
                  "errors": errors[:5]}
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["attempted"] else 1)


if __name__ == "__main__":
    main()
