"""The store every run serves from: built once per (scale, seed) in a
checkout, checkpointed through the program's own ``persist/``, then
recovered by every run (the first included, so all runs serve the same
state). References are computed at build, on the generated frames, by
plain pandas, and stored beside the snapshot."""

import json
import os
import shutil
import time

import pandas as pd

from . import registry


def store_dir(root, sf, seed):
    return os.path.join(root, f"sf{sf:g}-seed{seed}")


KEEP_SNAPSHOTS = 6      # an SF1 snapshot is 1.7 GB on disk


def prune(root, keep=KEEP_SNAPSHOTS - 1):
    """Before a build: drop the snapshots used longest ago (``BUILT`` is
    touched by every run that recovers from it) beyond ``keep``, and
    any build that died."""
    if not os.path.isdir(root):
        return
    used = {}
    for name in os.listdir(root):
        built = os.path.join(root, name, "BUILT")
        used[name] = os.path.getmtime(built) if os.path.exists(built) else 0
    for name in sorted(used, key=used.get, reverse=True)[keep:]:
        shutil.rmtree(os.path.join(root, name), ignore_errors=True)


def _frames(cfg, sf, seed, ctx=None):
    """Generate the frames from the seed; with ``ctx``, ingest them the
    way the server's own ``--tpch`` flag does."""
    from spark_druid_olap_tpu.tools import tpch
    if ctx is not None:
        tables, flat = tpch.setup_context(
            ctx, sf=sf, seed=seed,
            target_rows=cfg["schema"]["target_rows"])
    else:
        tables = tpch.generate(sf, seed)
        flat = tpch.flatten(tables)
    data = dict(tables)
    data.update(tpch.nation_region_views(tables))
    data[cfg["schema"]["flat_index"]] = flat
    return data


def _write_references(data, sdir, set_name):
    classes = registry.load_json("statements", set_name + ".json")["classes"]
    rdir = os.path.join(sdir, "references", set_name)
    tmp = rdir + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    for cls, st in classes.items():
        registry.reference_fn(st["reference"])(data).to_parquet(
            os.path.join(tmp, cls + ".parquet"))
    shutil.rmtree(rdir, ignore_errors=True)
    os.rename(tmp, rdir)


def build(cfg, sf, seed, sdir, emit):
    """Generate, ingest, checkpoint, compute every statement set's
    references. ``BUILT`` is written last: a directory without it is a
    build that died, and is built again."""
    import spark_druid_olap_tpu as sdot
    shutil.rmtree(sdir, ignore_errors=True)
    prune(os.path.dirname(sdir))
    os.makedirs(sdir)
    t0 = time.perf_counter()
    ctx = sdot.Context({"sdot.cache.enabled": False,
                        "sdot.persist.path": os.path.join(sdir, "snapshot")})
    try:
        data = _frames(cfg, sf, seed, ctx)
        t1 = time.perf_counter()
        ctx.checkpoint()
    finally:
        ctx.close()
    t2 = time.perf_counter()
    for set_name in registry.statement_sets():
        _write_references(data, sdir, set_name)
    t3 = time.perf_counter()
    flat = data[cfg["schema"]["flat_index"]]
    meta = {"sf": sf, "seed": seed, "flat_rows": int(len(flat)),
            "datasources": len(ctx.store.names())}
    with open(os.path.join(sdir, "BUILT"), "w") as f:
        json.dump(meta, f)
    emit("store_built", **meta, generate_ingest_s=t1 - t0,
         checkpoint_s=t2 - t1, references_s=t3 - t2)


def load_references(cfg, sf, seed, sdir, set_name, classes, emit):
    """{class: reference frame}. A statement set added after the store
    was built gets its references from frames generated anew."""
    rdir = os.path.join(sdir, "references", set_name)
    if not os.path.isdir(rdir):
        t0 = time.perf_counter()
        _write_references(_frames(cfg, sf, seed), sdir, set_name)
        emit("references_added", set=set_name,
             seconds=time.perf_counter() - t0)
    return {cls: pd.read_parquet(os.path.join(rdir, cls + ".parquet"))
            for cls in classes}


def recover(cfg, sdir, emit):
    """A Context over the snapshot with the configuration's settings."""
    import spark_druid_olap_tpu as sdot
    t0 = time.perf_counter()
    ctx = sdot.Context({**cfg["settings"],
                        "sdot.persist.path": os.path.join(sdir, "snapshot")})
    with open(os.path.join(sdir, "BUILT")) as f:
        meta = json.load(f)
    os.utime(os.path.join(sdir, "BUILT"))
    flat = ctx.store.get(cfg["schema"]["flat_index"])
    if int(flat.num_rows) != meta["flat_rows"] \
            or len(ctx.store.names()) != meta["datasources"]:
        raise RuntimeError(
            f"recovered store differs from the one built: "
            f"{flat.num_rows} rows, {len(ctx.store.names())} datasources "
            f"against {meta}")
    emit("store_recovered", seconds=time.perf_counter() - t0,
         datasources=len(ctx.store.names()), flat_rows=int(flat.num_rows),
         segments=int(flat.num_segments), padded_rows=int(flat.padded_rows))
    return ctx


def scan_bytes(ctx, classes):
    """{class: bytes one statement of the class must read}: every padded
    row of every segment of its datasource, times the stored item size
    of each column the class lists. No pruning is assumed, so a
    statement whose segments are pruned reads less than this."""
    out = {}
    for cls, st in classes.items():
        ds = ctx.store.get(st["datasource"])
        rows = int(ds.num_segments) * int(ds.padded_rows)
        out[cls] = rows * sum(int(ds.stacked(c).dtype.itemsize)
                              for c in st["columns"])
    return out
