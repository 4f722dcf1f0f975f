"""Sorted-run hashed aggregation (ops/sorted_groupby.py): the one-sort
payload-riding tier that replaces the hashed path's per-agg scatters.

Differential contract: with ``sdot.engine.groupby.hash.sortedrun`` forced
on (CPU default is off — the x64 sort dominates there), every hashed
query must produce bit-identical int results and ~1e-6 float results
against both the scatter tier and a pandas oracle — including wide int
sums, filtered aggregations, FD-demoted anyvalue dims, NULL-bearing
min/max, overflow retry, and the sharded mesh.
"""

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.ops import groupby as G
from spark_druid_olap_tpu.ops import hash_groupby as H
from spark_druid_olap_tpu.ops import sorted_groupby as SG

HASHED_CONF = {"sdot.engine.groupby.dense.max.keys": 512,
               "sdot.engine.groupby.hash.sortedrun": "on"}


def _frame(n=50_000, seed=9, n_keys=8000):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "k": rng.integers(0, n_keys, n).astype(str),
        "q": rng.integers(0, 50, n),
        "wide": rng.integers(-10**9, 10**9, n),
        "price": rng.normal(100, 30, n).round(4),
        "nul": np.where(rng.random(n) < 0.15, np.nan,
                        rng.normal(5, 2, n)),
        "flag": rng.choice(["a", "b"], n),
    })


SQL = ("select k, sum(q) as s, sum(wide) as w, sum(price) as p, "
       "min(nul) as mn, max(nul) as mx, count(*) as c, "
       "sum(case when flag = 'a' then q else 0 end) as fq "
       "from t group by k order by k")


def _run(conf, df):
    ctx = sdot.Context(config=conf)
    ctx.ingest_dataframe("t", df)
    r = ctx.sql(SQL).to_pandas()
    st = ctx.history.entries()[-1].stats
    assert st.get("hashed"), st
    return r


def test_sorted_run_matches_scatter_and_pandas():
    df = _frame()
    on = _run(HASHED_CONF, df)
    off = _run({**HASHED_CONF,
                "sdot.engine.groupby.hash.sortedrun": "off"}, df)
    pd.testing.assert_frame_equal(on, off, check_dtype=False, rtol=1e-9,
                                  atol=1e-9)
    o = df.assign(fqv=np.where(df.flag == "a", df.q, 0)).groupby("k").agg(
        s=("q", "sum"), w=("wide", "sum"), p=("price", "sum"),
        mn=("nul", "min"), mx=("nul", "max"), c=("q", "size"),
        fq=("fqv", "sum")).reset_index().sort_values("k") \
        .reset_index(drop=True)
    assert on.s.astype(int).tolist() == o.s.tolist()
    assert on.w.astype(int).tolist() == o.w.tolist()
    assert on.c.astype(int).tolist() == o.c.tolist()
    assert on.fq.astype(int).tolist() == o.fq.tolist()
    assert np.allclose(on.p, o.p, rtol=1e-6)
    assert np.allclose(on.mn.fillna(-9), o.mn.fillna(-9), rtol=1e-6)
    assert np.allclose(on.mx.fillna(-9), o.mx.fillna(-9), rtol=1e-6)


@pytest.mark.parametrize("mode", ["on", "off"])
def test_stats_say_which_core_ran(mode):
    """'sorted_run' and 'hash_rows' ride beside 'hash_slots' (and reach
    the /history record as the rest of last_stats does)."""
    df = _frame(n=20_000, seed=3, n_keys=3000)
    ctx = sdot.Context(config={
        **HASHED_CONF, "sdot.engine.groupby.hash.sortedrun": mode,
        "sdot.engine.scan.compact.min.rows": 0})
    ctx.ingest_dataframe("t", df)
    ctx.sql("select k, sum(q) as s from t where q < 5 group by k")
    st = ctx.history.entries()[-1].stats
    assert st["hashed"] and st["sorted_run"] is (mode == "on"), st
    ds = ctx.store.get("t")
    rows = st.get("compact_m") or ds.padded_rows * ds.num_segments
    assert st["hash_rows"] == rows and st["hash_slots"] > 0, st


def test_sorted_run_sharded_matches():
    df = _frame(n=40_000, seed=4)
    conf = {**HASHED_CONF, "sdot.querycostmodel.enabled": False}
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    ctx = sdot.Context(config=conf, mesh=make_mesh())
    ctx.ingest_dataframe("t", df, target_rows=4096)
    r = ctx.sql(SQL).to_pandas()
    st = ctx.history.entries()[-1].stats
    assert st.get("hashed") and st.get("sharded"), st
    want = _run({**HASHED_CONF}, df)
    pd.testing.assert_frame_equal(r, want, check_dtype=False, rtol=1e-6,
                                  atol=1e-9)


def test_sorted_run_overflow_retry():
    df = _frame(n=20_000, seed=7, n_keys=6000)
    conf = {**HASHED_CONF, "sdot.engine.groupby.hash.slots": 1024}
    r = _run(conf, df)
    want = _run(HASHED_CONF, df)
    pd.testing.assert_frame_equal(r, want, check_dtype=False, rtol=1e-9)


def test_fd_demoted_anyvalue_dims():
    # c_name is functionally determined by k: rides as an anyvalue agg
    df = _frame(n=30_000, seed=12, n_keys=4000)
    df["kname"] = "name_" + df.k
    conf = HASHED_CONF
    ctx = sdot.Context(config=conf)
    ctx.ingest_dataframe("t", df)
    r = ctx.sql("select k, kname, sum(q) as s from t "
                "group by k, kname order by k").to_pandas()
    assert ctx.history.entries()[-1].stats.get("hashed")
    o = df.groupby(["k", "kname"]).agg(s=("q", "sum")).reset_index() \
        .sort_values("k").reset_index(drop=True)
    assert len(r) == len(o)
    assert r.kname.tolist() == o.kname.tolist()
    assert r.s.astype(int).tolist() == o.s.tolist()


# -- s64 emulated 64-bit prefix sums (the TPU wide-sum route) ----------------

def test_cumsum64_crosses_32bit_boundaries():
    rng = np.random.default_rng(0)
    v = rng.integers(-2**31 + 1, 2**31 - 1, 5000).astype(np.int32)
    hi, lo = SG._cumsum64(jnp.asarray(v))
    got = (np.asarray(hi).astype(np.int64) << 32) \
        | np.asarray(lo).astype(np.int64)
    want = np.cumsum(v.astype(np.int64))
    np.testing.assert_array_equal(got, want)


def test_sub64_borrow():
    a = np.int64(3) << 33
    b = np.int64(5)
    ahi, alo = jnp.int32(a >> 32), jnp.uint32(a & 0xFFFFFFFF)
    bhi, blo = jnp.int32(0), jnp.uint32(5)
    rhi, rlo = SG._sub64(ahi, alo, bhi, blo)
    got = (int(rhi) << 32) | int(np.uint32(rlo))
    assert got == int(a - b)


def test_s64_route_kernel_direct():
    """Force the s64 plan (as a 32-bit TPU backend would choose) and diff
    the kernel output against numpy — covers the emulated path the
    x64 test process would otherwise never take."""
    rng = np.random.default_rng(5)
    n, T = 30_000, 1 << 12
    keys = rng.integers(0, 3000, n).astype(np.int32)
    vals = rng.integers(-2**30, 2**30, n).astype(np.int32)
    valid = rng.random(n) < 0.9
    inputs = [G.AggInput("w", "sum", jnp.asarray(vals), None,
                         is_int=True, maxabs=2.0**30)]
    routes = {"w": G.Route("w", "sum", "s64")}
    out = SG.sorted_hash_groupby(jnp.asarray(keys),
                                 jnp.zeros(n, jnp.int32),
                                 jnp.asarray(valid), T, inputs, routes)
    assert int(out["__unres__"][0]) == 0
    khi = np.asarray(out["__tkhi__"])
    occ = khi != H.EMPTY
    got = np.asarray(G.combine_route(routes["w"],
                                     {k: np.asarray(v)
                                      for k, v in out.items()}, T))[occ]
    df = pd.DataFrame({"k": keys[valid], "v": vals[valid].astype(np.int64)})
    o = df.groupby("k").v.sum()
    np.testing.assert_array_equal(np.sort(khi[occ]), o.index.to_numpy())
    # table keys are sorted ascending -> group order == key order
    np.testing.assert_array_equal(got, o.to_numpy())


def test_float_sums_small_groups_after_large_prefix():
    """The segmented compensated scan must NOT leak the prefix magnitude
    into later small groups (the failure mode of a naive prefix-sum
    difference in f32)."""
    n_big, n_small = 4000, 1000
    big = np.full(n_big, 1.0e7, np.float32)          # huge first group
    rng = np.random.default_rng(2)
    small = rng.normal(1e-3, 1e-4, n_small).astype(np.float32)
    keys = np.concatenate([np.zeros(n_big, np.int32),
                           np.arange(1, n_small + 1, dtype=np.int32)
                           .repeat(1)])
    vals = np.concatenate([big, small])
    inputs = [G.AggInput("v", "sum", jnp.asarray(vals), None)]
    routes = {"v": G.Route("v", "sum", "ff", merged=False)}
    out = SG.sorted_hash_groupby(
        jnp.asarray(keys), jnp.zeros(len(keys), jnp.int32),
        jnp.ones(len(keys), bool), 1 << 11, inputs, routes)
    acc = np.asarray(out["v.acc"]).astype(np.float64)
    c = np.asarray(out["v.c"]).astype(np.float64)
    khi = np.asarray(out["__tkhi__"])
    occ = khi != H.EMPTY
    got = (acc + c)[occ]
    want = np.concatenate([[big.astype(np.float64).sum()],
                           small.astype(np.float64)])
    # keys 0..n_small in sorted order == table order
    assert np.allclose(got, want, rtol=1e-5), \
        np.abs((got - want) / want).max()


# -- medium-K reroute (dense-range K onto the sorted-run tier) ---------------

def test_medium_k_reroutes_to_sorted_run_and_matches():
    """K above sorted.min.keys but far below dense.max.keys: with the
    backend constants saying sort-is-cheap (forced here), the dense
    query must route hashed/sorted-run and match the dense answer."""
    df = _frame(n=40_000, seed=20, n_keys=3000)
    sql = ("select k, sum(q) as s, sum(price) as p, count(*) as c "
           "from t group by k order by k")

    dense_ctx = sdot.Context(
        config={"sdot.engine.groupby.sorted.min.keys": 0})
    dense_ctx.ingest_dataframe("t", df)
    dense = dense_ctx.sql(sql).to_pandas()
    assert not dense_ctx.history.entries()[-1].stats.get("hashed")

    ctx = sdot.Context(config={
        "sdot.engine.groupby.sorted.min.keys": 1024,
        "sdot.engine.groupby.hash.sortedrun": "on",
        # force the sort-is-cheap verdict regardless of backend
        "sdot.querycostmodel.sort.payload.seconds.per.row": 1e-12,
        "sdot.querycostmodel.scatter.seconds.per.update": 1e-8,
    })
    ctx.ingest_dataframe("t", df)
    r = ctx.sql(sql).to_pandas()
    st = ctx.history.entries()[-1].stats
    assert st.get("hashed"), st
    pd.testing.assert_frame_equal(r, dense, check_dtype=False, rtol=1e-6,
                                  atol=1e-9)


@pytest.mark.parametrize("fn,hashed", [
    ("approx_count_distinct_theta", False), ("approx_count_distinct", True)])
def test_medium_k_reroute_skips_sketches(fn, hashed):
    """theta (and KLL) keep the dense tier: the sorted-run core has no
    route for them. An HLL sketch goes along since PR 35 where the dense
    tier would itself take the sparse form (3,001 groups' register block
    is past ``ops.hll.DENSE_BLOCK_MAX_BYTES``): the core has the rows
    sorted by group already, the register is one more sort key."""
    df = _frame(n=20_000, seed=22, n_keys=3000)
    ctx = sdot.Context(config={
        "sdot.engine.groupby.sorted.min.keys": 1024,
        "sdot.querycostmodel.sort.payload.seconds.per.row": 1e-12,
        "sdot.querycostmodel.scatter.seconds.per.update": 1e-8,
    })
    ctx.ingest_dataframe("t", df)
    r = ctx.sql(f"select k, {fn}(q) as d from t "
                "group by k order by k").to_pandas()
    st = ctx.history.entries()[-1].stats
    assert st["mode"] == "engine" and bool(st.get("hashed")) == hashed, st
    assert st.get("hll_form") == ("sparse" if hashed else None)
    want = df.groupby("k").q.nunique().sort_index()
    assert r.k.tolist() == want.index.tolist()
    # (50 values over 2^11 registers; theta's k-min sketch is coarser)
    assert np.abs(r.d.to_numpy() - want.to_numpy()).max() \
        <= (1 if hashed else 5)


def test_segmented_scan_by_doubling_is_the_tree_scan():
    """Past ``_SCAN_TREE_MAX_ROWS`` a segmented scan runs as a loop of
    doubling shifts: the same combine over the same runs — singletons, a
    run of every length up to one of 3,000 rows, the compensated float sum
    and a max."""
    rng = np.random.default_rng(35)
    lengths = np.concatenate([np.ones(200, np.int64), np.arange(1, 60),
                              [3000, 1, 2, 777]])
    rng.shuffle(lengths)
    n = int(lengths.sum())
    flag = np.zeros(n, bool)
    flag[np.concatenate([[0], np.cumsum(lengths)[:-1]])] = True
    v = jnp.asarray((rng.random(n) * 1e5).astype(np.float32))
    flag = jnp.asarray(flag)

    def comb(xa, xb):
        s, e = SG._two_sum(xa[0], xb[0])
        return (s, e + xa[1] + xb[1])

    def total(pair):
        return np.asarray(pair[0], np.float64) + np.asarray(pair[1],
                                                            np.float64)

    assert n <= SG._SCAN_TREE_MAX_ROWS
    tree = SG._seg_scan(flag, (v, jnp.zeros_like(v)), comb)
    loop = jax.jit(lambda f, x: SG._seg_scan_doubling(
        f, (x, jnp.zeros_like(x)), comb))(flag, v)
    want = np.concatenate([np.cumsum(c) for c in np.split(
        np.asarray(v, np.float64), np.cumsum(lengths)[:-1])])
    np.testing.assert_allclose(total(loop), want, rtol=1e-12)
    np.testing.assert_allclose(total(loop), total(tree), rtol=1e-12)
    pick = lambda x, y: (jnp.maximum(x[0], y[0]),)      # noqa: E731
    assert np.array_equal(
        np.asarray(SG._seg_scan_doubling(flag, (v,), pick)[0]),
        np.asarray(SG._seg_scan(flag, (v,), pick)[0]))


def test_doubling_scan_stops_at_the_longest_run_that_is_read():
    """The core's invalid rows sort last as one long run nobody reads:
    the doubling form's trip count follows the runs that ARE read, whose
    results are the tree's; the unread run is left unfinished."""
    lengths = np.array([3, 1, 30, 7, 5000])
    n = int(lengths.sum())
    flag = np.zeros(n, bool)
    flag[np.concatenate([[0], np.cumsum(lengths)[:-1]])] = True
    read = np.arange(n) < n - 5000
    v = jnp.ones(n, jnp.int32)
    add = lambda x, y: (x[0] + y[0],)      # noqa: E731
    want = np.asarray(SG._seg_scan(jnp.asarray(flag), (v,), add)[0])
    got = np.asarray(SG._seg_scan_doubling(
        jnp.asarray(flag), (v,), add, jnp.asarray(read))[0])
    assert np.array_equal(got[read], want[read])
    # 5 rounds for the 30-row run: the 5,000-row run got as far as 32
    assert got[~read].max() == 32 and want[~read].max() == 5000
    assert np.array_equal(np.asarray(SG._seg_scan_doubling(
        jnp.asarray(flag), (v,), add)[0]), want)


def test_sorted_run_core_past_the_tree_scans_row_limit(monkeypatch):
    """The whole core with every segmented scan in the doubling form."""
    df = _frame(n=30_000, seed=36)
    want = _run(HASHED_CONF, df)
    monkeypatch.setattr(SG, "_SCAN_TREE_MAX_ROWS", 0)
    # (another padded_rows: a program of its own, traced under the patch)
    got = _run({**HASHED_CONF, "sdot.segment.target.rows": 1 << 13}, df)
    pd.testing.assert_frame_equal(got, want, check_dtype=False, rtol=1e-9,
                                  atol=1e-9)


# -- the finals stage: run-last rows to the table by one compaction sort -----
#
# Differential against the scatter tier (``build_slots`` + ``dense_groupby``)
# at the kernel's own signature, over the shapes a run-end search used to
# absorb. Float values are whole numbers, so an f32 sum is exact in any
# order and even the ``ff`` (acc, c) pair is bit-identical across tiers.

# name -> (n rows, T slots, key space, valid share)
SHAPES = {
    "T_gt_n": (3000, 8192, 700, 0.9),            # q3's: T = 2n and more
    "T_eq_n": (4096, 4096, 700, 0.9),
    "T_lt_groups": (3000, 256, 700, 0.9),        # '__unres__' > 0
    "all_invalid": (3000, 512, 700, 0.0),
    "one_group": (3000, 64, 1, 1.0),
    "every_row_a_group": (2048, 4096, 0, 1.0),   # key space 0: key = row
    "every_row_a_group_T_eq_n": (2048, 2048, 0, 1.0),
    "trailing_invalid": (3000, 1024, 50, 0.5),
    # T * 64 <= n: the row index alone rides the compaction sort
    "T_much_lt_n": (8192, 128, 40, 0.9),
    "T_much_lt_n_overflow": (8192, 64, 40, 0.9),
}

# name -> (kind, sorted-run tag, scatter tag, integer values, filtered)
AGGS = {
    "count_i32": ("count", "i32", "i32", True, True),
    "sum_i32": ("sum", "i32", "i32", True, False),
    "sum_s64": ("sum", "s64", "i64", True, True),
    "sum_ff": ("sum", "ff", "ff", False, True),
    "min_f32": ("min", "f32", "f32", False, True),
    "max_f32": ("max", "f32", "f32", False, False),
    "min_i32": ("min", "i32", "i32", True, False),
    "max_i32": ("max", "i32", "i32", True, True),
}


def _finals_case(shape, seed=11):
    n, T, n_keys, p_valid = SHAPES[shape]
    rng = np.random.default_rng(seed)
    khi = rng.integers(0, n_keys, n) if n_keys else rng.permutation(n)
    klo = rng.integers(0, 3, n) if n_keys else np.zeros(n)
    valid = rng.random(n) < p_valid
    filt = rng.random(n) < 0.7
    ints = rng.integers(-2**30, 2**30, n)
    small = rng.integers(-40, 40, n)
    # a kernel takes key parts in any shape: two "segments" of rows
    seg = lambda a, dt: jnp.asarray(np.asarray(a).astype(dt)).reshape(2, -1)
    return (n, T, seg(khi, np.int32), seg(klo, np.int32), seg(valid, bool),
            seg(filt, bool), seg(ints, np.int32), seg(small, np.int32))


def _agg_inputs(names, filt, ints, small):
    """(inputs, sorted-run routes, scatter routes) for the named AGGS."""
    inputs, sroutes, routes = [], {}, {}
    for name in names:
        kind, stag, dtag, is_int, filtered = AGGS[name]
        vals = None if kind == "count" else \
            (small if name == "sum_i32" else ints) if is_int \
            else small.astype(jnp.float32)
        inputs.append(G.AggInput(name, kind, vals,
                                 filt if filtered else None, is_int=is_int))
        merged = stag != "ff"
        sroutes[name] = G.Route(name, kind, stag, merged=merged)
        routes[name] = G.Route(name, kind, dtag, merged=merged)
    return inputs, sroutes, routes


def _both_tiers(shape, names):
    n, T, khi, klo, valid, filt, ints, small = _finals_case(shape)
    inputs, sroutes, routes = _agg_inputs(names, filt, ints, small)
    got = SG.sorted_hash_groupby(khi, klo, valid, T, inputs, sroutes)
    slot, tk_hi, tk_lo, unres = H.build_slots(khi, klo, valid, T)
    want = G.dense_groupby(slot, valid, T, inputs, routes)
    want.update({"__tkhi__": tk_hi, "__tklo__": tk_lo,
                 "__unres__": unres.reshape(1)})
    return T, sroutes, routes, got, want


def _assert_same_bits(got, want, what):
    """Exact equality, value for value (under the suite's x64 the scatter
    tier widens an i32 sum's dtype; the values are what must agree)."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype.kind == want.dtype.kind, what
    bad = np.flatnonzero(got != want)
    assert bad.size == 0, (what, bad[:5], got[bad[:5]], want[bad[:5]])


def _assert_routes_match(T, sroutes, routes, got, want, what):
    """Keys and '__unres__' equal everywhere; route outputs equal on the
    occupied slots (an EMPTY slot is dropped by the host, and the scatter
    tier leaves a float min/max's identity there, +-inf, not the route's
    sentinel). Returns False for an overflowed table: retried, not read."""
    for k in ("__tkhi__", "__tklo__", "__unres__"):
        _assert_same_bits(got[k], want[k], what + (k,))
    if int(got["__unres__"][0]):
        return False
    occ = np.asarray(got["__tkhi__"]) != H.EMPTY
    for name, r in sroutes.items():
        for oname, size, dt in r.outputs(T):
            assert got[oname].shape == (size,), oname
            assert got[oname].dtype == {"i32": np.int32,
                                        "f32": np.float32}[dt], oname
        if r.tag == routes[name].tag:
            for oname, _, _ in r.outputs(T):
                _assert_same_bits(np.asarray(got[oname])[occ],
                                  np.asarray(want[oname])[occ],
                                  what + (oname,))
        else:                         # s64 against the scatter tier's i64
            _assert_same_bits(G.combine_route(r, got, T)[occ],
                              G.combine_route(routes[name], want, T)[occ],
                              what + (name,))
    return True


@pytest.mark.parametrize("shape", list(SHAPES))
def test_finals_match_scatter_tier_every_route(shape):
    """All eight routes in one program, over each edge shape: the table
    keys, '__unres__' and every route output equal the scatter tier's."""
    T, sroutes, routes, got, want = _both_tiers(shape, list(AGGS))
    read = _assert_routes_match(T, sroutes, routes, got, want, (shape,))
    assert read == (shape not in ("T_lt_groups", "T_much_lt_n_overflow"))


@pytest.mark.parametrize("agg", list(AGGS))
@pytest.mark.parametrize("shape", ["T_gt_n", "trailing_invalid",
                                   "T_much_lt_n"])
def test_finals_match_scatter_tier_one_route(shape, agg):
    """Each route tag alone (one payload, its own column offsets)."""
    T, sroutes, routes, got, want = _both_tiers(shape, [agg])
    assert set(got) == {o for o, _, _ in sroutes[agg].outputs(T)} \
        | {"__tkhi__", "__tklo__", "__unres__"}
    assert _assert_routes_match(T, sroutes, routes, got, want, (shape,))


def test_occupied_prefix_is_sorted_and_padding_empty():
    T, sroutes, _, got, _ = _both_tiers("T_gt_n", ["count_i32", "min_f32"])
    khi, klo = np.asarray(got["__tkhi__"]), np.asarray(got["__tklo__"])
    n_occ = int((khi != H.EMPTY).sum())
    assert 0 < n_occ < T and (khi[n_occ:] == H.EMPTY).all() \
        and (klo[n_occ:] == H.EMPTY).all()
    packed = H.pack_key(khi[:n_occ], klo[:n_occ])
    assert (np.diff(packed) > 0).all()
    assert (np.asarray(got["count_i32"])[n_occ:] == 0).all()
    assert (np.asarray(got["min_f32"])[n_occ:] == G.F32_MAX).all()


@pytest.mark.parametrize("shape", ["T_gt_n", "T_much_lt_n"])
def test_lowered_program_has_no_loop(shape):
    """The run ends are known from the run starts: no search, so the
    lowered program holds no ``while`` on either way to the table (a
    table-wide binary search for them cost q3 three fifths of its device
    time on the v5e — PERF.md, PR 27)."""
    n, T, khi, klo, valid, filt, ints, small = _finals_case(shape)
    assert (T * SG._TAKE_BELOW <= n) == (shape == "T_much_lt_n")

    def run(khi, klo, valid, filt, ints, small):
        inputs, sroutes, _ = _agg_inputs(list(AGGS), filt, ints, small)
        return SG.sorted_hash_groupby(khi, klo, valid, T, inputs, sroutes)

    text = jax.jit(run).lower(khi, klo, valid, filt, ints, small).as_text()
    assert text.count("stablehlo.sort") == 2
    assert "stablehlo.while" not in text


# -- FD-demoted columns: read at the run's last row, not scanned (PR 33) -----

def _fd_case(shape, seed=5):
    """Key parts, validity and two value columns that are a FUNCTION of
    the key (what the planner's ``anyvalue`` promises), plus a sum."""
    n, T, khi, klo, valid, filt, ints, small = _finals_case(shape, seed)
    dep_i = (khi * 7 + klo * 3 - 11).astype(jnp.int32)
    dep_f = (khi.astype(jnp.float32) * 0.5 - klo.astype(jnp.float32))
    return n, T, khi, klo, valid, dep_i, dep_f, small


def _fd_inputs(dep_i, dep_f, small, same):
    inputs = [G.AggInput("any_i", "max", dep_i, None, is_int=True,
                         same_in_group=same),
              G.AggInput("any_f", "max", dep_f, None, is_int=False,
                         same_in_group=same),
              G.AggInput("s", "sum", small, None, is_int=True)]
    routes = {"any_i": G.Route("any_i", "max", "i32"),
              "any_f": G.Route("any_f", "max", "f32"),
              "s": G.Route("s", "sum", "i32")}
    return inputs, routes


@pytest.mark.parametrize("shape", ["T_gt_n", "trailing_invalid",
                                   "T_much_lt_n"])
def test_same_in_group_reads_the_runs_last_row(shape):
    """A column every row of a group agrees on gives the same table
    whether the core scans it (max) or reads it at the run's last row."""
    n, T, khi, klo, valid, dep_i, dep_f, small = _fd_case(shape)
    out = {}
    for same in (False, True):
        inputs, routes = _fd_inputs(dep_i, dep_f, small, same)
        out[same] = SG.sorted_hash_groupby(khi, klo, valid, T, inputs,
                                           routes)
    assert int(out[True]["__unres__"][0]) == 0
    assert set(out[True]) == set(out[False])
    for k in out[False]:
        _assert_same_bits(out[True][k], out[False][k], (shape, k))


def test_same_in_group_drops_the_segmented_scans():
    """What the flag is for: q18's four demoted columns cost four
    23-level scans of 8.0 M rows, and the chip's compiler did not finish
    the program inside a benchmark run's limit (PERF.md, PR 33). Counted
    here by the scan's slices in the lowered text."""
    n, T, khi, klo, valid, dep_i, dep_f, small = _fd_case("T_gt_n")

    def lowered(same):
        def run(khi, klo, valid, dep_i, dep_f, small):
            inputs, routes = _fd_inputs(dep_i, dep_f, small, same)
            return SG.sorted_hash_groupby(khi, klo, valid, T, inputs,
                                          routes)
        return jax.jit(run).lower(khi, klo, valid, dep_i, dep_f,
                                  small).as_text()

    scanned, read = lowered(False), lowered(True)
    assert scanned.count("stablehlo.sort") == read.count("stablehlo.sort") \
        == 2
    assert scanned.count("stablehlo.maximum") > 10
    assert read.count("stablehlo.maximum") == 0
    assert read.count("stablehlo.slice") < scanned.count("stablehlo.slice") / 4


# -- HAVING on the hashed tier's device-resident table (PR 33) ----------------

HAVING_CONF = {**HASHED_CONF,
               "sdot.engine.groupby.hash.compact.min.slots": 1024,
               "sdot.engine.having.device.min.keys": 1024}


def _having_run(conf, sql, df):
    ctx = sdot.Context(config=conf)
    ctx.ingest_dataframe("t", df)
    r = ctx.sql(sql).to_pandas()
    st = ctx.history.entries()[-1].stats
    assert st.get("hashed"), st
    return r, st


@pytest.mark.parametrize("op,lit", [(">", 230), ("<=", 60), ("=", 151),
                                    (">", 10**6)])
@pytest.mark.parametrize("sortedrun", ["on", "off"])
def test_hashed_having_on_the_device_table(op, lit, sortedrun):
    """TPC-H q18's inner shape: a high-cardinality group-by whose HAVING
    keeps a few groups. The table form counts the passing slots on the
    device and the gather dispatch brings only those (`having_device`,
    `fetch_bytes`); the answer is the host-HAVING form's, row for row."""
    df = _frame(n=60_000, seed=3, n_keys=9000)
    sql = (f"select k, sum(q) as s, count(*) as c from t group by k "
           f"having sum(q) {op} {lit} order by k")
    conf = {**HAVING_CONF, "sdot.engine.groupby.hash.sortedrun": sortedrun}
    got, st = _having_run(conf, sql, df)
    # the same tier with the device HAVING priced out: every group travels
    want, st0 = _having_run(
        {**conf, "sdot.engine.having.device.min.keys": 1 << 30}, sql, df)
    pd.testing.assert_frame_equal(got, want)
    s = df.groupby("k").q.sum()
    n_pass = int({">": s > lit, "<=": s <= lit, "=": s == lit}[op].sum())
    assert len(got) == n_pass and (n_pass > 0) == (lit != 10**6)
    n_groups = df.k.nunique()
    assert st0["having_device"] == 0 and st0["groups"] == n_groups
    assert st["having_device"] == max(64, 1 << (max(n_pass, 1) - 1)
                                      .bit_length())
    assert st["groups"] <= st["having_device"] < n_groups
    assert st["n_dispatch"] == st0["n_dispatch"] == 2
    assert st["sorted_run"] is (sortedrun == "on")


def test_hashed_having_stays_on_the_host_where_the_table_cannot_say():
    """A float total is an (acc, c) pair on the chip and a comparison
    with a non-integer literal is not exact there: the host filters."""
    df = _frame(n=30_000, seed=4, n_keys=5000)
    for sql in ("select k, sum(price) as p from t group by k "
                "having sum(price) > 900 order by k",
                "select k, sum(q) as s from t group by k "
                "having sum(q) > 200.5 order by k"):
        got, st = _having_run(HAVING_CONF, sql, df)
        assert st["having_device"] == 0 and st["groups"] == df.k.nunique()
        col = "price" if "price" in sql else "q"
        s = df.groupby("k")[col].sum()
        assert len(got) == int((s > (900 if col == "price" else 200.5)).sum())


def test_device_having_comparison_is_one_for_both_tiers():
    """``_having_passes`` is what the dense tier's ``_having_mask`` and
    the hashed table's ``_hash_having_mask`` both compare with: a min/max
    whose sentinel survived is NULL, UNKNOWN under any operator, and
    drops; a sum has no NULL."""
    from spark_druid_olap_tpu.parallel import executor as X
    out = {"m": jnp.asarray([5, G.I32_MIN, 30], jnp.int32),
           "s": jnp.asarray([5, 0, 30], jnp.int32),
           "__tkhi__": jnp.asarray([1, 2, X.H.EMPTY], jnp.int32)}
    mx, sm = G.Route("m", "max", "i32"), G.Route("s", "sum", "i32")
    assert X._having_passes(mx, out, "<=", 20).tolist() == [True, False,
                                                            False]
    assert X._having_passes(mx, out, "!=", 5).tolist() == [False, False,
                                                           True]
    assert X._having_passes(sm, out, "<=", 20).tolist() == [True, True,
                                                            False]
    # the hashed table's mask: that, on occupied slots
    assert X._hash_having_mask(("s", ">=", 0), out,
                               {"s": sm}).tolist() == [True, True, False]
    assert X._hash_having_mask(("m", "<", 40), out,
                               {"m": mx}).tolist() == [True, False, False]
