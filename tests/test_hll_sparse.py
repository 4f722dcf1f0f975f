"""The sparse form of the grouped HLL sketch (ops/hll.py, PR 35): rows
sorted by (group, register, rho), a group's ``sum of 2^-rho``, its count
of live registers and its count of distinct coupons taken as
run-boundary differences of three prefix sums, the estimate computed on
the device — never a ``[groups, 2^log2m]`` register block.

What is held here, on the CPU at small sizes:

- against a plain NumPy HLL (hash, register, rho, ``np.maximum.at`` into
  ``[K, m]``, ``estimate``) and a plain count of distinct coupons: the
  form's three integer totals a group EXACTLY (the registers, summed:
  ``sum of 2^(R - rho)`` and the count of live ones; the coupons), its
  answers — the coupons' count up to ``coupon_limit`` of them, the
  registers' estimate past it — to a stated float tolerance;
- the three forms of ``register_form`` on one input, and what it chooses
  on a v5e's constants (``acd`` keeps ``sort``);
- through ``Context.sql`` at SF 0.02: the four statements of the
  deployment ``tpch_sf1_sketch`` against pandas ``nunique`` within 5 %,
  ``mode == "engine"``, under the CPU's defaults (dense registers below
  the medium-K reroute, the hashed tier past it), in the hashed tier's
  scatter core and in its sorted-run core; the record's counters, counted
  from what was copied back, and its ``sketch`` span;
- a historical's ``partial_sketches`` mode still ships raw registers that
  merge to the estimate of the single engine's register block.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.ops import hll as H
from spark_druid_olap_tpu.tools import tpch
from spark_druid_olap_tpu.utils import config as CF

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
M32 = 0xFFFFFFFF

# what estimate_sums may leave the host's float64 estimate by: float32
# rounds the register sum to 24 bits (6e-8) and the quotient and the
# logarithm add an ulp or two each — 1e-6 of the estimate holds that five
# times over — and the device rounds to an integer, which is half a unit
# (one where the host's value lies within that 1e-6 of a half)
EST_RTOL, EST_ATOL = 1e-6, 0.5


def _fmix32(x):
    x = np.asarray(x).astype(np.int32).view(np.uint32).astype(np.uint64)
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & M32
    x ^= x >> 16
    return x


def numpy_registers(key, mask, values, n_keys, log2m):
    """The plain HLL: int64 [n_keys, m] register maxima."""
    h = _fmix32(values)
    m = 1 << log2m
    reg = (h & (m - 1)).astype(np.int64)
    w = h >> log2m
    # position of the first 1-bit of w within its 32 - log2m bits, 1-based
    bitlen = np.zeros(len(w), np.int64)
    nz = w > 0
    bitlen[nz] = np.floor(np.log2(w[nz].astype(np.float64))).astype(
        np.int64) + 1
    rho = (32 - log2m) - bitlen + 1
    live = mask & (key >= 0) & (key < n_keys)
    regs = np.zeros((n_keys, m), np.int64)
    np.maximum.at(regs, (key[live], reg[live]), rho[live])
    return regs


def numpy_coupons(key, mask, values, n_keys, log2m):
    """Per group the distinct coupons of its live rows: the register, rho
    and the low ``_coupon_bits`` bits of the hash above the register's."""
    h = _fmix32(values).astype(np.int64)
    t = H._coupon_bits(log2m)
    w = h >> log2m
    bitlen = np.array([int(x).bit_length() for x in w], np.int64)
    rho = (32 - log2m) - bitlen + 1
    coupon = (((h & ((1 << log2m) - 1)) << H._rho_bits(log2m) | rho)
              << t) | (w & ((1 << t) - 1))
    live = mask & (key >= 0) & (key < n_keys)
    pairs = np.unique(np.stack([key[live].astype(np.int64),
                                coupon[live]]), axis=1)
    return np.bincount(pairs[0], minlength=n_keys)


def sparse_estimate(regs, coupons, log2m):
    """What the sparse form answers: a group's distinct coupons up to
    ``coupon_limit`` of them (HyperLogLog++'s sparse precision), the
    registers' estimate past it."""
    return np.where(coupons <= H.coupon_limit(log2m), coupons,
                    H.estimate(regs))


def register_sums(regs, log2m):
    """What the sparse form holds of a register block: per group the sum
    of 2^(R - rho) over its live registers and their count."""
    top = 32 - log2m + 1
    return (np.where(regs > 0, 1 << (top - regs), 0).sum(axis=1),
            (regs > 0).sum(axis=1))


def _rows(n, n_keys, seed, live=0.7, distinct=200_000):
    rng = np.random.default_rng(seed)
    key = rng.integers(0, n_keys, n).astype(np.int32)
    key[key == 3] = 4                    # group 3 has no row
    if n_keys > 100:
        key[key % 97 == 5] = 6           # nor has one group in 97
    return (key, rng.random(n) < live,
            rng.integers(-distinct, distinct, n).astype(np.int32))


@pytest.mark.parametrize("log2m", [11, 14])
@pytest.mark.parametrize("n_keys,n", [(7, 60_000), (1_000, 120_000),
                                      (40_000, 150_000)])
def test_sparse_form_is_the_numpy_hll(n_keys, n, log2m):
    import jax
    key, mask, values = _rows(n, n_keys, 7 * n_keys + log2m)
    key[: n // 4] = 1                    # one group past any coupon limit
    regs = numpy_registers(key, mask, values, n_keys, log2m)
    want_s, want_live = register_sums(regs, log2m)
    assert (want_live == 0).sum() >= 1 and want_live.max() > 1

    want_coupons = numpy_coupons(key, mask, values, n_keys, log2m)
    limit = H.coupon_limit(log2m)
    # groups on both sides of the limit (7 groups: every one past it)
    assert want_coupons.max() > limit
    assert n_keys == 7 or 0 < want_coupons[want_coupons > 0].min() <= limit

    s, live, coupons = jax.jit(
        lambda k, m, v: H.hll_sums(k, m, v, n_keys, log2m))(
            key, mask, values)
    # the integers: every register's maximum went into these, exactly
    assert np.asarray(s).dtype == np.int32
    assert np.array_equal(
        np.asarray(s).view(np.uint32).astype(np.int64), want_s)
    assert np.array_equal(np.asarray(live), want_live)
    assert np.array_equal(np.asarray(coupons), want_coupons)

    got = np.asarray(jax.jit(
        lambda k, m, v: H.hll_estimates(k, m, v, n_keys, log2m))(
            key, mask, values))
    assert got.shape == (n_keys,) and got.dtype == np.int32
    want = sparse_estimate(regs, want_coupons, log2m)
    np.testing.assert_allclose(got, want, rtol=EST_RTOL, atol=EST_ATOL)
    assert (got[want_live == 0] == 0).all()
    # up to the limit a group is counted, not estimated: its distinct
    # values, but for two that share a coupon (none here)
    counted = (want_coupons > 0) & (want_coupons <= limit)
    live_rows = mask & (key >= 0)
    exact = np.array([len(np.unique(values[live_rows & (key == g)]))
                      for g in np.flatnonzero(counted)[:50]])
    assert np.array_equal(got[np.flatnonzero(counted)[:50]], exact)


def test_estimate_sums_branches_against_the_hosts_estimate():
    """``estimate``'s three branches, each from registers built to land
    in it: linear counting (few live registers), the harmonic mean (every
    register live), every register at rho 1 (the sum wraps to 0), and the
    empty group."""
    import jax
    log2m = 11
    m = 1 << log2m
    rng = np.random.default_rng(5)
    regs = np.zeros((6, m), np.int64)
    regs[0, rng.choice(m, 300, replace=False)] = rng.integers(1, 6, 300)
    regs[1] = rng.integers(1, 12, m)                   # e ~ 20 m: raw
    regs[2] = rng.integers(4, 9, m)                    # e ~ 50 m: raw
    regs[3] = 1                                        # sum = 2^32 -> 0
    regs[4, : m - 1] = rng.integers(1, 3, m - 1)       # one zero: e > 2.5 m
    s, live = register_sums(regs, log2m)
    assert s[3] == 1 << 32
    # as many coupons as would leave every group but the empty one to
    # its registers' estimate, then few enough to count group 0 by them
    past = np.where(live > 0, H.coupon_limit(log2m) + 1, 0).astype(np.int32)
    fn = jax.jit(lambda a, b, c: H.estimate_sums(a, b, c, log2m))
    args = ((s & M32).astype(np.uint32).view(np.int32),
            live.astype(np.int32))
    got = np.asarray(fn(*args, past))
    want = H.estimate(regs)
    assert want[0] < 2.5 * m < want[1] < want[2] and want[5] == 0
    np.testing.assert_allclose(got, want, rtol=EST_RTOL, atol=EST_ATOL)
    counted = past.copy()
    counted[0] = 305
    assert np.asarray(fn(*args, counted)).tolist() \
        == [305] + got[1:].tolist()


# unit costs that pin each form: a free search, a free update, a free sort
# whose search is not
PIN = {"sort": H.RegisterCosts(1e-15, 1e-21, 1.0),
       "scatter": H.RegisterCosts(1.0, 1.0, 1e-15),
       "sparse": H.RegisterCosts(1e-15, 1.0, 1.0)}


def test_three_forms_agree_on_one_input():
    import jax
    n_keys, log2m = 300, 11
    key, mask, values = _rows(50_000, n_keys, 11, distinct=3_000)
    for form, costs in PIN.items():
        assert H.register_form(key.size, n_keys, log2m, costs,
                               sparse_ok=True) == form
    dense = {form: np.asarray(jax.jit(
        lambda k, m, v, c=PIN[form]: H.hll_registers(
            k, m, v, n_keys, log2m, c))(key, mask, values))
        for form in ("sort", "scatter")}
    assert np.array_equal(dense["sort"], dense["scatter"])
    assert np.array_equal(dense["sort"],
                          numpy_registers(key, mask, values, n_keys, log2m))
    s, live, coupons = H.hll_sums(key, mask, values, n_keys, log2m)
    want_s, want_live = register_sums(dense["sort"], log2m)
    assert np.array_equal(np.asarray(s).view(np.uint32), want_s)
    assert np.array_equal(np.asarray(live), want_live)
    assert np.array_equal(np.asarray(coupons),
                          numpy_coupons(key, mask, values, n_keys, log2m))


def test_register_form_on_the_v5e_constants():
    v5e = H.RegisterCosts(float(CF.COST_SORT_ROW.default),
                          float(CF.COST_GATHER_PROBE.default),
                          float(CF.COST_SCATTER_UPDATE.default))
    acd_rows = 8 * 1_000_448
    # acd's shape keeps the form it has, allowed the sparse one or not
    # (8.2 ms against 16.0 on the chip) ...
    assert H.register_form(acd_rows, 7, 11, v5e) == "sort"
    assert H.register_form(acd_rows, 7, 11, v5e, sparse_ok=True) == "sort"
    assert H.register_form(6 * 1_000_448, 7, 11, v5e,
                           sparse_ok=True) == "sort"
    # ... and would leave it at 2^14 registers, where the sort form's
    # search visits 114,688 slots (measured 25.4 ms against 16.0; the
    # issue asked for "sort" here too: the chip disagreed)
    assert H.register_form(acd_rows, 7, 14, v5e) == "sort"
    assert H.register_form(acd_rows, 7, 14, v5e, sparse_ok=True) == "sparse"
    # the deployment's group counts: a block nobody should fetch
    for n_keys in (10_001, 150_001, 1 << 21):
        for log2m in (11, 14, 16):
            assert H.register_form(acd_rows, n_keys, log2m, v5e,
                                   sparse_ok=True) == "sparse"
    # ... which a program that merges partial registers must still take
    for n_keys in (10_001, 150_001):
        assert H.register_form(acd_rows, n_keys, 11, v5e) == "scatter"
    # between: priced. A thousand groups' search outweighs the sort form,
    # and the two-operand sort beats 8.0 M serial updates
    assert H.register_form(acd_rows, 1000, 11, v5e) == "scatter"
    assert H.register_form(acd_rows, 1000, 11, v5e,
                           sparse_ok=True) == "sparse"
    # the CPU fallback's constants never buy a sort of any kind ...
    cpu = sdot.Context().engine._hll_costs(
        [type("Plan", (), {"kind": "hll"})()])
    assert H.register_form(acd_rows, 1000, 11, cpu,
                           sparse_ok=True) == "scatter"
    # ... until the block is past what a send may fetch
    slots = H.DENSE_BLOCK_MAX_BYTES // 4
    assert H.register_form(acd_rows, slots // 2048 - 1, 11, cpu,
                           sparse_ok=True) == "scatter"
    assert H.register_form(acd_rows, slots // 2048, 11, cpu,
                           sparse_ok=True) == "sparse"


# -- through Context.sql ------------------------------------------------------

def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


compare = _load("sketch_harness_compare", "harness", "compare.py")
REF = _load("sketch_reference", "references", "tpch_sketch.py")
with open(os.path.join(BENCH, "statements", "tpch_sketch4.json")) as _f:
    STATEMENTS = json.load(_f)["classes"]
with open(os.path.join(BENCH, "configs", "tpch_sf1_sketch.json")) as _f:
    CONFIG = json.load(_f)
SF = 0.02

# settings beside the deployment's own, the classes the hashed tier runs
# under them (there a sketch is sparse; in the dense tier it is a block
# of registers) and whether its core is the sorted-run one
RANKED = ("uq_customer_top", "uq_partsupp_top")
TIERS = {
    # the CPU's constants: 201 suppliers stay under the medium-K reroute
    # (dense registers, 13 MB a column at 2^14); 3,001 customers' block
    # (197 MB) and the pairs' are past DENSE_BLOCK_MAX_BYTES and reroute
    "cpu_defaults": ({}, RANKED, False),
    # the hashed tier's scatter core for everything, as on any backend
    # whose sort is dear
    "hashed_scatter": ({"sdot.engine.groupby.dense.max.keys": 100},
                       tuple(STATEMENTS), False),
    # what a v5e runs: the medium-K reroute, priced on ITS unit costs
    # (set, they hold on any backend), and the sorted-run core
    "sorted_run": ({"sdot.engine.groupby.dense.max.keys": 100_000,
                    "sdot.engine.groupby.hash.sortedrun": "on",
                    "sdot.engine.groupby.sorted.min.keys": 128,
                    **{e.key: e.default for e in (
                        CF.COST_SORT_ROW, CF.COST_GATHER_PROBE,
                        CF.COST_SCATTER_UPDATE)}},
                   tuple(STATEMENTS), True),
}


@pytest.fixture(scope="module")
def frames():
    tables = tpch.generate(SF)
    return tables, tpch.flatten(tables)


@pytest.fixture(scope="module", params=list(TIERS))
def tier(request, frames):
    settings, hashed, sorted_run = TIERS[request.param]
    ctx = sdot.Context({**CONFIG["settings"], **settings})
    tables, flat = tpch.setup_context(ctx, sf=SF, target_rows=1 << 15)
    data = {**tables, "tpch_flat": flat}
    yield ctx, data, hashed, sorted_run
    ctx.close()


@pytest.mark.parametrize("cls", list(STATEMENTS))
def test_statement_against_pandas_nunique(tier, cls):
    ctx, data, hashed, sorted_run = tier
    st = STATEMENTS[cls]
    got = ctx.sql(st["sql"]).to_pandas()
    rec = ctx.history.entries()[-1].stats
    want = getattr(REF, st["reference"].split(":")[1])(data)
    # the harness's own comparison under the deployment's guarantees:
    # EVERY group of the answer within 5 % of nunique, the rest exact
    g = CONFIG["guarantees"]
    compare.check_frames(cls, got, want, approx=st["approx"],
                         rtol=g["float_rtol"],
                         approx_rtol=g["approx_count_distinct_rtol"])
    assert rec["mode"] == "engine" and rec["waves"] == 1, rec
    assert bool(rec.get("hashed")) == (cls in hashed), rec
    assert bool(rec.get("sorted_run")) == (cls in hashed and sorted_run), rec
    n_sketches = len(st["approx"])
    if cls in hashed:
        # one int32 a sketch column for every row of the table that was
        # copied back — the top-k's, the occupied slots' power of two, or
        # the whole table — whatever log2m
        assert rec["hll_form"] == "sparse", rec
        rows = rec["topk_device"] or rec["hash_compact_k"] \
            or rec["hash_slots"]
        assert rec["sketch_fetch_bytes"] == 4 * rows * n_sketches, rec
    else:
        # a [groups, 2^log2m] block a sketch column
        assert rec["hll_form"] in ("sort", "scatter"), rec
        rows = len(want)
        assert rec["sketch_fetch_bytes"] == 4 * n_sketches * (
            rec["sketch_groups"]
            << CONFIG["settings"]["sdot.engine.hll.log2m"]), rec
    assert rec["sketch_groups"] >= rows >= len(got), rec
    assert rec["sketch_fetch_bytes"] <= rec["fetch_bytes"]
    # sparse: live (group, register) pairs, at most one a row; dense:
    # the block's slots, the masked rows' sentinel group with them
    assert 0 < rec["hll_slots"] <= 2 * len(data["tpch_flat"]) \
        or cls not in hashed
    # the host's part is a span under decode, one a sketch column
    spans = rec["spans"]
    sketch = [s for s in spans if s[0] == "sketch"]
    assert len(sketch) == n_sketches
    assert all(spans[s[3]][0] == "decode" for s in sketch)
    assert "sketch" not in rec["phases"]


def test_sorted_run_core_equals_the_scatter_core(frames):
    """The two cores of the hashed tier give one estimate column: a
    group past the coupon limit reads what the dense tier's register
    block reads (the same registers), one up to it its distinct values
    themselves, where the block's linear counting is off by the values
    that share a register."""
    log2m = 11
    answers = {}
    for name, settings in (
            # no reroute: the dense tier's register block, estimated by
            # the host (2^11 registers: 3,001 customers' block is 25 MB)
            ("dense", {"sdot.engine.groupby.sorted.min.keys": 0}),
            ("hashed_scatter", TIERS["hashed_scatter"][0]),
            ("sorted_run", {**TIERS["sorted_run"][0],
                            "sdot.engine.groupby.dense.max.keys": 100})):
        ctx = sdot.Context({**CONFIG["settings"], **settings,
                            "sdot.engine.hll.log2m": log2m})
        tpch.setup_context(ctx, sf=SF, target_rows=1 << 15)
        answers[name] = {}
        for cls in ("uq_supplier", "uq_customer_top"):
            answers[name][cls] = ctx.sql(STATEMENTS[cls]["sql"]) \
                .to_pandas().sort_values(STATEMENTS[cls]["columns"][0])
            rec = ctx.history.entries()[-1].stats
            assert (rec["hll_form"] == "sparse") == (name != "dense"), rec
        ctx.close()
    tables, flat = frames
    data = {**tables, "tpch_flat": flat}
    sides = set()
    for cls, dense in answers["dense"].items():
        st = STATEMENTS[cls]
        exact = getattr(REF, st["reference"].split(":")[1])(data) \
            .sort_values(st["columns"][0])
        for col in st["approx"]:
            counted = exact[col].to_numpy() <= H.coupon_limit(log2m)
            sides |= set(counted.tolist())
            want = np.where(counted, exact[col], dense[col])
            assert (dense[col].to_numpy() != want).any() or not counted.any()
            for name in ("hashed_scatter", "sorted_run"):
                assert answers[name][cls][col].tolist() == want.tolist(), \
                    (name, cls, col)
    assert sides == {True, False}


def test_statement_without_a_sketch_says_nothing_of_one():
    ctx = sdot.Context()
    tpch.setup_context(ctx, sf=0.002)
    ctx.sql("select l_suppkey, count(*) as n from lineitem "
            "group by l_suppkey").to_pandas()
    rec = ctx.history.entries()[-1].stats
    assert not {"hll_form", "sketch_fetch_bytes", "sketch_groups"} \
        & set(rec)
    assert not [s for s in rec["spans"] if s[0] == "sketch"]


def test_theta_and_kll_keep_their_fallback_over_the_hashed_tier():
    ctx = sdot.Context({"sdot.engine.groupby.dense.max.keys": 64})
    tables, _ = tpch.setup_context(ctx, sf=0.002)
    got = ctx.sql("select l_partkey, approx_count_distinct_theta(l_suppkey) "
                  "as supps from lineitem group by l_partkey").to_pandas()
    rec = ctx.history.entries()[-1].stats
    assert rec["mode"].startswith("host (theta / kll sketch"), rec["mode"]
    assert len(got) == tables["lineitem"].l_partkey.nunique()
    # ... while HLL over the same key space runs on the engine
    ctx.sql("select l_partkey, approx_count_distinct(l_suppkey) as supps "
            "from lineitem group by l_partkey").to_pandas()
    rec = ctx.history.entries()[-1].stats
    assert rec["mode"] == "engine" and rec["hashed"], rec


# -- a historical's partial registers ----------------------------------------

def test_partial_sketches_ship_raw_registers_that_merge(frames):
    """``partial_sketches`` (cluster historicals) keeps the dense form
    whatever the group count: two halves' register blocks, merged by
    elementwise max, give the estimate of the single engine's register
    block — and a program free to choose takes the sparse form here."""
    from spark_druid_olap_tpu.ir import spec as S
    log2m = 11
    spec = S.GroupByQuerySpec(
        "tpch_flat", (S.DimensionSpec("o_custkey", "o_custkey"),),
        (S.AggregationSpec("cardinality", "parts", field="l_partkey"),))
    tables, flat = frames
    cut = len(flat) // 2

    def context(frame, **settings):
        ctx = sdot.Context({"sdot.cache.enabled": False,
                            "sdot.engine.hll.log2m": log2m, **settings})
        ctx.ingest_dataframe("tpch_flat", frame, time_column="l_shipdate",
                             target_rows=1 << 15)
        return ctx

    whole = context(flat)
    single = whole.engine.execute(spec).to_pandas() \
        .set_index("o_custkey").parts
    assert whole.engine.last_stats["hll_form"] == "sparse"
    whole.close()
    # ... and in a register block where the reroute is off
    whole = context(flat, **{"sdot.engine.groupby.sorted.min.keys": 0})
    block = whole.engine.execute(spec).to_pandas() \
        .set_index("o_custkey").parts
    assert whole.engine.last_stats["hll_form"] in ("sort", "scatter")
    whole.close()

    merged = {}
    for part in (flat.iloc[:cut], flat.iloc[cut:]):
        ctx = context(part.reset_index(drop=True))
        ctx.engine.partial_sketches = True
        got = ctx.engine.execute(spec)
        rec = ctx.engine.last_stats
        assert rec["hll_form"] in ("sort", "scatter"), rec
        assert rec["sketch_fetch_bytes"] \
            == 4 * rec["sketch_groups"] << log2m
        for k, regs in zip(got.data["o_custkey"], got.data["parts"]):
            regs = np.asarray(regs)
            assert regs.shape == (1 << log2m,)
            merged[k] = np.maximum(merged.get(k, 0), regs)
        ctx.close()
    keys = sorted(merged)
    est = np.round(H.estimate(np.stack([merged[k] for k in keys])))
    assert keys == sorted(single.index) == sorted(block.index)
    # the merged registers are the single engine's registers ...
    assert est.tolist() == block.loc[keys].tolist()
    want = flat.groupby("o_custkey").l_partkey.nunique().loc[keys]
    assert np.abs(est - want).max() / want.max() < 0.2
    # ... whose sparse form counts these groups' few coupons instead:
    # the values themselves, which the registers' linear counting only
    # approaches
    assert want.max() <= H.coupon_limit(log2m)
    assert single.loc[keys].tolist() == want.tolist()
    assert (est != want).any()
