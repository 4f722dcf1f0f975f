"""Shared-scan multi-query execution (parallel/sharedscan.py).

Differential tests: a batch of concurrent eligible queries coalesced into
one fused device dispatch must return bit-identical answers to the same
queries run sequentially with coalescing disabled — across mixed filters,
granularities, query types (GroupBy / Timeseries / TopN), datasources
(TPC-H + SSB stars), fallback shapes, and mid-batch cancellation. Plus
the deterministic perf smoke: the fused batch must report fewer device
dispatches and positive bind savings (counted via ``dispatch_counts`` and
coalescer stats, never wall time).
"""

import threading
import time

import numpy as np
import pandas as pd
import pytest

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.parallel.executor import QueryCancelled, QueryEngine
from spark_druid_olap_tpu.segment.ingest import ingest_dataframe
from spark_druid_olap_tpu.segment.store import SegmentStore
from spark_druid_olap_tpu.utils.config import Config
from spark_druid_olap_tpu.tools import ssb, tpch

from conftest import assert_frames_equal, make_sales_df


# -- harness ------------------------------------------------------------------

# Wide hold window so every thread of a batch reliably joins the same
# group even under CI scheduling jitter; the waiters poll their own
# cancel/timeout checks every 20ms, so a wide window stays responsive.
WINDOW_MS = 500.0


def _engine(store, **overrides):
    cfg = {"sdot.sharedscan.enabled": True,
           "sdot.wlm.batch.window.ms": WINDOW_MS,
           "sdot.wlm.enabled": False}
    cfg.update(overrides)
    return QueryEngine(store, config=Config(cfg))


def _ref_engine(store, **overrides):
    cfg = {"sdot.sharedscan.enabled": False, "sdot.wlm.enabled": False}
    cfg.update(overrides)
    return QueryEngine(store, config=Config(cfg))


def _run_concurrent(eng, specs, collect_stats=False):
    """Fire all specs at once (barrier start) and return per-query results
    (frames), errors, and optionally the per-thread last_stats snapshots."""
    n = len(specs)
    res, errs, stats = [None] * n, [None] * n, [None] * n
    bar = threading.Barrier(n)

    def worker(i):
        bar.wait()
        try:
            res[i] = eng.execute(specs[i]).to_pandas()
            if collect_stats:
                stats[i] = dict(eng.last_stats)
        except Exception as e:          # noqa: BLE001 - surfaced via errs
            errs[i] = e

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return res, errs, stats


def _diff(eng, eng_ref, specs, min_coalesced=2):
    """Differential: concurrent coalesced answers == sequential answers."""
    before = eng.sharedscan.stats()["queries_coalesced"]
    ref = [eng_ref.execute(q).to_pandas() for q in specs]
    res, errs, _ = _run_concurrent(eng, specs)
    assert not any(errs), [e for e in errs if e]
    for got, want in zip(res, ref):
        assert_frames_equal(got, want)
    gained = eng.sharedscan.stats()["queries_coalesced"] - before
    assert gained >= min_coalesced, (
        f"expected >= {min_coalesced} coalesced constituents, got {gained}: "
        f"{eng.sharedscan.stats()}")


# -- sales-store batches ------------------------------------------------------

AGGS = (S.AggregationSpec("doublesum", "revenue", field="price"),
        S.AggregationSpec("longsum", "units", field="qty"),
        S.AggregationSpec("count", "n"))


def _sales_batch():
    """Mixed shapes over one datasource: plain GroupBy, filtered GroupBy,
    monthly Timeseries, interval-restricted Timeseries, TopN."""
    return [
        S.GroupByQuerySpec("sales", (S.DimensionSpec("region", "region"),),
                           AGGS),
        S.GroupByQuerySpec("sales", (S.DimensionSpec("flag", "flag"),),
                           AGGS, filter=S.SelectorFilter("status", "O")),
        S.TimeseriesQuerySpec("sales", AGGS,
                              granularity=S.Granularity("month")),
        S.TimeseriesQuerySpec(
            "sales", AGGS,
            intervals=((int(pd.Timestamp("2015-03-01").value // 10**6),
                        int(pd.Timestamp("2016-02-01").value // 10**6)),)),
        S.TopNQuerySpec("sales", S.DimensionSpec("product", "product"),
                        "revenue", 7, AGGS),
    ]


def test_sales_mixed_batch_matches_sequential(store):
    eng = _engine(store)
    _diff(eng, _ref_engine(store), _sales_batch(), min_coalesced=4)


def test_repeat_batches_reuse_compile_cache(store):
    """Second identical batch must coalesce again (and hit the fused
    program cache rather than recompiling per batch)."""
    eng = _engine(store)
    specs = _sales_batch()[:3]
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    for _ in range(2):
        res, errs, _ = _run_concurrent(eng, specs)
        assert not any(errs), [e for e in errs if e]
        for got, want in zip(res, ref):
            assert_frames_equal(got, want)
    st = eng.sharedscan.stats()
    assert st["groups_coalesced"] >= 2
    n_fused = sum(1 for sig in eng._programs if sig and sig[0] == "aggmulti")
    assert n_fused == 1, "identical batches must share one fused program"


# -- TPC-H / SSB differential batches ----------------------------------------

@pytest.fixture(scope="module")
def tpch_ctx():
    ctx = sdot.Context({"sdot.sharedscan.enabled": True,
                        "sdot.wlm.batch.window.ms": WINDOW_MS})
    tpch.setup_context(ctx, sf=0.002, target_rows=4096, flat_only=True)
    return ctx


@pytest.fixture(scope="module")
def ssb_ctx():
    ctx = sdot.Context({"sdot.sharedscan.enabled": True,
                        "sdot.wlm.batch.window.ms": WINDOW_MS})
    ssb.setup_context(ctx, sf=0.003, target_rows=4096, flat_only=True)
    return ctx


def test_tpch_mixed_batch_matches_sequential(tpch_ctx):
    aggs = (S.AggregationSpec("doublesum", "revenue",
                              field="l_extendedprice"),
            S.AggregationSpec("longsum", "qty", field="l_quantity"),
            S.AggregationSpec("count", "n"))
    specs = [
        S.GroupByQuerySpec("tpch_flat",
                           (S.DimensionSpec("l_returnflag", "l_returnflag"),
                            S.DimensionSpec("l_linestatus", "l_linestatus")),
                           aggs),
        S.GroupByQuerySpec("tpch_flat",
                           (S.DimensionSpec("c_mktsegment", "seg"),),
                           aggs, filter=S.SelectorFilter("l_returnflag", "R")),
        S.TimeseriesQuerySpec("tpch_flat", aggs,
                              granularity=S.Granularity("year")),
        S.TopNQuerySpec("tpch_flat", S.DimensionSpec("p_brand", "p_brand"),
                        "revenue", 5, aggs),
    ]
    eng = tpch_ctx.engine
    _diff(eng, _ref_engine(eng.store), specs, min_coalesced=3)


def test_ssb_mixed_batch_matches_sequential(ssb_ctx):
    aggs = (S.AggregationSpec("longsum", "revenue", field="lo_revenue"),
            S.AggregationSpec("longsum", "qty", field="lo_quantity"),
            S.AggregationSpec("count", "n"))
    specs = [
        S.GroupByQuerySpec("ssb_flat",
                           (S.DimensionSpec("c_region", "c_region"),), aggs),
        S.GroupByQuerySpec("ssb_flat",
                           (S.DimensionSpec("p_category", "p_category"),),
                           aggs, filter=S.SelectorFilter("s_region",
                                                         "AMERICA")),
        S.TimeseriesQuerySpec("ssb_flat", aggs,
                              granularity=S.Granularity("year")),
    ]
    eng = ssb_ctx.engine
    _diff(eng, _ref_engine(eng.store), specs, min_coalesced=2)


# -- cache-key isolation ------------------------------------------------------

def test_constituents_populate_cache_under_own_keys(store):
    """Each coalesced constituent must land in the result cache under its
    own canonical key: a later solo re-run of every member is a hit and
    returns the identical frame."""
    eng = _engine(store, **{"sdot.cache.enabled": True})
    specs = _sales_batch()[:4]
    res, errs, stats = _run_concurrent(eng, specs, collect_stats=True)
    assert not any(errs), [e for e in errs if e]
    assert all(s.get("cache") == "miss" for s in stats)
    assert eng.sharedscan.stats()["queries_coalesced"] >= 3
    for q, fused_frame in zip(specs, res):
        again = eng.execute(q).to_pandas()       # solo, same thread
        assert eng.last_stats.get("cache") == "hit", (q, eng.last_stats)
        assert_frames_equal(again, fused_frame)


# -- ineligible shapes fall back, correctly ----------------------------------

def test_select_paging_never_coalesces(store):
    """Select (raw-row paging) is not an engine aggregation shape — it must
    run solo even when fired inside an eligible batch."""
    eng = _engine(store)
    sel = S.SelectQuerySpec("sales", ("region", "qty"),
                            filter=S.SelectorFilter("status", "F"),
                            page_size=100)
    assert not eng.sharedscan.should_try(sel)
    specs = [_sales_batch()[0], _sales_batch()[2], sel]
    before = eng.sharedscan.stats()["queries_coalesced"]
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    res, errs, _ = _run_concurrent(eng, specs)
    assert not any(errs), [e for e in errs if e]
    for got, want in zip(res, ref):
        assert_frames_equal(got, want)
    # only the two aggregate queries may have fused
    assert eng.sharedscan.stats()["queries_coalesced"] - before <= 2


def test_different_datasources_form_different_groups(sales_df):
    st = SegmentStore()
    st.register(ingest_dataframe("sales", sales_df, time_column="ts",
                                 target_rows=4096))
    st.register(ingest_dataframe("sales_eu", make_sales_df(n=8000, seed=11),
                                 time_column="ts", target_rows=4096))
    eng = _engine(st)
    gb = lambda ds: S.GroupByQuerySpec(  # noqa: E731
        ds, (S.DimensionSpec("region", "region"),), AGGS)
    ts = lambda ds: S.TimeseriesQuerySpec(  # noqa: E731
        ds, AGGS, granularity=S.Granularity("month"))
    specs = [gb("sales"), ts("sales"), gb("sales_eu"), ts("sales_eu")]
    ref = [_ref_engine(st).execute(q).to_pandas() for q in specs]
    res, errs, stats = _run_concurrent(eng, specs, collect_stats=True)
    assert not any(errs), [e for e in errs if e]
    for got, want in zip(res, ref):
        assert_frames_equal(got, want)
    groups = {}
    for q, s in zip(specs, stats):
        ss = s.get("sharedscan")
        if ss:
            groups.setdefault(q.datasource, set()).add(ss["group"])
    for ds_name, gids in groups.items():
        assert len(gids) == 1, (ds_name, gids)
    if "sales" in groups and "sales_eu" in groups:
        assert groups["sales"].isdisjoint(groups["sales_eu"]), (
            "a coalesced group crossed datasources")


def test_host_tier_residual_falls_back_solo(store):
    """A member whose lane cannot run on the dense device tier (key
    cardinality above the dense cap -> hashed/host tier) must fall back to
    its own solo execution while the rest of the batch still fuses."""
    eng = _engine(store, **{"sdot.engine.groupby.dense.max.keys": 8})
    specs = [
        # flag (3 values) and status (2 values): under the cap, fusable
        S.GroupByQuerySpec("sales", (S.DimensionSpec("flag", "flag"),),
                           AGGS),
        S.GroupByQuerySpec("sales", (S.DimensionSpec("status", "status"),),
                           AGGS),
        # product (50 values): over the cap -> hashed tier, solo fallback
        S.GroupByQuerySpec("sales", (S.DimensionSpec("product", "product"),),
                           AGGS),
    ]
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    res, errs, _ = _run_concurrent(eng, specs)
    assert not any(errs), [e for e in errs if e]
    for got, want in zip(res, ref):
        assert_frames_equal(got, want)
    st = eng.sharedscan.stats()
    assert st["queries_coalesced"] >= 2
    assert st["fallbacks"] >= 1, st


# -- cancellation -------------------------------------------------------------

def test_cancel_one_of_the_batch(store):
    """Cancelling one constituent during the hold window drops only that
    member (QueryCancelled); the survivors' fused answers are unchanged."""
    eng = _engine(store, **{"sdot.wlm.batch.window.ms": 800.0})
    victim = S.GroupByQuerySpec(
        "sales", (S.DimensionSpec("product", "product"),), AGGS,
        context=S.QueryContext(query_id="sharedscan-victim"))
    survivors = [_sales_batch()[0], _sales_batch()[2]]
    specs = survivors + [victim]
    ref = [_ref_engine(store).execute(q).to_pandas() for q in survivors]

    n = len(specs)
    res, errs = [None] * n, [None] * n
    bar = threading.Barrier(n + 1)      # +1: the cancelling main thread

    def worker(i):
        bar.wait()
        try:
            res[i] = eng.execute(specs[i]).to_pandas()
        except Exception as e:          # noqa: BLE001
            errs[i] = e

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    bar.wait()
    time.sleep(0.2)                     # well inside the 800ms hold window
    assert eng.cancel("sharedscan-victim")
    for t in threads:
        t.join()

    assert isinstance(errs[n - 1], QueryCancelled), errs[n - 1]
    for i, want in enumerate(ref):
        assert errs[i] is None, errs[i]
        assert_frames_equal(res[i], want)


# -- WLM handoff --------------------------------------------------------------

def test_wlm_queue_hands_off_into_open_group(store):
    """Queries queued behind a full lane are handed to an open coalesced
    group by the admission poll loop instead of waiting for a slot."""
    eng = _engine(store, **{
        "sdot.wlm.enabled": True,
        "sdot.wlm.lanes": "interactive:slots=1,queue=16",
        "sdot.wlm.default.lane": "interactive",
        "sdot.wlm.batch.cost.threshold": 0})
    specs = _sales_batch()[:4]
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    res, errs, _ = _run_concurrent(eng, specs)
    assert not any(errs), [e for e in errs if e]
    for got, want in zip(res, ref):
        assert_frames_equal(got, want)
    st = eng.wlm.stats()
    assert st["sharedscan"]["queries_coalesced"] >= 2
    assert st["sharedscan"]["wlm_handoffs"] >= 1, st
    lane = next(l for l in st["lanes"] if l["lane"] == "interactive")
    assert lane["coalesced_handoff"] >= 1, lane


# -- deterministic perf smoke (CI gate) ---------------------------------------

def test_coalesced_batch_saves_dispatches_and_binds(store):
    """The CI perf gate: a 4-query coalesced batch must cost fewer device
    dispatches than sequential execution and must report positive bind
    savings. Counted via the engine's monotone ``dispatch_counts`` and the
    coalescer's stats — never wall time, so this is jitter-free."""
    specs = _sales_batch()[:4]

    eng_off = _ref_engine(store)
    d0 = eng_off.dispatch_counts[0]
    for q in specs:
        eng_off.execute(q)
    seq_dispatches = eng_off.dispatch_counts[0] - d0
    assert seq_dispatches >= len(specs)

    eng_on = _engine(store)
    per_thread = [0] * len(specs)
    errs = [None] * len(specs)
    bar = threading.Barrier(len(specs))

    def worker(i):
        bar.wait()
        base = eng_on.dispatch_counts[0]     # thread-local counter
        try:
            eng_on.execute(specs[i])
            per_thread[i] = eng_on.dispatch_counts[0] - base
        except Exception as e:              # noqa: BLE001
            errs[i] = e

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(specs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not any(errs), [e for e in errs if e]

    coal_dispatches = sum(per_thread)
    st = eng_on.sharedscan.stats()
    assert st["queries_coalesced"] == len(specs), st
    # one fused dispatch replaced four solo dispatches
    assert coal_dispatches < seq_dispatches, (coal_dispatches,
                                              seq_dispatches)
    assert seq_dispatches - coal_dispatches >= len(specs) - 1
    assert st["dispatches_saved"] >= len(specs) - 1, st
    # the union bind is strictly smaller than four per-query binds
    assert st["binds_saved_bytes"] > 0, st


# -- cross-lane fusion planner (predicate CSE) --------------------------------

# the canned 4-lane dashboard storm: every lane carries the same global
# selector conjunct (a dashboard's tenant/time filter), plus a private
# residual — the planner must lower `status = 'O'` exactly once
_SHARED = S.SelectorFilter("status", "O")


def _storm_batch():
    return [
        S.GroupByQuerySpec("sales", (S.DimensionSpec("region", "region"),),
                           AGGS, filter=_SHARED),
        S.GroupByQuerySpec(
            "sales", (S.DimensionSpec("flag", "flag"),), AGGS,
            filter=S.LogicalFilter("and", (
                _SHARED, S.SelectorFilter("region", "east")))),
        S.TimeseriesQuerySpec(
            "sales", AGGS, granularity=S.Granularity("month"),
            filter=S.LogicalFilter("and", (
                _SHARED,
                S.BoundFilter("qty", lower=10, numeric=True)))),
        S.TopNQuerySpec("sales", S.DimensionSpec("product", "product"),
                        "revenue", 7, AGGS, filter=_SHARED),
    ]


def _fusion_delta(eng, fn):
    """Run ``fn`` and return the delta of the engine's fusion counters."""
    f0 = eng.sharedscan.stats()["fusion"]
    fn()
    f1 = eng.sharedscan.stats()["fusion"]
    return {k: f1[k] - f0[k] for k in f1 if k not in ("cse_hit_rate",)}


def test_fusion_identical_subfilters_across_lanes(store):
    """Identical sub-filters across lanes must evaluate once: the storm
    coalesces, answers match sequential exactly, and the planner reports
    cross-lane sharing on deterministic counters."""
    eng = _engine(store)
    d = _fusion_delta(
        eng, lambda: _diff(eng, _ref_engine(store), _storm_batch(),
                           min_coalesced=3))
    assert d["groups"] >= 1, d
    assert d["plan_fallbacks"] == 0, d
    assert d["shared_predicates"] > 0, d
    assert d["predicate_evals_saved"] > 0, d


def test_fusion_partially_overlapping_trees(store):
    """Partially-overlapping AND trees (one shared conjunct, different
    residuals, one lane with commuted operand order) unify on canonical
    keys and stay bit-identical to sequential execution."""
    eng = _engine(store)
    shared = S.BoundFilter("qty", lower=5, upper=40, numeric=True)
    east = S.SelectorFilter("region", "east")
    specs = [
        S.GroupByQuerySpec("sales", (S.DimensionSpec("region", "region"),),
                           AGGS, filter=S.LogicalFilter("and", (shared,
                                                                east))),
        # commuted operand order: same canonical key as the lane above
        S.GroupByQuerySpec("sales", (S.DimensionSpec("flag", "flag"),),
                           AGGS, filter=S.LogicalFilter("and", (
                               S.SelectorFilter("status", "F"), shared))),
        S.TimeseriesQuerySpec("sales", AGGS,
                              granularity=S.Granularity("month"),
                              filter=shared),
    ]
    d = _fusion_delta(
        eng, lambda: _diff(eng, _ref_engine(store), specs, min_coalesced=2))
    assert d["shared_predicates"] > 0, d
    assert d["predicate_evals_saved"] > 0, d


def test_fusion_not_or_nesting(store):
    """NOT/OR nesting: shared sub-predicates inside negations and
    disjunctions still unify (OR operands sort canonically), and the
    all-true short-circuit semantics survive CSE."""
    eng = _engine(store)
    ew = S.LogicalFilter("or", (S.SelectorFilter("region", "east"),
                                S.SelectorFilter("region", "west")))
    we = S.LogicalFilter("or", (S.SelectorFilter("region", "west"),
                                S.SelectorFilter("region", "east")))
    specs = [
        S.GroupByQuerySpec("sales", (S.DimensionSpec("flag", "flag"),),
                           AGGS, filter=S.LogicalFilter("not", (ew,))),
        # commuted OR: canonically identical to `ew`
        S.GroupByQuerySpec("sales", (S.DimensionSpec("status", "status"),),
                           AGGS, filter=we),
        S.TimeseriesQuerySpec(
            "sales", AGGS, granularity=S.Granularity("month"),
            filter=S.LogicalFilter("and", (
                ew, S.LogicalFilter("not", (
                    S.SelectorFilter("status", "F"),))))),
    ]
    d = _fusion_delta(
        eng, lambda: _diff(eng, _ref_engine(store), specs, min_coalesced=2))
    assert d["shared_predicates"] > 0, d
    assert d["predicate_evals_saved"] > 0, d


def test_fusion_dense_cap_fallback_parity(store):
    """With fusion on, a lane over the dense key cap still falls back to
    its own solo execution (routing tiers never change) while the
    remaining lanes fuse WITH cross-lane CSE — all answers exact."""
    eng = _engine(store, **{"sdot.engine.groupby.dense.max.keys": 8})
    specs = [
        S.GroupByQuerySpec("sales", (S.DimensionSpec("flag", "flag"),),
                           AGGS, filter=_SHARED),
        S.GroupByQuerySpec("sales", (S.DimensionSpec("status", "status"),),
                           AGGS, filter=S.LogicalFilter("and", (
                               _SHARED, S.BoundFilter("qty", lower=3,
                                                      numeric=True)))),
        # product (50 values) exceeds the cap -> hashed tier, solo
        S.GroupByQuerySpec("sales", (S.DimensionSpec("product", "product"),),
                           AGGS, filter=_SHARED),
    ]
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    f0 = eng.sharedscan.stats()["fusion"]
    res, errs, _ = _run_concurrent(eng, specs)
    assert not any(errs), [e for e in errs if e]
    for got, want in zip(res, ref):
        assert_frames_equal(got, want)
    st = eng.sharedscan.stats()
    assert st["fallbacks"] >= 1, st
    assert st["fusion"]["shared_predicates"] - f0["shared_predicates"] > 0
    assert st["fusion"]["plan_fallbacks"] == f0["plan_fallbacks"]


def test_fusion_compile_cache_key_isolation(store):
    """Two storms that differ ONLY in a shared sub-predicate must compile
    two distinct fused programs (the fusion plan folds into the cache
    key) and each must return its own correct answers."""
    eng = _engine(store)

    def storm(shared):
        return [
            S.GroupByQuerySpec("sales",
                               (S.DimensionSpec("region", "region"),),
                               AGGS, filter=shared),
            S.TimeseriesQuerySpec(
                "sales", AGGS, granularity=S.Granularity("month"),
                filter=S.LogicalFilter("and", (
                    shared, S.SelectorFilter("region", "west")))),
        ]

    specs_o = storm(S.SelectorFilter("status", "O"))
    specs_f = storm(S.SelectorFilter("status", "F"))
    _diff(eng, _ref_engine(store), specs_o, min_coalesced=2)
    _diff(eng, _ref_engine(store), specs_f, min_coalesced=2)
    n_fused = sum(1 for sig in eng._programs if sig and sig[0] == "aggmulti")
    assert n_fused == 2, (
        "storms differing only in a shared sub-predicate must not share "
        f"a fused program (got {n_fused})")


def test_fusion_off_matches_on(store):
    """Kill switch differential: the same storm with the fusion planner
    disabled (pre-fusion fused program) returns identical answers, and
    the two configurations compile under distinct program keys."""
    eng_on = _engine(store)
    eng_off = _engine(store,
                      **{"sdot.sharedscan.fusion.enabled": False})
    specs = _storm_batch()
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    for eng in (eng_on, eng_off):
        res, errs, _ = _run_concurrent(eng, specs)
        assert not any(errs), [e for e in errs if e]
        for got, want in zip(res, ref):
            assert_frames_equal(got, want)
    d = eng_off.sharedscan.stats()["fusion"]
    assert d["predicate_evals_saved"] == 0, d
    assert d["column_streams_saved"] == 0, d


def test_fusion_smoke_canned_storm(store):
    """The CI deterministic-counter smoke (tier-1, CPU): the canned
    4-lane storm must report column_streams_saved > 0 (each union column
    streams once instead of once per lane) with exact-answer parity, and
    every fused constituent must surface the per-group fusion counters
    in its own stats."""
    eng = _engine(store)
    specs = _storm_batch()
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    f0 = eng.sharedscan.stats()["fusion"]
    res, errs, stats = _run_concurrent(eng, specs, collect_stats=True)
    assert not any(errs), [e for e in errs if e]
    for got, want in zip(res, ref):
        assert_frames_equal(got, want)
    f1 = eng.sharedscan.stats()["fusion"]
    assert f1["column_streams_saved"] - f0["column_streams_saved"] > 0, f1
    assert f1["predicate_evals_saved"] - f0["predicate_evals_saved"] > 0, f1
    fused = [s["sharedscan"]["fusion"] for s in stats
             if s.get("sharedscan")]
    assert fused, "no constituent reported sharedscan stats"
    for fc in fused:
        assert fc is not None
        assert fc["column_streams_saved"] > 0, fc
        assert fc["shared_predicates"] > 0, fc


# -- one dense decode (QueryEngine._decode_dense), two spans ------------------

_PARTNER = S.GroupByQuerySpec("sales", (S.DimensionSpec("flag", "flag"),),
                              AGGS)
_HLL = S.AggregationSpec("cardinality", "products", field="product")
DECODE_CASES = {
    # dictionary decode + an HLL estimate beside exact sums
    "dims_hll": (S.GroupByQuerySpec(
        "sales", (S.DimensionSpec("region", "region"),),
        AGGS + (_HLL,)), False),
    # a global aggregate over zero matching rows: the one identity row
    # (both values live in every segment, so nothing is pruned)
    "global_empty": (S.TimeseriesQuerySpec(
        "sales", AGGS + (S.AggregationSpec("doublemin", "lo",
                                           field="price"),),
        filter=S.LogicalFilter("and", (S.SelectorFilter("flag", "A"),
                                       S.SelectorFilter("flag", "N")))),
        False),
    # a historical's raw register blocks instead of estimates
    "partial_sketches": (S.GroupByQuerySpec(
        "sales", (S.DimensionSpec("region", "region"),), (_HLL, AGGS[2])),
        True),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_lane_decode_equals_solo_decode(store, case):
    """A lane of a fused group and a solo statement decode through the
    same function: columns, order, dtypes and values agree — estimates,
    the identity row and raw registers included."""
    spec, partial = DECODE_CASES[case]
    eng, ref = _engine(store), _ref_engine(store)
    eng.partial_sketches = ref.partial_sketches = partial
    want = ref.execute(spec)
    before = eng.sharedscan.stats()["queries_coalesced"]
    n = 2
    got, errs = [None] * n, []
    bar = threading.Barrier(n)

    def worker(i, q):
        bar.wait()
        try:
            got[i] = eng.execute(q)
        except Exception as e:          # noqa: BLE001 - asserted below
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i, q))
          for i, q in enumerate((spec, _PARTNER))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    assert eng.sharedscan.stats()["queries_coalesced"] - before == n
    assert got[0].columns == want.columns
    for name in want.columns:
        a, b = np.asarray(got[0].data[name]), np.asarray(want.data[name])
        assert a.dtype == b.dtype and a.shape == b.shape, (name, a, b)
        if a.dtype == object:
            assert list(a) == list(b), name
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)
    if case == "global_empty":
        assert len(want.data["n"]) == 1 and want.data["n"][0] == 0
        assert np.isnan(want.data["lo"][0])
    if partial:
        assert np.asarray(want.data["products"]).ndim == 2   # [G, m]


def test_lane_records_demux_solo_records_decode(sales_df):
    """The span says who decoded: the leader of a fused group decodes
    every lane under ``demux`` (its record has no ``decode``, a
    follower's neither), a solo statement under ``decode``."""
    sqls = ["SELECT region, SUM(qty) AS q FROM sales GROUP BY region",
            "SELECT flag, COUNT(*) AS n FROM sales GROUP BY flag"]
    c = sdot.Context({"sdot.cache.enabled": False,
                      "sdot.sharedscan.enabled": True,
                      "sdot.wlm.batch.window.ms": WINDOW_MS})
    c.ingest_dataframe("sales", sales_df, time_column="ts")
    try:
        bar = threading.Barrier(len(sqls))

        def fire(sql):
            bar.wait()
            c.sql(sql)
        ts = [threading.Thread(target=fire, args=(q,)) for q in sqls]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        fused = [r.stats for r in c.history.entries()]
        c.config.set("sdot.sharedscan.enabled", False)
        c.sql(sqls[0])
        solo = c.history.entries()[-1].stats
    finally:
        c.close()
    assert len(fused) == 2 and all("sharedscan" in st for st in fused)
    for st in fused:
        names = [sp[0] for sp in st["spans"]]
        assert "decode" not in names, names
        lead = st["sharedscan"]["role"] == "leader"
        assert names.count("demux") == (len(sqls) if lead else 0), names
    names = [sp[0] for sp in solo["spans"]]
    assert "sharedscan" not in solo
    assert names.count("decode") == 1 and "demux" not in names, names
