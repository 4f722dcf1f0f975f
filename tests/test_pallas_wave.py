"""Pallas wave mega-kernel (ops/pallas_wave.py + sharedscan wave path).

Interpreter-mode CI differentials: with ``SDOT_PALLAS=interpret`` (set
per-batch via ``_interpret_env`` — see its docstring for why it is NOT
an autouse fixture) the hand-scheduled wave kernel runs through
``pl.pallas_call(..., interpret=True)`` on CPU, so every test here
guards the kernel's semantics chip-independently:

- coalesced storm answers under the wave kernel == sequential solo
  answers AND == the jaxpr-fused program's answers (kill-switch A/B) —
  integer aggregates, counts, and sketch registers exactly (Neumaier
  int sums and min-algebra are order-free), float sums within the
  standard frame tolerance;
- the kill switch (``sdot.pallas.wave.enabled=false``) routes back to
  the jaxpr program with zero launches;
- a lane the kernel cannot lower (pattern filter -> dictionary-LUT
  gather, rejected by the trace probe) falls back to the jaxpr program
  WITHOUT changing routing tiers: the group still coalesces, nothing
  bounces solo;
- launch accounting: one kernel launch per dispatch wave on the canned
  4-lane storm, surfaced through coalescer stats and per-constituent
  stats.
"""

import contextlib
import os

import numpy as np
import pytest

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.tools import tpch

from conftest import assert_frames_equal
from test_sharedscan import (
    AGGS,
    WINDOW_MS,
    _engine,
    _ref_engine,
    _run_concurrent,
    _sales_batch,
    _storm_batch,
)


@contextlib.contextmanager
def _interpret_env():
    """Make the wave kernel available via ``pl.pallas_call(...,
    interpret=True)`` — the chip-independent CI configuration.

    Scoped to the wave-engine batch runs ONLY, deliberately: with
    ``SDOT_PALLAS=interpret`` set process-wide, every solo reference and
    jaxpr-fused comparison would also route its ``'ffl'`` sum/count
    lanes through interpreter-mode ``pallas_groupby`` (~20x slower than
    the XLA route for identical answers — measured 28s vs 1.4s for one
    solo reference sweep). Keeping references on the pure-XLA path both
    fits the tier-1 budget and makes the differential stronger: the
    interpreted wave kernel is compared against the canonical XLA
    lowering, not against another interpreter artifact."""
    old = os.environ.get("SDOT_PALLAS")
    os.environ["SDOT_PALLAS"] = "interpret"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("SDOT_PALLAS", None)
        else:
            os.environ["SDOT_PALLAS"] = old


def _wave_engine(store, **overrides):
    cfg = {"sdot.pallas.wave.enabled": True}
    cfg.update(overrides)
    return _engine(store, **cfg)


def _jaxpr_engine(store, **overrides):
    cfg = {"sdot.pallas.wave.enabled": False}
    cfg.update(overrides)
    return _engine(store, **cfg)


def _pallas_delta(eng, fn):
    p0 = eng.sharedscan.stats()["pallas"]
    out = fn()
    p1 = eng.sharedscan.stats()["pallas"]
    return out, {k: p1[k] - p0[k] for k in p1 if k != "vmem_bytes_peak"}


def _run_batch(eng, specs):
    res, errs, stats = _run_concurrent(eng, specs, collect_stats=True)
    assert not any(errs), [e for e in errs if e]
    return res, stats


def _assert_matches(got_frames, want_frames, exact_cols=()):
    for got, want in zip(got_frames, want_frames):
        assert_frames_equal(got, want)
        for c in exact_cols:
            if c in got.columns:
                assert np.array_equal(got[c].to_numpy(),
                                      want[c].to_numpy()), c


# -- differentials ------------------------------------------------------------

def test_wave_sales_mixed_matches_sequential_and_jaxpr(store):
    """The standard mixed batch (GroupBy / filtered GroupBy / monthly
    Timeseries / interval Timeseries / TopN) under the wave kernel must
    match both the solo sequential reference and the jaxpr-fused program,
    with integer aggregates exact."""
    specs = _sales_batch()
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    eng = _wave_engine(store)
    with _interpret_env():
        (res, _), dp = _pallas_delta(eng, lambda: _run_batch(eng, specs))
    assert dp["launches"] >= 1, dp
    assert dp["fallbacks"] == 0, dp
    _assert_matches(res, ref, exact_cols=("units", "n"))
    jx, _ = _run_batch(_jaxpr_engine(store), specs)
    _assert_matches(res, jx, exact_cols=("units", "n"))


def test_wave_integer_storm_bit_exact(store):
    """All-integer canned storm with a COMMUTED shared predicate (a AND b
    vs b AND a — canonicalized to one CSE node): wave answers must be
    bitwise identical to both solo and jaxpr paths (Neumaier integer
    sums, counts, and int min/max are exact in the f32 scratch)."""
    iaggs = (S.AggregationSpec("longsum", "units", field="qty"),
             S.AggregationSpec("longmin", "qmin", field="qty"),
             S.AggregationSpec("longmax", "qmax", field="qty"),
             S.AggregationSpec("count", "n"))
    a = S.SelectorFilter("status", "O")
    b = S.SelectorFilter("flag", "A")
    specs = [
        S.GroupByQuerySpec("sales", (S.DimensionSpec("region", "region"),),
                           iaggs, filter=S.LogicalFilter("and", (a, b))),
        S.GroupByQuerySpec("sales", (S.DimensionSpec("flag", "flag"),),
                           iaggs, filter=S.LogicalFilter("and", (b, a))),
        S.TimeseriesQuerySpec("sales", iaggs,
                              granularity=S.Granularity("year")),
        S.GroupByQuerySpec("sales", (S.DimensionSpec("status", "status"),),
                           iaggs),
    ]
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    eng = _wave_engine(store)
    with _interpret_env():
        (res, _), dp = _pallas_delta(eng, lambda: _run_batch(eng, specs))
    assert dp["launches"] >= 1 and dp["fallbacks"] == 0, dp
    exact = ("units", "qmin", "qmax", "n")
    _assert_matches(res, ref, exact_cols=exact)
    jx, _ = _run_batch(_jaxpr_engine(store), specs)
    _assert_matches(res, jx, exact_cols=exact)


def test_wave_sketch_lanes_match(store):
    """HLL (XLA epilogue inside the same jit) and theta (in-kernel
    register minima) lanes: estimates must be exactly equal to the solo
    path — both registers are bit-exact by construction (HLL reuses the
    identical XLA ops; theta is order-free min algebra on the identical
    hash stream)."""
    saggs = (S.AggregationSpec("cardinality", "uprod", field="product"),
             S.AggregationSpec("thetasketch", "tprod", field="product"),
             S.AggregationSpec("longsum", "units", field="qty"),
             S.AggregationSpec("count", "n"))
    specs = [
        S.GroupByQuerySpec("sales", (S.DimensionSpec("region", "region"),),
                           saggs),
        S.GroupByQuerySpec("sales", (S.DimensionSpec("flag", "flag"),),
                           saggs, filter=S.SelectorFilter("status", "O")),
        S.TimeseriesQuerySpec("sales", saggs,
                              granularity=S.Granularity("year")),
    ]
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    eng = _wave_engine(store)
    with _interpret_env():
        (res, _), dp = _pallas_delta(eng, lambda: _run_batch(eng, specs))
    assert dp["launches"] >= 1 and dp["fallbacks"] == 0, dp
    _assert_matches(res, ref, exact_cols=("uprod", "tprod", "units", "n"))


def test_wave_tpch_storm(tpch_wave_ctx):
    """TPC-H star storm (shared return-flag predicate across lanes +
    a sketch lane) through the session context: wave answers match the
    solo reference and the leader's statement stats surface the launch."""
    aggs = (S.AggregationSpec("doublesum", "revenue",
                              field="l_extendedprice"),
            S.AggregationSpec("longsum", "qty", field="l_quantity"),
            S.AggregationSpec("cardinality", "uparts", field="p_brand"),
            S.AggregationSpec("count", "n"))
    shared = S.SelectorFilter("l_returnflag", "R")
    specs = [
        S.GroupByQuerySpec("tpch_flat",
                           (S.DimensionSpec("l_linestatus", "l_linestatus"),),
                           aggs, filter=shared),
        S.GroupByQuerySpec("tpch_flat",
                           (S.DimensionSpec("c_mktsegment", "seg"),),
                           aggs, filter=shared),
        S.TimeseriesQuerySpec("tpch_flat", aggs,
                              granularity=S.Granularity("year")),
    ]
    eng = tpch_wave_ctx.engine
    ref = [_ref_engine(eng.store).execute(q).to_pandas() for q in specs]
    with _interpret_env():
        (res, _), dp = _pallas_delta(eng, lambda: _run_batch(eng, specs))
    assert dp["launches"] >= 1 and dp["fallbacks"] == 0, dp
    _assert_matches(res, ref, exact_cols=("qty", "uparts", "n"))


@pytest.fixture(scope="module")
def tpch_wave_ctx():
    ctx = sdot.Context({"sdot.sharedscan.enabled": True,
                        "sdot.wlm.batch.window.ms": WINDOW_MS,
                        "sdot.pallas.wave.enabled": True})
    tpch.setup_context(ctx, sf=0.002, target_rows=4096, flat_only=True)
    return ctx


# -- kill switch + fallback ---------------------------------------------------

def _small_storm():
    """3-lane batch for the routing-gate tests: the gates fire before any
    kernel work, so these lanes stay deliberately cheap (the env-set
    batches still pay interpreter-mode 'ffl' lanes on the jaxpr program
    they route to)."""
    shared = S.SelectorFilter("status", "O")
    return [
        S.GroupByQuerySpec("sales", (S.DimensionSpec("region", "region"),),
                           AGGS, filter=shared),
        S.GroupByQuerySpec("sales", (S.DimensionSpec("flag", "flag"),),
                           AGGS, filter=shared),
        S.TimeseriesQuerySpec("sales", AGGS,
                              granularity=S.Granularity("year")),
    ]


def test_wave_kill_switch_routes_to_jaxpr(store):
    """``sdot.pallas.wave.enabled=false`` must take the jaxpr program
    (zero kernel launches, no fallback ticks — the wave path was never
    attempted) with identical answers, even while the wave path IS
    available (interpret env set for the batch)."""
    specs = _small_storm()
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    eng = _jaxpr_engine(store)
    with _interpret_env():
        (res, _), dp = _pallas_delta(eng, lambda: _run_batch(eng, specs))
    assert dp == {"launches": 0, "tiles": 0, "fallbacks": 0}, dp
    _assert_matches(res, ref, exact_cols=("units", "n"))


def test_wave_fallback_keeps_group_fused(store):
    """A lane whose filter lowers through a dictionary LUT (a regex
    selecting 25 alternating dictionary codes exceeds the fused
    range-chain cap in BOTH polarities, so ``_take_mask`` falls to a
    real gather — outside the Mosaic-safe whitelist) must reject at
    the trace probe and lower the WHOLE group through the jaxpr-fused
    program: pallas_fallbacks ticks, zero launches, the group still
    coalesces (routing tiers unchanged — nothing bounces solo), and
    answers still match."""
    specs = [
        S.GroupByQuerySpec("sales", (S.DimensionSpec("region", "region"),),
                           AGGS,
                           filter=S.PatternFilter("product", "regex",
                                                  "[13579]$")),
        S.GroupByQuerySpec("sales", (S.DimensionSpec("flag", "flag"),),
                           AGGS),
        S.TimeseriesQuerySpec("sales", AGGS,
                              granularity=S.Granularity("year")),
    ]
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    eng = _wave_engine(store)
    c0 = eng.sharedscan.stats()
    with _interpret_env():
        (res, _), dp = _pallas_delta(eng, lambda: _run_batch(eng, specs))
    c1 = eng.sharedscan.stats()
    assert dp["launches"] == 0, dp
    assert dp["fallbacks"] == 1, dp
    assert c1["groups_coalesced"] - c0["groups_coalesced"] == 1, c1
    assert c1["fallbacks"] - c0["fallbacks"] == 0, c1
    _assert_matches(res, ref, exact_cols=("units", "n"))


def test_wave_max_lanes_gate(store):
    """Groups wider than ``sdot.pallas.wave.max.lanes`` take the jaxpr
    program via the static precheck (no fallback tick — never attempted)
    and still coalesce."""
    specs = _small_storm()
    ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
    eng = _wave_engine(store, **{"sdot.pallas.wave.max.lanes": 1})
    c0 = eng.sharedscan.stats()
    with _interpret_env():
        (res, _), dp = _pallas_delta(eng, lambda: _run_batch(eng, specs))
    c1 = eng.sharedscan.stats()
    assert dp == {"launches": 0, "tiles": 0, "fallbacks": 0}, dp
    assert c1["groups_coalesced"] - c0["groups_coalesced"] == 1, c1
    _assert_matches(res, ref, exact_cols=("units", "n"))


# -- launch accounting --------------------------------------------------------

def test_wave_one_launch_per_wave_canned_storm(store):
    """CI launch-accounting smoke: the canned 4-lane storm runs as ONE
    kernel launch per dispatch wave — coalescer counters and every
    constituent's own stats agree."""
    specs = _storm_batch()
    eng = _wave_engine(store)
    with _interpret_env():
        ((res, stats), dp) = _pallas_delta(eng,
                                           lambda: _run_batch(eng, specs))
    waves = {s["waves"] for s in stats if s.get("sharedscan")}
    assert waves, "no constituent reported sharedscan stats"
    n_waves = max(waves)
    assert dp["launches"] == n_waves, (dp, n_waves)
    assert dp["tiles"] >= dp["launches"], dp
    per_member = [s["sharedscan"]["pallas"] for s in stats
                  if s.get("sharedscan")]
    for pm in per_member:
        assert pm is not None, "wave group member missing pallas stats"
        assert pm["launches"] == n_waves, pm
        assert pm["block_rows"] >= 128, pm
        assert pm["vmem_bytes"] > 0, pm


def test_wave_compile_cache_key_isolation(store):
    """Flipping the kill switch on one engine must re-key the fused
    program (wave and jaxpr programs never collide in the compile
    cache) and keep answers identical across the flip."""
    specs = _small_storm()
    eng = _wave_engine(store)
    with _interpret_env():
        res1, _ = _run_batch(eng, specs)
        n1 = sum(1 for sig in eng._programs if sig and sig[0] == "aggmulti")
        eng.config.set("sdot.pallas.wave.enabled", False)
        res2, _ = _run_batch(eng, specs)
        n2 = sum(1 for sig in eng._programs if sig and sig[0] == "aggmulti")
    assert n2 == n1 + 1, (n1, n2)
    _assert_matches(res1, res2, exact_cols=("units", "n"))


# -- loud failures ------------------------------------------------------------

class _RefusedLowering:
    """A traced wave program whose compile the backend's compiler refuses."""

    def lower(self, shapes):
        return self

    def compile(self):
        raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: "
                           "Invalid relayout (transport of vector<i1>)")


@pytest.mark.parametrize("failure", ["compiler-refusal", "trace-crash"])
def test_wave_build_failures_are_loud(store, monkeypatch, failure):
    """Only a planned decline (``WaveFallback``) falls back to the jaxpr
    program. A compiler refusal fails every member's statement with the
    compiler's text and the lane set (never a silent solo re-run, never
    taken for device loss); any other build crash still degrades the
    group to solo — nobody hangs — and is kept in ``last_error``."""
    from spark_druid_olap_tpu.ops.pallas_wave import WaveCompileError
    from spark_druid_olap_tpu.parallel.sharedscan import SharedScanCoalescer
    specs = _small_storm()
    eng = _wave_engine(store)
    orig = SharedScanCoalescer._wave_program_fn

    def broken(self, *a, **k):
        if failure == "trace-crash":
            raise ValueError("lane builder bug")
        _, unpacks, info, shapes = orig(self, *a, **k)
        return _RefusedLowering(), unpacks, info, shapes

    monkeypatch.setattr(SharedScanCoalescer, "_wave_program_fn", broken)
    c0 = eng.sharedscan.stats()
    with _interpret_env():
        res, errs, _ = _run_concurrent(eng, specs)
    c1 = eng.sharedscan.stats()
    assert c1["pallas"]["fallbacks"] == c0["pallas"]["fallbacks"]
    assert c1["pallas"]["launches"] == c0["pallas"]["launches"]
    if failure == "compiler-refusal":
        assert all(isinstance(e, WaveCompileError) for e in errs), errs
        msg = str(errs[0])
        assert "Invalid relayout" in msg and "GroupByQuerySpec" in msg
        assert c1["fallbacks"] == c0["fallbacks"], "re-ran solo in silence"
        assert eng._backend_lost_at is None
    else:
        assert not any(errs), errs
        ref = [_ref_engine(store).execute(q).to_pandas() for q in specs]
        _assert_matches(res, ref, exact_cols=("units", "n"))
        assert c1["fallbacks"] - c0["fallbacks"] == len(specs)
        assert c1["last_error"] == "ValueError: lane builder bug"
