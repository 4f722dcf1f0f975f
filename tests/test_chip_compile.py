"""Ask the chip's compiler before the chip: the main-path Pallas kernels
compiled, at real widths, for a DESCRIBED (not attached) TPU v5e.

Interpret mode passes kernels Mosaic refuses (an ``i1`` relayout, an
unaligned slice, a VMEM limit). The TPU compiler is installed in the
test environment and compiles for a topology description, so every case
here lowers the program the chip would run — x64 off, no interpreter —
and asserts a ``tpu_custom_call`` in the compiled text. Nothing runs:
these say nothing about answers or times (the interpret-mode
differentials in test_pallas.py / test_pallas_wave.py guard answers).

The topology is described inside a module-scoped fixture, never at
import (one process at a time may load the TPU library; under xdist
every worker imports every test file). Keep these tests in ONE file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from spark_druid_olap_tpu.ir import expr as E
from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.ops import groupby as G
from spark_druid_olap_tpu.ops import pallas_groupby as PG
from spark_druid_olap_tpu.ops import pallas_wave as PW
from spark_druid_olap_tpu.ops import time_ops as T
from spark_druid_olap_tpu.ops.scan import array_names
from spark_druid_olap_tpu.parallel import meshexec as MX
from spark_druid_olap_tpu.parallel.executor import QueryEngine
from spark_druid_olap_tpu.parallel.mesh import SEGMENT_AXIS, make_mesh
from spark_druid_olap_tpu.planner import fusion as FU
from spark_druid_olap_tpu.utils.config import (
    Config,
    PALLAS_WAVE_TILE_BYTES,
    SHAREDSCAN_FUSION_MAX_NODES,
)

from test_sharedscan import AGGS, _sales_batch, _storm_batch

# TPC-H SF1 lineitem, padded to the store's 6 x 2^20-row segments
SF1_ROWS = 6_001_215
SF1_PADDED = 6 << 20
# wave programs compile at the SF1 store's shape, not the toy store's:
# (segments, padded rows per segment)
SF1_WAVE_SHAPE = (8, 1 << 20)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def chip_mode(monkeypatch):
    """The chip's settings around one lowering: 32-bit, Mosaic (not the
    interpreter), planner gates as on a TPU, persistent cache off (an
    entry compiled for a described chip cannot be read back without
    one, and the next compile would warn)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.delenv("SDOT_PALLAS", raising=False)
    monkeypatch.setattr(PG, "_tpu_backend", lambda: True)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            assert not PG._interpret()
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()


def _assert_kernel(compiled, name, n_kernels=1):
    """A Mosaic kernel, under the HLO instruction name a TPU trace's
    ``XLA Ops`` line will show (``pl.pallas_call(..., name=...)``) —
    not under the name of whichever Python function enclosed the call."""
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= n_kernels, \
        "no Mosaic kernel in the compiled program"
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert calls and all(ln.lstrip().startswith(("%" + name, "ROOT %" + name))
                         for ln in calls), \
        f"kernel not named {name}: {[ln.split(' = ')[0] for ln in calls]}"


# -- dense small-K kernel (ops/pallas_groupby.py) -----------------------------

def _dense_inputs(n, sharding):
    def col(dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=sharding)
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    # TPC-H q1: sum(qty) [int], sum(price), sum(disc_price), sum(charge),
    # avg(...) = sum + count, count(*), plus min/max and filtered forms
    metas = [("sum_qty", "sum", i32, None, True, 50.0),
             ("sum_base_price", "sum", f32, None, False, None),
             ("sum_disc_price", "sum", f32, None, False, None),
             ("sum_charge", "sum", f32, None, False, None),
             ("sum_disc", "sum", f32, b, False, None),
             ("count_order", "count", None, None, True, 1.0),
             ("count_f", "count", None, b, True, 1.0),
             ("min_price", "min", f32, None, False, None),
             ("max_price", "max", f32, b, False, None),
             ("__rows__", "count", None, None, True, 1.0)]
    shapes = {"key": col(i32)}
    for name, _, vdt, mdt, _, _ in metas:
        if vdt is not None:
            shapes["v_" + name] = col(vdt)
        if mdt is not None:
            shapes["m_" + name] = col(mdt)
    return metas, shapes


@pytest.mark.parametrize("n_keys,n_aggs", [(6, 10), (64, 5)],
                         ids=["q1-6keys", "cap-64keys"])
def test_dense_groupby_compiles_at_sf1(chip_mode, one_chip, n_keys, n_aggs):
    """q1's shape in full; the 64-key cap with five of the aggregates
    (sums with and without a mask, a count: the unroll is keys x aggs)."""
    metas, shapes = _dense_inputs(SF1_PADDED, one_chip)
    metas = metas[-n_aggs:] if n_aggs < len(metas) else metas

    def fn(arrays):
        inputs = [G.AggInput(name, kind,
                             values=arrays.get("v_" + name),
                             mask=arrays.get("m_" + name),
                             is_int=is_int, maxabs=maxabs)
                  for name, kind, _, _, is_int, maxabs in metas]
        assert PG.eligible(n_keys, inputs, 64, n_rows=SF1_ROWS)
        return PG.pallas_dense_groupby(arrays["key"], n_keys, inputs)

    _assert_kernel(jax.jit(fn).lower(shapes).compile(),
                   "sdot_dense_groupby")


# -- wave mega-kernel (ops/pallas_wave.py via parallel/sharedscan.py) ---------

def _plan_wave(eng, specs, mesh_sharded=False):
    """The planning half of ``SharedScanCoalescer._run_fused`` for one
    group of specs: lanes, fusion plan, union bind names."""
    co = eng.sharedscan
    ds = eng.store.get(specs[0].datasource)
    shaped = [co._shape_member(eng, ds, q) for q in specs]
    assert all(lp is not None for lp in shaped)
    seg_u = np.unique(np.concatenate([lp.seg for lp in shaped]))
    mins, maxs = ds.segment_time_bounds()
    min_day = int(mins[seg_u].min() // T.MILLIS_PER_DAY)
    max_day = int(maxs[seg_u].max() // T.MILLIS_PER_DAY)
    for lp in shaped:
        assert co._plan_lane(eng, ds, lp, min_day, max_day), lp.sig
    lanes = sorted(shaped, key=lambda lp: lp.sig)
    assert PW.wave_eligible(lanes, 16)
    fplan = FU.plan_lanes(
        [(lp.q.filter, lp.q.intervals, tuple(a.filter for a in lp.aggs))
         for lp in lanes],
        per_lane_cols=[len(lp.needed) for lp in lanes],
        union_cols=len(set().union(*[lp.needed for lp in lanes])),
        max_nodes=int(eng.config.get(SHAREDSCAN_FUSION_MAX_NODES)))
    union_cols = sorted(set().union(*[lp.needed for lp in lanes]))
    union_names = array_names(ds, union_cols,
                              any(lp.time_in_play for lp in lanes))
    dec = MX.decide(eng, ds, lanes, len(seg_u)) if mesh_sharded else None
    return ds, lanes, min_day, max_day, fplan, union_names, dec


def _compile_wave(eng, specs, sharding, shape=SF1_WAVE_SHAPE,
                  mesh_sharded=False):
    ds, lanes, min_day, max_day, fplan, names, dec = _plan_wave(
        eng, specs, mesh_sharded)
    if mesh_sharded:
        assert dec.sharded and dec.n_dev == 4, dec
    fn, _, info, shapes = eng.sharedscan._wave_program_fn(
        ds, lanes, min_day, max_day, fplan, union_names=names,
        s_pad=shape[0], mesh_dec=dec)
    assert not info["interpret"]
    shapes = {k: jax.ShapeDtypeStruct(shape, v.dtype, sharding=sharding)
              for k, v in shapes.items()}
    return fn.lower(shapes).compile(), info


def _wave_engine(store, mesh=None, **overrides):
    cfg = {"sdot.sharedscan.enabled": True, "sdot.wlm.enabled": False,
           "sdot.querycostmodel.enabled": False}
    cfg.update(overrides)
    return QueryEngine(store, config=Config(cfg), mesh=mesh)


@pytest.mark.parametrize("batch", [_storm_batch, _sales_batch],
                         ids=["storm", "sales-mixed"])
def test_wave_program_compiles(chip_mode, one_chip, store, batch):
    """The canned storm, and the mixed batch whose ungrouped
    interval-restricted lane (TPC-H q6's shape) Mosaic once refused with
    an ``i1`` relayout error (docs/KERNELS.md kernel contract)."""
    compiled, _ = _compile_wave(_wave_engine(store), batch(), one_chip)
    _assert_kernel(compiled, "sdot_wave")


def _theta_wide_batch():
    """An in-kernel theta stripe (region: 4 keys x K_LANES rows of
    unsigned-32 hash minima, single-row stores) next to the widest
    scratch block the planner allows: count-only product lanes (50 keys
    x two Neumaier rows per count) until the block nears MAX_OUT_ROWS."""
    saggs = (S.AggregationSpec("thetasketch", "tprod", field="product"),
             S.AggregationSpec("longsum", "units", field="qty"),
             S.AggregationSpec("count", "n"))
    counts = tuple(S.AggregationSpec("count", f"n{i}") for i in range(8))
    specs = [S.GroupByQuerySpec(
        "sales", (S.DimensionSpec("region", "region"),), saggs)]
    for flt in (None, S.SelectorFilter("status", "O"),
                S.SelectorFilter("flag", "A"),
                S.BoundFilter("qty", lower=10, numeric=True)):
        specs.append(S.GroupByQuerySpec(
            "sales", (S.DimensionSpec("product", "product"),), counts,
            filter=flt))
    return specs


def test_wave_theta_stripe_and_widest_scratch_compile(chip_mode, one_chip,
                                                      store):
    eng = _wave_engine(store)
    compiled, info = _compile_wave(eng, _theta_wide_batch(), one_chip)
    _assert_kernel(compiled, "sdot_wave")
    assert info["theta_inkernel"] == 1, info
    assert PW.MAX_OUT_ROWS * 3 // 4 <= info["out_rows"] \
        <= PW.MAX_OUT_ROWS, info
    assert info["vmem_bytes"] <= int(
        eng.config.get(PALLAS_WAVE_TILE_BYTES)), info


def test_wave_program_compiles_under_shard_map(chip_mode, topo, store):
    """The mesh tier's program: the wave kernel per device under
    ``shard_map`` on a 4-device mesh of the described chips. Sums and
    counts stay per chip ('ffl' pairs, combined on the host); the
    min/max lanes are what crosses the interconnect."""
    mesh = make_mesh(devices=topo.devices)
    assert mesh.devices.size == 4
    eng = _wave_engine(store, mesh=mesh)
    minmax = AGGS + (S.AggregationSpec("longmin", "qmin", field="qty"),
                     S.AggregationSpec("doublemax", "pmax", field="price"))
    specs = _storm_batch()[:3] + [S.GroupByQuerySpec(
        "sales", (S.DimensionSpec("status", "status"),), minmax)]
    compiled, _ = _compile_wave(
        eng, specs, NamedSharding(mesh, P(SEGMENT_AXIS, None)),
        mesh_sharded=True)
    _assert_kernel(compiled, "sdot_wave")
    assert "all-reduce" in compiled.as_text(), \
        "no interconnect merge in the program"


# -- a solo program that takes its filter literals as an operand --------------

def test_literal_operand_program_compiles(chip_mode, one_chip, store):
    """TPC-H q6's shape on the solo path — an interval on the time
    column, a float ``between``, an integer bound, one global sum — as
    the engine builds it since literals became operands: the program's
    parameters are the columns plus ONE small int32 vector
    (``ops/literals.py``), the dense kernel reads masks built from it,
    and the chip's compiler accepts both."""
    from spark_druid_olap_tpu.ops import literals as L
    from spark_druid_olap_tpu.ops.scan import array_dtype
    eng = QueryEngine(store, config=Config({"sdot.wlm.enabled": False}))
    day = T.MILLIS_PER_DAY
    lo = int(np.datetime64("2015-03-01").astype("datetime64[D]")
             .astype(np.int64)) * day
    q = S.TimeseriesQuerySpec(
        "sales",
        (S.AggregationSpec("doublesum", "revenue", expr=E.BinaryOp(
            "*", E.Column("price"), E.Column("discount"))),),
        filter=S.LogicalFilter("and", (
            S.BoundFilter("discount", lower=0.05, upper=0.07, numeric=True),
            S.BoundFilter("qty", upper=24, upper_strict=True,
                          numeric=True))),
        intervals=((lo, lo + 365 * day),))
    ds = store.get("sales")
    seg_idx = ds.prune_segments(q.intervals, q.filter)
    dim_plans, agg_plans, min_day, max_day, n_keys, names, routes = \
        eng._plan_agg(ds, seg_idx, [], q.aggregations, q.granularity,
                      q.filter, q.intervals)
    lits, days = eng._plan_literals(q, ds, dim_plans, min_day, max_day)
    assert lits.count == 7 and days is None
    assert "0.05" not in lits.shape and "24" not in lits.shape
    fn, *_ = eng._build_agg_program(
        ds, dim_plans, agg_plans, q.filter, q.intervals, days, n_keys,
        False, routes, lits=lits)
    words = lits.pack()
    shapes = {k: jax.ShapeDtypeStruct((6, 1 << 20), array_dtype(ds, k),
                                      sharding=one_chip) for k in names}
    shapes[L.LITERALS_KEY] = jax.ShapeDtypeStruct(
        words.shape, words.dtype, sharding=one_chip)
    compiled = fn.lower(shapes).compile()
    text = compiled.as_text()
    assert f"s32[1,{words.shape[1]}]" in text, "no literal operand"
    _assert_kernel(compiled, "sdot_dense_groupby")


# -- the sorted-run core's segmented scan past the tree's row limit (PR 35) ---

def test_segmented_scan_over_sf1_rows_compiles_as_a_loop(chip_mode, one_chip):
    """A float sum over the 8.0 M rows of an SF1 scan (the sketch cell's
    ``sum(revenue)`` beside its HLL columns): ``associative_scan``'s
    unrolled tree did not compile for the chip in a quarter of an hour at
    that size, so past ``_SCAN_TREE_MAX_ROWS`` the scan is a ``while`` of
    doubling shifts, which the chip's compiler takes in seconds."""
    from spark_druid_olap_tpu.ops import sorted_groupby as SG
    n = 8 * 1_000_448
    assert n > SG._SCAN_TREE_MAX_ROWS

    def comb(xa, xb):
        s, e = SG._two_sum(xa[0], xb[0])
        return (s, e + xa[1] + xb[1])

    def scan(flag, v):
        return SG._seg_scan(flag, (v, jnp.zeros_like(v)), comb)

    compiled = jax.jit(scan).lower(
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)).compile()
    assert " while(" in compiled.as_text()
