"""Query cost model: single-chip vs mesh-sharded execution.

≈ ``DruidQueryCostModel.scala`` (872 LoC), which decides broker vs direct
historical queries and segments-per-query from input/output estimates:
``estimateInput:660-677`` (filter selectivity), ``estimateOutputCardinality
:691-716`` (dim cardinality product × selectivity), per-query-type cost
classes summing historical processing + merge + transport costs over
scheduling "waves". The TPU translation: the 'historicals' are mesh chips,
'broker merge' is the ICI collective, 'transport' is host<->device + DCN, and
a TPU-specific compile-amortization term replaces Spark scheduling cost.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.parallel.mesh import mesh_size
from spark_druid_olap_tpu.utils.config import (
    COST_COMPILE,
    COST_MODEL_ENABLED,
    COST_PER_BYTE_INTERCONNECT,
    COST_PER_BYTE_TRANSPORT,
    COST_PER_ROW_MERGE,
    COST_PER_ROW_SCAN,
    COST_SHARD_EFFICIENCY,
)


@dataclasses.dataclass
class CostEstimate:
    rows: int                      # rows scanned after interval pruning
    selectivity: float             # estimated filter selectivity
    output_groups: int             # estimated result cardinality
    single_cost: float
    sharded_cost: float
    n_devices: int
    recommend_sharded: bool
    scan_bytes: int = 0            # est. device bytes the scan binds
    segments_per_wave: int = 0     # 0 = everything in one wave
    n_waves: int = 1
    xhost_bytes: int = 0           # est. cross-host result replication
    host_xhost_bytes: int = 0      # est. host-tier column reassembly bytes
    ici_bytes: int = 0             # est. intra-host interconnect merge bytes

    def table(self) -> str:
        wave = "" if self.n_waves <= 1 else \
            f"  waves={self.n_waves}x{self.segments_per_wave}seg"
        xh = "" if not self.xhost_bytes else \
            f" xhost_bytes={self.xhost_bytes:,}"
        if self.host_xhost_bytes:
            xh += f" host_xhost_bytes={self.host_xhost_bytes:,}"
        return (f"rows={self.rows:,} sel={self.selectivity:.3f} "
                f"est_groups={self.output_groups:,} "
                f"scan_bytes={self.scan_bytes:,}{xh}\n"
                f"single-chip cost={self.single_cost:.4g}  "
                f"sharded({self.n_devices})={self.sharded_cost:.4g}  "
                f"-> {'SHARDED' if self.recommend_sharded else 'SINGLE'}"
                + wave)


def _filter_selectivity(f: Optional[S.FilterSpec], ds) -> float:
    """≈ the reference's per-filter selectivity heuristics."""
    if f is None:
        return 1.0
    if isinstance(f, S.SelectorFilter):
        card = ds.cardinality(f.dimension) or 100
        return 1.0 / max(card, 1)
    if isinstance(f, S.BoundFilter):
        frac = _bound_overlap_fraction(f, ds)
        if frac is not None:
            return frac
        both = f.lower is not None and f.upper is not None
        return 0.25 if both else 0.5
    if isinstance(f, S.InFilter):
        card = ds.cardinality(f.dimension) or 100
        return min(1.0, len(f.values) / max(card, 1))
    if isinstance(f, S.PatternFilter):
        frac = _pattern_fraction(f, ds)
        return frac if frac is not None else 0.25
    if isinstance(f, S.NullFilter):
        return 0.9 if f.negated else 0.1
    if isinstance(f, S.LogicalFilter):
        sels = [_filter_selectivity(x, ds) for x in f.fields]
        if f.op == "and":
            out = 1.0
            for s_ in sels:
                out *= s_
            return out
        if f.op == "or":
            return min(1.0, sum(sels))
        return max(0.0, 1.0 - (sels[0] if sels else 0.0))
    return 0.5  # ExprFilter: unknown


_PATTERN_FRAC_BOUND = 256


# CPU-fallback measured unit costs (round-3 probe workbench): consulted
# when the config still carries the v5e-measured DEFAULT on a cpu
# backend, so the perf gates are measurement-driven on BOTH backends out
# of the box. tools/calibrate.calibrate_primitives refits either backend
# in place (an explicitly-set config value always wins).
_CPU_MEASURED = {
    "sdot.querycostmodel.sort.seconds.per.row": 3.0e-7,
    "sdot.querycostmodel.sort.payload.seconds.per.row": 1.0e-7,
    "sdot.querycostmodel.scatter.seconds.per.update": 4.0e-9,
    "sdot.querycostmodel.scatter.big.seconds.per.update": 1.5e-7,
    "sdot.querycostmodel.gather.seconds.per.probe": 2.0e-9,
}


def unit_cost(config, entry) -> float:
    """Per-backend unit cost: the configured value when EXPLICITLY set
    (even to the default — config.is_set, not value equality), else the
    CPU-measured table on cpu backends, else the TPU-measured default."""
    import jax
    if config.is_set(entry):
        return float(config.get(entry))
    if jax.default_backend() == "cpu":
        return float(_CPU_MEASURED.get(entry.key, float(entry.default)))
    return float(entry.default)


def _pattern_fraction(f: S.PatternFilter, ds) -> Optional[float]:
    """Matching-dictionary fraction as the pattern's selectivity
    (uniform-frequency assumption). One regex pass over the dictionary,
    cached on the datasource — the filter lowering pays the same pass at
    trace time, and the late-materialization budget needs the real
    fraction (LIKE '%green%' over p_name is ~5%, not the 0.25 blanket)."""
    import re as _re
    from spark_druid_olap_tpu.ops import expr_compile as EC
    dim = getattr(ds, "dims", {}).get(f.dimension)
    if dim is None:
        return None
    from collections import OrderedDict
    cache = getattr(ds, "_pattern_frac_cache", None)
    if cache is None:
        cache = ds._pattern_frac_cache = OrderedDict()
    key = (f.dimension, f.kind, f.pattern)
    hit = cache.get(key)
    if hit is not None:
        cache.move_to_end(key)
        return hit
    vals = dim.dictionary
    n = len(vals)
    if n == 0:
        return None
    try:
        if f.kind == "like":
            rx = _re.compile(EC.like_to_regex(f.pattern))
            cnt = sum(1 for s in vals if rx.match(s))
        elif f.kind == "regex":
            rx = _re.compile(f.pattern)
            cnt = sum(1 for s in vals if rx.search(s))
        elif f.kind == "contains":
            cnt = sum(1 for s in vals if f.pattern in s)
        else:
            return None
    except _re.error:
        return None
    frac = max(cnt / n, 1.0 / (2 * n))
    cache[key] = frac
    # LRU-bounded like the session result caches: ad-hoc dashboards /
    # fuzzers emit unbounded distinct patterns (ADVICE r3)
    while len(cache) > _PATTERN_FRAC_BOUND:
        cache.popitem(last=False)
    return frac


def _bound_overlap_fraction(f: S.BoundFilter, ds) -> Optional[float]:
    """Range-overlap selectivity from column min/max metadata (DATE /
    LONG / DOUBLE metrics): |bound ∩ [min, max]| / |[min, max]|, assuming
    uniform density. Far better than the blanket 0.25 for the BI-typical
    date-quarter predicates (TPC-H q10-class: a 3-month window over 7
    years is ~0.036, not 0.25) — and the late-materialization budget
    depends on it."""
    from spark_druid_olap_tpu.ops import time_ops
    from spark_druid_olap_tpu.segment.column import ColumnKind
    try:
        kind = ds.column_kind(f.dimension)
    except KeyError:
        return None
    if kind not in (ColumnKind.DATE, ColumnKind.LONG, ColumnKind.DOUBLE):
        return None
    m = ds.metrics.get(f.dimension)
    if m is None:
        return None
    mn, mx = m.min, m.max              # uncached O(n) properties: bind once
    if mn is None or mx is None:
        return None
    lo_col, hi_col = float(mn), float(mx)
    if not (hi_col > lo_col):            # also rejects NaN bounds
        return None
    unit = 0.0 if kind == ColumnKind.DOUBLE else 1.0

    def conv(v):
        if v is None:
            return None
        if kind == ColumnKind.DATE:
            return float(time_ops.date_literal_to_days(v))
        return float(v)

    try:
        lo = conv(f.lower)
        hi = conv(f.upper)
    except (TypeError, ValueError):
        return None
    # half-open [lo_eff, hi_eff) over the column's [min, max + unit):
    # integer/date inclusive bounds widen by one unit; strict bounds
    # shift by one unit (measure-zero for DOUBLE, where unit = 0)
    lo = lo_col if lo is None else (lo + (unit if f.lower_strict else 0.0))
    hi = (hi_col + unit) if hi is None \
        else (hi + (0.0 if f.upper_strict else unit))
    lo = max(lo, lo_col)
    hi = min(hi, hi_col + unit)
    width = hi_col + unit - lo_col
    if width <= 0:
        return None
    return max(0.0, min(1.0, (hi - lo) / width))


def _output_groups(q: S.QuerySpec, ds) -> int:
    dims = S.query_dimensions(q)
    out = 1
    for d in dims:
        if d.extraction is None:
            out *= max(1, ds.cardinality(d.dimension) or 100)
        elif isinstance(d.extraction, S.TimeExtraction):
            out *= 32
        else:
            out *= 100
    gran = getattr(q, "granularity", S.GRAN_ALL)
    if gran is not None and not gran.is_all():
        lo, hi = ds.interval()
        buckets = {"year": 3.2e10, "quarter": 8e9, "month": 2.6e9,
                   "week": 6.05e8, "day": 8.64e7, "hour": 3.6e6,
                   "minute": 6e4}.get(gran.kind, 8.64e7)
        out *= max(1, int((hi - lo) / buckets))
    return out


def array_itemsize(ds, key: str) -> int:
    """Host itemsize of one stacked array (device canonicalization can only
    shrink f64->f32, so this bounds device bytes from above)."""
    from spark_druid_olap_tpu.ops.scan import (
        NULL_VALID_PREFIX, ROW_VALID_KEY, TIME_MS_KEY)
    if key == ROW_VALID_KEY or key.startswith(NULL_VALID_PREFIX):
        return 1
    if key == TIME_MS_KEY:
        return int(ds.time.ms_dtype().itemsize)
    if key in ds.dims:
        return int(ds.dims[key].data_dtype().itemsize)
    if key in ds.metrics:
        return int(ds.metrics[key].data_dtype().itemsize)
    if ds.time is not None and key == ds.time.name:
        return int(ds.time.data_dtype().itemsize)
    return 4


def bytes_per_segment(ds, names) -> int:
    return int(ds.padded_rows) * sum(array_itemsize(ds, k) for k in names)


def wave_tile_itemsize(ds, key: str) -> int:
    """Per-row VMEM bytes of one union array inside the wave mega-kernel
    (ops/pallas_wave.py) AFTER its input prep: validity masks ship as i8
    (1 byte), narrow integer codes widen to i32 on the host side of the
    kernel (uniform Mosaic tiling), wide types keep their itemsize."""
    from spark_druid_olap_tpu.ops.scan import NULL_VALID_PREFIX, ROW_VALID_KEY
    if key == ROW_VALID_KEY or key.startswith(NULL_VALID_PREFIX):
        return 1
    return max(4, array_itemsize(ds, key))


def pallas_tile_budget_bytes(conf) -> int:
    """VMEM byte budget the wave mega-kernel's tile planner
    (planner/fusion.py:plan_wave_tiles) fits the double-buffered input
    tiles plus the resident scratch block into."""
    from spark_druid_olap_tpu.utils.config import PALLAS_WAVE_TILE_BYTES
    return int(conf.get(PALLAS_WAVE_TILE_BYTES))


def wave_budget_bytes(conf) -> Optional[int]:
    """Per-device byte budget for one wave's scan arrays. Config override,
    else 60% of the device's reported HBM limit, else None (single wave)."""
    from spark_druid_olap_tpu.utils.config import WAVE_MAX_BYTES
    b = conf.get(WAVE_MAX_BYTES)
    if b:
        return int(b)
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        if limit:
            return int(limit * 0.6)
    except Exception:  # noqa: BLE001 - CPU/interpret backends have no stats
        pass
    return None


def tier_io_budget(ds, conf) -> Optional[int]:
    """Per-wave host-I/O byte cap for a tiered (cold) datasource, or
    None on an in-memory store. A cold scan in one giant wave serializes
    the entire fault traffic ahead of the first dispatch; capping wave
    bytes at ``sdot.tier.wave.io.bytes`` forces enough waves that the
    prefetcher can hide wave i+1's loads behind wave i's compute."""
    if getattr(ds, "tier", None) is None:
        return None
    from spark_druid_olap_tpu.utils.config import TIER_WAVE_IO_BYTES
    b = int(conf.get(TIER_WAVE_IO_BYTES))
    return b if b > 0 else None


def tier_io_seg_bytes(ds, names) -> Optional[int]:
    """Per-segment HOST bytes one wave actually faults for the named
    scan keys — COMPRESSED bytes on an encoded tiered store
    (``TieredDatasource.host_bytes_per_segment``), None elsewhere. This
    is the divisor for the cold-tier io cap: an encoded store moves
    ratio× fewer bytes per segment, so the same ``sdot.tier.wave.io.
    bytes`` admits ratio× more segments per wave. The HBM-budget term
    keeps using the LOGICAL ``bytes_per_segment`` — chunks decode
    before device binding, so device bytes are unchanged by encoding."""
    fn = getattr(ds, "host_bytes_per_segment", None)
    if fn is None:
        return None
    b = int(fn(names))
    return b if b > 0 else None


def plan_waves(n_segments: int, n_dev: int, seg_bytes: int,
               budget: Optional[int], conf, output_groups: int,
               n_aggs: int, io_budget: Optional[int] = None,
               io_seg_bytes: Optional[int] = None) -> tuple:
    """Min-cost search over segments-per-wave (≈ the reference's
    ``druidQueryMethod`` searching 1..histSegsPerQueryLimit,
    DruidQueryCostModel.scala:343-414). Each wave costs a dispatch plus a
    host-side merge of the wave's [K] partials; each wave's scan arrays for
    one device must fit ``budget`` bytes. ``io_budget`` additionally caps
    one WAVE's total host bytes (all devices) — the cold-tier I/O term
    (``tier_io_budget``) that keeps load-behind-compute overlap full.
    ``io_seg_bytes`` is the per-segment divisor for that I/O term when the
    faulted bytes differ from the device bytes (encoded tiered stores,
    ``tier_io_seg_bytes``); it defaults to ``seg_bytes``.

    Returns (segments_per_wave, n_waves); segments_per_wave is a multiple of
    n_dev.
    """
    n_dev = max(1, n_dev)
    if n_segments <= 0:
        return n_dev, 1
    # every wave costs a dispatch plus a host merge of its [K] partials while
    # scan + transport totals are wave-count invariant, so the min-cost
    # segments-per-wave is simply the largest n_dev multiple under the HBM
    # budget (the reference's search space has a per-wave scheduling term
    # with the same monotone structure). Unbounded scans round UP to one
    # wave — segment padding covers the tail.
    cap = -(-n_segments // n_dev) * n_dev
    if budget is not None and seg_bytes > 0:
        per_dev = int(budget // seg_bytes)
        cap = min(cap, max(1, per_dev) * n_dev)
    io_div = io_seg_bytes if io_seg_bytes is not None else seg_bytes
    if io_budget is not None and io_div > 0:
        per_wave = max(1, int(io_budget // io_div))
        cap = min(cap, -(-per_wave // n_dev) * n_dev)
    return cap, -(-n_segments // cap)


def estimate(ctx_or_engine, q: S.QuerySpec) -> CostEstimate:
    engine = getattr(ctx_or_engine, "engine", ctx_or_engine)
    ds = engine.store.get(q.datasource)
    conf = engine.config
    seg_idx = ds.prune_segments(getattr(q, "intervals", None))
    if ds.num_segments:
        rows = int(ds.num_rows * len(seg_idx) / ds.num_segments)
    else:
        rows = 0
    sel = _filter_selectivity(getattr(q, "filter", None), ds)
    groups = min(_output_groups(q, ds), max(1, int(rows * sel)) or 1)

    scan_c = conf.get(COST_PER_ROW_SCAN)
    merge_c = conf.get(COST_PER_ROW_MERGE)
    byte_c = conf.get(COST_PER_BYTE_TRANSPORT)
    compile_c = conf.get(COST_COMPILE)

    n_dev = mesh_size(engine.mesh)
    eff = max(1e-3, min(1.0, float(conf.get(COST_SHARD_EFFICIENCY))))
    # single chip: scan everything + decode output
    single = rows * scan_c + groups * byte_c * 16
    # sharded: scan split across devices (at the CALIBRATED parallel
    # efficiency — a virtual mesh on shared cores splits nothing) + ICI
    # merge of [K] partials per agg
    n_aggs = max(1, len(S.query_aggregations(q)))
    # cross-host replication bytes (multi-host pods only): result rows
    # travel DCN/ICI once per peer host so every process can fetch the
    # replicated merge — O(groups x n_aggs), the two-dispatch compacted
    # transfer (VERDICT r4 item 3; the full-[T]-table gather this
    # replaced would be O(slots x n_aggs))
    import jax as _jax
    try:
        n_hosts = _jax.process_count()
    except Exception:   # noqa: BLE001 — uninitialized backend
        n_hosts = 1
    xhost_bytes = groups * n_aggs * 8 * max(0, n_hosts - 1) \
        if n_hosts > 1 else 0
    # intra-host interconnect merge bytes: each device contributes its
    # merged [K x n_aggs] partial block to the all-reduce, so the
    # reduction moves payload x (n_dev - 1) over the links (ring
    # convention; parallel/meshexec.py accounts dispatches identically)
    ici_bytes = groups * n_aggs * 8 * max(0, n_dev - 1)
    sharded = (rows / max(n_dev * eff, 1e-9)) * scan_c \
        + groups * n_aggs * merge_c \
        + groups * byte_c * 16 \
        + xhost_bytes * byte_c \
        + ici_bytes * conf.get(COST_PER_BYTE_INTERCONNECT) \
        + compile_c * 0.1  # sharded programs compile slower
    recommend = n_dev > 1 and sharded < single
    if not conf.get(COST_MODEL_ENABLED):
        recommend = n_dev > 1

    # approximate scan footprint + wave plan (exact names are executor-side;
    # this mirrors them closely enough for explain)
    names = set()
    for d in S.query_dimensions(q):
        names.add(d.dimension)
    for a in S.query_aggregations(q):
        if a.field:
            names.add(a.field)
    from spark_druid_olap_tpu.ops.filters import columns_of_filter
    names |= columns_of_filter(getattr(q, "filter", None))
    names = {c for c in names if c in ds.dims or c in ds.metrics
             or (ds.time is not None and c == ds.time.name)}
    seg_bytes = bytes_per_segment(
        ds, list(names) + ["__rows__"]) if ds.num_segments else 0
    scan_bytes = seg_bytes * len(seg_idx)
    # host-tier reassembly term (multi-host partial stores): a statement
    # shape that drops to the host fallback must rebuild each needed
    # column via the paged allgather — O(rows x column bytes), dwarfing
    # the engine path's O(groups) replication above. Surfaced so explain
    # shows WHY the engine path is worth keeping on a partial store.
    host_xhost = 0
    if getattr(ds, "is_partial", False) and ds.host_assignment is not None \
            and len(ds.host_assignment):
        ds_hosts = int(ds.host_assignment.max()) + 1
        if ds_hosts > 1:
            host_xhost = int(ds.num_rows) * \
                sum(array_itemsize(ds, k) for k in names)
    eff_dev = n_dev if recommend else 1
    spw, waves = plan_waves(len(seg_idx), eff_dev, seg_bytes,
                            wave_budget_bytes(conf), conf, groups, n_aggs)
    return CostEstimate(rows, sel, groups, single, sharded, n_dev, recommend,
                        scan_bytes=scan_bytes, segments_per_wave=spw,
                        n_waves=waves, xhost_bytes=int(xhost_bytes),
                        host_xhost_bytes=int(host_xhost),
                        ici_bytes=int(ici_bytes))


@dataclasses.dataclass
class MeshEstimate:
    """Mesh-or-single pricing for one fused shared-scan group
    (parallel/meshexec.py:decide). The solo path's ``estimate`` prices a
    whole query spec; the fused tier already holds planned lanes, so
    this variant takes the resolved quantities directly — including the
    EXACT merged-payload byte count the packers will ship across the
    interconnect, not a heuristic."""
    single_cost: float
    sharded_cost: float
    n_devices: int
    merge_bytes: int
    recommend_sharded: bool


def mesh_estimate(conf, *, n_dev: int, rows: int, groups: int,
                  n_aggs: int, merge_bytes: int) -> MeshEstimate:
    """Price one fused dispatch single-device vs sharded over ``n_dev``
    devices. Same unit costs as ``estimate`` — scan splits across the
    mesh at the calibrated parallel efficiency; the merge adds a
    per-row collective term plus the interconnect transport of the
    merged partial payload (``merge_bytes``, already x(n_dev - 1))."""
    scan_c = conf.get(COST_PER_ROW_SCAN)
    merge_c = conf.get(COST_PER_ROW_MERGE)
    byte_c = conf.get(COST_PER_BYTE_TRANSPORT)
    compile_c = conf.get(COST_COMPILE)
    icx_c = conf.get(COST_PER_BYTE_INTERCONNECT)
    eff = max(1e-3, min(1.0, float(conf.get(COST_SHARD_EFFICIENCY))))
    n_dev = max(1, int(n_dev))
    single = rows * scan_c + groups * byte_c * 16
    sharded = (rows / max(n_dev * eff, 1e-9)) * scan_c \
        + groups * n_aggs * merge_c \
        + groups * byte_c * 16 \
        + merge_bytes * icx_c \
        + compile_c * 0.1
    recommend = n_dev > 1 and sharded < single
    if not conf.get(COST_MODEL_ENABLED):
        recommend = n_dev > 1
    return MeshEstimate(single, sharded, n_dev, int(merge_bytes),
                        recommend)


def explain_cost(ctx, q: S.QuerySpec) -> str:
    try:
        out = estimate(ctx, q).table()
    except Exception as e:  # cost must never break explain
        return f"cost: unavailable ({e})"
    try:
        out += _explain_scan_plan(ctx, q)
    except Exception:   # noqa: BLE001 — advisory detail only
        pass
    return out


def _seen_compact_shape(eng, q, ds, seg_idx):
    """The shape (``QueryEngine._compact_shape``) a one-chip, one-wave
    run of ``q`` remembers its survivor count under, where it has one;
    else None."""
    from spark_druid_olap_tpu.parallel import executor as X
    agg = X._agg_shape(q)
    if agg is None or not eng._compact_seen:
        return None
    dim_plans, _, min_day, max_day, _, names, _ = eng._plan_agg(
        ds, seg_idx, agg[0], q.aggregations, q.granularity, q.filter,
        q.intervals)
    lits, days = eng._plan_literals(q, ds, dim_plans, min_day, max_day)
    for tier in ("agg", "hashagg"):
        shape = eng._compact_shape(tier, ds, lits,
                                   X._pad_segments(len(seg_idx), 1), days,
                                   False, 1, names)
        if shape in eng._compact_seen:
            return shape
    return None


def _explain_scan_plan(ctx, q: S.QuerySpec) -> str:
    """Physical scan decisions: late-materialization budget and staged
    (post-compaction) filter conjuncts — the explain surface for the
    compact-then-aggregate path."""
    from spark_druid_olap_tpu.parallel import executor as X
    eng = ctx.engine
    f = getattr(q, "filter", None)
    ds = eng.store.get(q.datasource)
    seg_idx = ds.prune_segments(getattr(q, "intervals", None), f)
    cheap, exp = X._compaction_filters(f)
    shape = _seen_compact_shape(eng, q, ds, seg_idx)
    m = eng._plan_compact_m(ds, seg_idx, cheap, sharded=False, shape=shape)
    if m is None:
        return ""
    if shape is None and X._estimate_blind(cheap, ds):
        # no estimate prices this mask: its first run counts it
        return ("\nscan: late-materialize, the budget counted at the "
                "first run")
    # ESTIMATE: the execution-time decision additionally sees the agg
    # routes ('ffl' Pallas ceiling), sharding, and overflow memory —
    # none of which exist at explain time (ADVICE r3). OBSERVED: a
    # compacting program of this shape has reported its survivors, and
    # the budget is the one that count holds the shape to
    line = (f"\nscan: late-materialize to [{m:,}] survivors "
            f"({'estimate' if shape is None else 'observed'})")
    if exp is not None:
        n_exp = len(exp.fields) if isinstance(exp, S.LogicalFilter) \
            and exp.op == "and" else 1
        line += f" (+{n_exp} gather-heavy conjunct(s) staged after)"
    return line


# =============================================================================
# general join tier pricing (planner/joinplan.py)
# =============================================================================

@dataclasses.dataclass
class JoinEstimate:
    """Broadcast-vs-partitioned pricing for one recognized join.

    ``build_bytes``/``probe_bytes`` are host-row upper bounds over the
    columns the join actually touches; ``shuffle_bytes`` estimates the
    partition exchange (both sides cross the wire twice: shard -> broker
    -> aligned node), priced at the interconnect byte rate like the mesh
    tier's merge traffic."""
    mode: str                  # 'broadcast' | 'partitioned' | 'host'
    probe_bytes: int
    build_bytes: int
    shuffle_bytes: int
    broadcast_cost: float
    partitioned_cost: float
    reason: str

    def table(self) -> str:
        return (f"join: build_bytes={self.build_bytes:,} "
                f"probe_bytes={self.probe_bytes:,} "
                f"shuffle_bytes={self.shuffle_bytes:,} "
                f"broadcast={self.broadcast_cost:.4g} "
                f"partitioned={self.partitioned_cost:.4g} "
                f"-> {self.mode.upper()} ({self.reason})")


def join_side_bytes(ds, cols) -> int:
    """Upper-bound host bytes of one join side restricted to ``cols``."""
    return int(ds.num_rows) * int(sum(array_itemsize(ds, c)
                                      for c in cols))


def join_estimate(config, *, probe_ds, build_ds, probe_cols, build_cols,
                  cluster_nodes: int = 0) -> JoinEstimate:
    """Pick the join tier. ``sdot.join.mode`` forces a tier; in auto
    mode the broadcast byte cap gates eligibility and the cheaper
    estimate wins when both tiers are available."""
    from spark_druid_olap_tpu.utils.config import (
        JOIN_BROADCAST_MAX_BYTES, JOIN_MODE)
    build_bytes = join_side_bytes(build_ds, build_cols)
    probe_bytes = join_side_bytes(probe_ds, probe_cols)
    cap = int(config.get(JOIN_BROADCAST_MAX_BYTES))
    scan_c = config.get(COST_PER_ROW_SCAN)
    byte_c = config.get(COST_PER_BYTE_TRANSPORT)
    icx_c = config.get(COST_PER_BYTE_INTERCONNECT)
    # broadcast: replicate the build table once, stream the probe scan
    bc_cost = build_bytes * byte_c + probe_ds.num_rows * scan_c
    # partitioned: both sides ship twice over the exchange; each node
    # scans 1/N of the probe rows
    shuffle = 2 * (probe_bytes + build_bytes)
    n = max(1, int(cluster_nodes))
    pt_cost = shuffle * icx_c + (probe_ds.num_rows / n) * scan_c
    forced = str(config.get(JOIN_MODE)).lower()
    if forced in ("broadcast", "partitioned", "host"):
        return JoinEstimate(forced, probe_bytes, build_bytes, shuffle,
                            bc_cost, pt_cost, "forced by sdot.join.mode")
    can_bc = build_bytes <= cap
    can_pt = cluster_nodes > 1
    if can_bc and (not can_pt or bc_cost <= pt_cost):
        return JoinEstimate("broadcast", probe_bytes, build_bytes,
                            shuffle, bc_cost, pt_cost,
                            f"build fits cap ({build_bytes:,} <= {cap:,})")
    if can_pt:
        why = "build exceeds broadcast cap" if not can_bc \
            else "exchange prices cheaper"
        return JoinEstimate("partitioned", probe_bytes, build_bytes,
                            shuffle, bc_cost, pt_cost, why)
    if can_bc:
        return JoinEstimate("broadcast", probe_bytes, build_bytes,
                            shuffle, bc_cost, pt_cost, "no cluster")
    return JoinEstimate("host", probe_bytes, build_bytes, shuffle,
                        bc_cost, pt_cost,
                        "build exceeds broadcast cap; no cluster")
