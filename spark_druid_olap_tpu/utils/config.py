"""Three-tier configuration system.

Mirrors the reference's conf layering (SURVEY.md §5 "Config / flag system"):

1. per-datasource options at registration time
   (reference: ``DefaultSource.scala:197-308`` — ~17 DataSource options);
2. session-level flags under the ``sdot.*`` namespace
   (reference: ``spark.sparklinedata.*`` SQLConf entries,
   ``DruidPlanner.scala:60-169``);
3. per-session overrides of datasource options via
   ``sdot.datasource.option.<name>``
   (reference: ``DruidRelationInfo.scala:103-138``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional


@dataclasses.dataclass(frozen=True)
class ConfigEntry:
    key: str
    default: Any
    doc: str
    parse: Callable[[str], Any] = lambda s: s
    #: semantic keys change query RESULTS or compiled programs and belong
    #: in cache fingerprints; operational keys (quotas, cadence, history
    #: sizing) must NOT churn every cache on tuning (sdlint keys/K4)
    semantic: bool = True


def _parse_bool(s: str) -> bool:
    return str(s).strip().lower() in ("1", "true", "yes", "on")


_REGISTRY: Dict[str, ConfigEntry] = {}


def _entry(key: str, default: Any, doc: str, parse=None,
           semantic: bool = True) -> ConfigEntry:
    if parse is None:
        if isinstance(default, bool):
            parse = _parse_bool
        elif isinstance(default, int):
            parse = int
        elif isinstance(default, float):
            parse = float
        else:
            parse = lambda s: s
    e = ConfigEntry(key, default, doc, parse, semantic)
    _REGISTRY[key] = e
    return e


# --- planner flags (reference: DruidPlanner.scala:60-169) ---------------------
DEBUG_TRANSFORMATIONS = _entry(
    "sdot.debug.transformations", False,
    "Log each planner transform's input and output (reference: "
    "spark.sparklinedata.druid.debug.transformations).")
TZ_ID = _entry(
    "sdot.timezone", "UTC",
    "Timezone for time bucketing and interval arithmetic (reference: "
    "spark.sparklinedata.tz.id).")
SELECT_PAGE_SIZE = _entry(
    "sdot.select.pagesize", 10000,
    "Rows per page for non-aggregate (select) scans (reference: "
    "spark.sparklinedata.druid.selectquery.pagesize).")
SELECT_DEVICE_MIN_ROWS = _entry(
    "sdot.select.device.min.rows", 1 << 17,
    "Min datasource rows before a select (raw scan) query evaluates its "
    "filter on device (compiled mask program, 32x bit-packed transfer); "
    "below it the host numpy path wins (device dispatch floor). 0 forces "
    "the device path when a device filter exists.")
ALLOW_TOPN = _entry(
    "sdot.querycostmodel.topn.allow", True,
    "Allow rewriting single-dim ordered-limit group-bys to the approximate "
    "topN path (reference: spark.sparklinedata.druid.allow.topn).")
TOPN_THRESHOLD = _entry(
    "sdot.querycostmodel.topn.threshold", 100000,
    "Max limit value eligible for the topN rewrite (reference: "
    "spark.sparklinedata.druid.topn.threshold).")
QUERY_HISTORY = _entry(
    "sdot.enable.query.history", True,
    "Record executed engine queries with timings into the bounded history "
    "queue (reference: spark.sparklinedata.enable.druid.query.history).")
QUERY_HISTORY_SIZE = _entry(
    "sdot.query.history.size", 500,
    "Bounded size of the in-memory query history queue (reference: "
    "DruidQueryHistory MAX_SIZE=500).")
PHASES_ENABLED = _entry(
    "sdot.phases.enabled", True,
    "Per-query host-path phase profiler (utils/phases.py): attribute "
    "host time to named phases (parse, plan.*, wlm.admit, compile, "
    "bind, dispatch, ...) emitted as stats[\"phases\"] and aggregated "
    "into BENCH JSON. Two clock reads per phase — cheap enough to stay "
    "always-on (< 1% of wall; no Druid analog).", semantic=False)
NON_AGG_PUSHDOWN = _entry(
    "sdot.nonagg.handling", "push_project_and_filters",
    "Handling of non-aggregate queries: push_project_and_filters | "
    "push_filters | push_none (reference: NonAggregateQueryHandling, "
    "DruidRelationInfo.scala:27-32).")
MODULES = _entry(
    "sdot.modules", "",
    "Comma-separated extension modules to install at Context creation, as "
    "package.module:ClassName (reference: spark.sparklinedata.modules via "
    "ModuleLoader).")
# --- cost model knobs (reference: DruidQueryCostModel via DruidPlanner) -------
COST_MODEL_ENABLED = _entry(
    "sdot.querycostmodel.enabled", True,
    "Use the cost model to pick single-chip vs sharded execution and the "
    "segments-per-wave; if false always use the sharded path (reference: "
    "spark.sparklinedata.querycostmodel.enabled).")
COST_PER_ROW_SCAN = _entry(
    "sdot.querycostmodel.historical.processing.cost", 1e-8,
    "Abstract cost to scan+filter one row on one chip (reference: "
    "historicalProcessingCostPerRow).", float)
COST_PER_ROW_MERGE = _entry(
    "sdot.querycostmodel.historical.merge.cost", 7e-8,
    "Abstract cost to merge one output row across shards (reference: "
    "historicalTimeSeriesProcessingCostPerRow).", float)
COST_PER_BYTE_TRANSPORT = _entry(
    "sdot.querycostmodel.transport.cost", 2.5e-9,
    "Abstract cost to move one byte host<->device or across DCN (reference: "
    "sparkSchedulingCostPerTask/shuffleCostPerByte family).", float)
COST_COMPILE = _entry(
    "sdot.querycostmodel.compile.cost", 0.05,
    "Fixed abstract cost charged per distinct compiled program (XLA "
    "compilation amortization; no reference analog — TPU-specific).", float)
COST_SHARD_EFFICIENCY = _entry(
    "sdot.querycostmodel.shard.efficiency", 1.0,
    "Calibrated parallel efficiency of the mesh's scan split in (0, 1]: "
    "1.0 = N chips scan N-fold faster (real ICI-connected TPUs); a "
    "virtual mesh over shared host cores measures far lower and the "
    "single-vs-sharded decision must reflect that. Fit by "
    "tools/calibrate.py from measured wall times.", float)
COST_PER_BYTE_INTERCONNECT = _entry(
    "sdot.querycostmodel.interconnect.cost", 5e-10,
    "Abstract cost to move one byte across the device interconnect (ICI) "
    "during the cross-chip merge of per-device partial aggregates — the "
    "mesh tier's analog of the reference's broker-merge transport term. "
    "Prices the reduction payload (merged partial bytes x (n_dev - 1)) "
    "so wide outputs on small scans correctly prefer single-device "
    "execution.", float)
COST_SORT_ROW = _entry(
    "sdot.querycostmodel.sort.seconds.per.row", 7e-10,
    "Measured seconds per row of late materialization's position sort: "
    "one int32 operand, a unique key, no stability (ops.scan."
    "compact_scan). Default = v5e measurement (2.6ms / 4.0M rows, 4.4ms "
    "/ 6.0M; a STABLE 2-operand sort of the same rows is 5.8 / 10.3ms); "
    "tools/calibrate.py refits it on the live backend — the CPU "
    "fallback's x64 sort is ~400x this, which is what flips the "
    "compaction gate there. Also what ops.hll.register_form prices the "
    "HLL registers' packed-key sort with (v5e: 5.4-7.5ms / 8.0M rows).",
    float)
COST_SORT_PAYLOAD_ROW = _entry(
    "sdot.querycostmodel.sort.payload.seconds.per.row", 6.7e-10,
    "Measured seconds per row per EXTRA sort payload operand "
    "(v5e: +4.1ms / 6.0M rows each, +1.8ms / 4.0M). Fit by "
    "tools/calibrate.py.", float)
COST_SCATTER_UPDATE = _entry(
    "sdot.querycostmodel.scatter.seconds.per.update", 6.7e-9,
    "Measured seconds per update of an XLA scatter/segment-sum into a "
    "group table that FITS in cache (v5e: ~40ms / 6M updates, index "
    "order irrelevant; fit at a 128KB table by tools/calibrate.py; the "
    "HLL registers' segment_max 54.6ms / 8.0M). The past-cache thrash "
    "regime is the separate scatter.big constant.", float)
COST_SCATTER_UPDATE_BIG = _entry(
    "sdot.querycostmodel.scatter.big.seconds.per.update", 6.7e-9,
    "Measured seconds per scatter update when the group table exceeds "
    "sdot.querycostmodel.table.cache.bytes. On TPU this equals the "
    "small-table constant (HBM scatters are size-invariant, measured); "
    "on the CPU fallback random updates into a table past LLC are "
    "~30-50x the in-cache cost — the regime behind the measured SF10 "
    "compacted-vs-uncompacted crossover. Fit by tools/calibrate.py.",
    float)
COST_TABLE_CACHE_BYTES = _entry(
    "sdot.querycostmodel.table.cache.bytes", 24 << 20,
    "Group-table byte size above which scatter updates are costed at the "
    "big-table constant (≈ the host LLC on the CPU fallback; irrelevant "
    "on TPU where both constants are equal).", int)
COST_GATHER_PROBE = _entry(
    "sdot.querycostmodel.gather.seconds.per.probe", 9e-9,
    "Measured seconds per probe of a flattened 1D device gather "
    "(v5e: 7.1ns at random positions, 8.2-9.2ns at late "
    "materialization's sorted ones, up to 23ns for 2^20 of them; 1-8ns "
    "in the HLL registers' run-end search). Fit by tools/calibrate.py.",
    float)
COST_FUSED_ROW = _entry(
    "sdot.querycostmodel.fused.seconds.per.row", 3.3e-10,
    "Measured seconds per row of the fused Pallas small-K group-by "
    "kernel's single streamed pass (v5e: 1.0-2.0ms of device time for "
    "6.0M rows, TPC-H q5/q12/q1). Governs the ffl-route compaction "
    "ceiling: the kernel streams a row faster than the compaction sort "
    "orders it, so an ffl statement compacts only when told to.", float)
# --- engine knobs (TPU-specific; no reference analog) -------------------------
SEGMENT_ROWS = _entry(
    "sdot.segment.target.rows", 1 << 20,
    "Target rows per time-sharded segment at ingest.")
SCAN_COMPACT = _entry(
    "sdot.engine.scan.compact", True,
    "Late materialization: when the filter-selectivity estimate says few "
    "rows survive, sort survivors to a static prefix and run group-key "
    "building, value derivation, and aggregation at O(survivors) instead "
    "of O(rows). The budget follows the survivors a statement shape was "
    "seen to keep; a run that exceeds it is run again at twice the count.")
SCAN_COMPACT_MIN_ROWS = _entry(
    "sdot.engine.scan.compact.min.rows", 1 << 21,
    "Scans below this many rows never compact (the sort pass wins "
    "nothing at small scale).")
GROUPBY_PALLAS_MAX_KEYS = _entry(
    "sdot.engine.groupby.pallas.max.keys", 64,
    "Dense group-by uses the fused single-pass Pallas TPU kernel when the "
    "fused key cardinality is at most this (0 disables). Also honors env "
    "SDOT_PALLAS=0|interpret.")
PALLAS_WAVE_ENABLED = _entry(
    "sdot.pallas.wave.enabled", True,
    "Shared-scan fused groups lower each dispatch wave to ONE "
    "hand-scheduled Pallas mega-kernel (ops/pallas_wave.py) when every "
    "lane's aggregations are wave-eligible: union columns tile through "
    "VMEM once, CSE'd shared predicates evaluate once per tile, and all "
    "lanes' filtered aggregates accumulate in kernel scratch. False "
    "routes back to the XLA jaxpr-fused program (kill switch). Requires "
    "a TPU-class backend or SDOT_PALLAS=interpret (CPU CI).")
PALLAS_WAVE_TILE_BYTES = _entry(
    "sdot.pallas.wave.tile.bytes", 8 << 20,
    "VMEM budget (bytes) the wave mega-kernel's tile planner fits the "
    "double-buffered union-column tiles plus the resident scratch "
    "accumulator block into (~half of a v5e core's 16MB VMEM).", int)
PALLAS_WAVE_MAX_LANES = _entry(
    "sdot.pallas.wave.max.lanes", 16,
    "Max fused lanes (distinct constituent plans) a single wave "
    "mega-kernel accumulates; larger groups fall back to the jaxpr-fused "
    "program (trace size and scratch rows grow per lane).", int)
MESH_ENABLED = _entry(
    "sdot.mesh.enabled", True,
    "Shared-scan fused groups (parallel/sharedscan.py) shard their "
    "segment waves across the local device mesh (parallel/meshexec.py): "
    "each device scans its segment slice — through the Pallas wave "
    "mega-kernel when the group is wave-eligible — and per-lane partial "
    "aggregates merge on the interconnect with the register algebra "
    "AGG_CLOSURE declares (psum sums/counts, pmax min-sentinel-free "
    "maxima + HLL registers, pmin minima + theta hash minima). False "
    "pins the fused tier to single-device execution (kill switch); solo "
    "queries keep their own cost-model shard decision either way.")
MESH_AUTO = _entry(
    "sdot.mesh.auto", False,
    "Build the local device mesh automatically at Context startup when "
    "more than one device is visible — how subprocess deployments "
    "(cluster historicals via --set sdot.mesh.auto=true) opt their "
    "engines into the multi-chip mesh tier without a code-level mesh "
    "handle. The in-process equivalent is Context(auto_mesh=True).")
MESH_MIN_SEGMENTS = _entry(
    "sdot.mesh.min.segments", 2,
    "Minimum selected segments before the fused tier shards a group "
    "across the mesh; below it one device owns the whole scan (a "
    "1-segment-per-device split pays collective latency for no scan "
    "parallelism).", int)
GROUPBY_MATMUL_MAX_KEYS = _entry(
    "sdot.engine.groupby.matmul.max.keys", 4096,
    "Dense group-by uses the MXU one-hot matmul path when the fused key "
    "cardinality is at most this; above it, scatter-add.")
JOIN_ENABLED = _entry(
    "sdot.join.enabled", True,
    "General (non-star) joins execute on the device join tier "
    "(join/broadcast.py, join/partitioned.py) when the statement shape "
    "qualifies; False routes every non-star join to the host pandas "
    "fallback (kill switch — answers are identical, only placement "
    "changes).")
JOIN_BROADCAST_MAX_BYTES = _entry(
    "sdot.join.broadcast.max.bytes", 64 << 20,
    "Build-side byte ceiling for the broadcast hash-join tier: when the "
    "smaller side's estimated bytes fit, its hash table is built once "
    "per node, device-resident, and probed inside the segment wave "
    "loop. Bigger builds go to the cluster partitioned tier (when a "
    "broker is attached) or the host fallback.", int)
JOIN_MAX_MATCHES = _entry(
    "sdot.join.max.matches", 64,
    "Widest per-key duplicate group the device probe expands in "
    "registers (the static match-expansion width C). A build side with "
    "a hotter key declines to the host fallback instead of "
    "materializing an oversized expansion.", int)
JOIN_PARTITIONS = _entry(
    "sdot.join.partitions", 0,
    "Hash-partition count for the cluster partitioned-join exchange "
    "(both sides re-shard on the join key through the historicals). "
    "0 = one partition per cluster node.", int)
JOIN_MODE = _entry(
    "sdot.join.mode", "auto",
    "Join-tier placement override: 'auto' (cost model picks), "
    "'broadcast', 'partitioned', or 'host' (device join tiers "
    "disabled for this statement shape only).")
GROUPBY_DENSE_MAX_KEYS = _entry(
    "sdot.engine.groupby.dense.max.keys", 1 << 22,
    "Max fused key cardinality for the dense device group-by; above it the "
    "engine switches to the hashed group-by (ops/hash_groupby.py).")
GROUPBY_SORTED_MIN_KEYS = _entry(
    "sdot.engine.groupby.sorted.min.keys", 1024,
    "Medium-K routing: key cardinalities at or above this route to the "
    "sorted-run tier even below dense.max.keys when the backend's sort "
    "is cheap (the sorted-run auto gate). The dense one-hot matmul "
    "writes ~N*K onehot bytes through HBM per scan — at v5e bandwidth "
    "that crosses the one-sort-plus-payloads cost near K~512. 0 "
    "disables the medium-K reroute.")
GROUPBY_HASH_SLOTS = _entry(
    "sdot.engine.groupby.hash.slots", 0,
    "Group-table slot count for the hashed group-by (any value; used "
    "as-is). 0 = auto-size to the next power of two above the group-count "
    "upper bound min(key space, selected rows). Overflow retries at 4x up "
    "to sdot.engine.groupby.hash.max.slots.")
DEVICE_CACHE_BYTES = _entry(
    "sdot.engine.device.cache.bytes", 8 << 30,
    "Budget for device-resident bound column arrays (host-side bytes "
    "tracked per upload). When a new binding would exceed it the whole "
    "array cache is dropped and rebuilt on demand — bounding HBM held by "
    "shifting segment selections (paged selects, moving intervals).")
GROUPBY_HASH_MAX_SLOTS = _entry(
    "sdot.engine.groupby.hash.max.slots", 1 << 24,
    "Max hash-table slot count; a query whose actual group count exceeds "
    "what this table can hold falls back to the host tier (reference "
    "contract: Druid groupBy v2 spills, never refuses — "
    "DruidQuerySpec.scala:558-571).")
HAVING_DEVICE_MIN_KEYS = _entry(
    "sdot.engine.having.device.min.keys", 1 << 16,
    "Min fused key cardinality before an exact-comparable HAVING (int "
    "literal vs limb/i32/i64/f64 aggregate) evaluates on device and only "
    "passing groups transfer (two dispatches: finals+mask count, then "
    "gather). Below it the full [K] result transfers and the host "
    "filters.")
DATABASE_DEFAULT = _entry(
    "sdot.database.default", "",
    "Default database namespace: an unqualified table name that is not "
    "registered resolves to '<default>.<name>' when that is (reference: "
    "multi-database operation across non-default Hive DBs, "
    "MultiDBTest.scala). Databases are dotted name prefixes in the one "
    "store; 'db.table' in FROM always addresses explicitly.")
BACKEND_RETRY_SECONDS = _entry(
    "sdot.engine.backend.retry.seconds", 30.0,
    "Cooldown between re-attach probes after the device backend is lost "
    "mid-session (e.g. the chip drops off the host): statements keep "
    "being served by the host tier, and at most one probe per cooldown "
    "window checks "
    "whether the device answers again (≈ the reference's ZK-watch cache "
    "invalidation re-planning against live servers, "
    "CuratorConnection.scala:77-136).", float)
TOPN_DEVICE_MIN_KEYS = _entry(
    "sdot.engine.topn.device.min.keys", 8192,
    "Min fused key cardinality before an ordered-limit group-by / topN "
    "runs its top-k selection on device (lax.top_k over the merged "
    "partials, transferring only the candidate rows). Below it the full "
    "[K] result transfers and the host sorts (cheap at small K).")
GROUPBY_HASH_MAX_SLOTS_CPU = _entry(
    "sdot.engine.groupby.hash.max.slots.cpu", 1 << 23,
    "Hash-table slot ceiling on non-TPU backends (effective cap = "
    "min(this, sdot.engine.groupby.hash.max.slots)). Measured basis: x64 "
    "scatters into a 16M-slot table thrash the host cache so badly the "
    "pandas host tier is ~3x faster (q18-inner SF10: 530s engine vs 193s "
    "host) — above the ceiling the query demotes to the host tier.")
GROUPBY_HASH_SORTED = _entry(
    "sdot.engine.groupby.hash.sortedrun", "auto",
    "Sorted-run aggregation for the hashed group-by tier "
    "(ops/sorted_groupby.py): ride agg values as sort payloads and "
    "replace per-agg scatters with prefix scans + run-boundary reads. "
    "'auto' = on for TPU backends (the sort is ~30x cheaper than one "
    "scatter there) and off on the CPU fallback (x64 sort dominates); "
    "'on'/'off' force it (tests force 'on' for differential coverage).")
GROUPBY_HASH_COMPACT_MIN = _entry(
    "sdot.engine.groupby.hash.compact.min.slots", 1 << 18,
    "Min hash-table slot count before the hashed group-by compacts on "
    "device (two dispatches: build table + read occupancy count, then "
    "gather only occupied slots) instead of transferring the full [T] "
    "table. Worth one extra dispatch RTT whenever the table is sized "
    "far above the actual group count.")
WAVE_MAX_BYTES = _entry(
    "sdot.engine.wave.max.bytes", 0,
    "Per-device byte budget for one execution wave's scan arrays; a scan "
    "whose bound arrays exceed it runs in multiple bounded waves over the "
    "segment axis. 0 = auto (60% of the device's reported HBM limit, or "
    "unbounded when the backend reports none). Reference analog: the cost "
    "model's segments-per-query limit bounding per-historical work "
    "(DruidQueryCostModel.scala:343-414).")
HLL_LOG2M = _entry(
    "sdot.engine.hll.log2m", 11,
    "log2 of the HLL register count for approximate count-distinct "
    "(reference: Druid hyperUnique uses 2^11 registers).")
QUANTILE_LANES = _entry(
    "sdot.quantile.lanes", 256,
    "Sample lanes per KLL level for percentile_approx (ops/kll.py). "
    "Register width is 2*4*lanes + 4 int32 per group; rank error "
    "shrinks ~1/sqrt(lanes). Must match across every engine in a "
    "cluster — registers merge elementwise at the broker.")
QUANTILE_RANK_BOUND = _entry(
    "sdot.quantile.rank_bound", 0.05,
    "Maximum |rank(estimate) - fraction| the bench/loadtest percentile "
    "differential gates accept from the KLL estimate (rank space, not "
    "value space — value error is unbounded for heavy-tailed data).")
WINDOW_ENABLED = _entry(
    "sdot.window.enabled", True,
    "Window-function post-pass (window/): OVER (PARTITION BY ... ORDER "
    "BY ...) computed by segment-sorted device kernels over the grouped "
    "(and, clustered, broker-merged) result frame. Off = window queries "
    "raise unsupported.")
WINDOW_MAX_FRAME = _entry(
    "sdot.window.max.frame", 1024,
    "Largest bounded ROWS frame (preceding + following + 1) the device "
    "window kernels lower via shift-stacking; wider frames raise "
    "unsupported rather than materializing an unbounded shift stack.")
# --- semantic result cache (cache/) -------------------------------------------
CACHE_ENABLED = _entry(
    "sdot.cache.enabled", True,
    "Semantic query-result cache over engine aggregate results "
    "(cache/result_cache.py): identical queries are served from host "
    "memory without touching the device. Keys fold in the per-datasource "
    "ingest version, so staleness is structural — any re-ingest, stream "
    "append or drop invalidates (≈ Druid's broker/historical result "
    "caches keyed on segment versions).")
CACHE_MAX_BYTES = _entry(
    "sdot.cache.max_bytes", 256 << 20,
    "Byte budget for materialized results held by the semantic result "
    "cache; least-recently-used entries evict past it. Results larger "
    "than the whole budget are never admitted.")
CACHE_SUBSUMPTION = _entry(
    "sdot.cache.subsumption", True,
    "Answer queries from SUPERSET cached entries without re-executing "
    "(cache/subsume.py): coarser-granularity timeseries from a cached "
    "finer one, TopN and dim-filtered GroupBy from a cached "
    "unfiltered/unlimited GroupBy over the same dims, and "
    "having/limit/post-agg re-evaluation on cached partials.")
# --- materialized rollup datasources (mv/) ------------------------------------
MV_REWRITE_ENABLED = _entry(
    "sdot.mv.rewrite.enabled", True,
    "Automatically rewrite eligible GroupBy queries onto a registered "
    "materialized rollup datasource (mv/match.py): grouping dims covered "
    "by the rollup dims (join-key equivalences count), merge-closed "
    "derivable aggregations, dim-only filters, cleanly-coarsening "
    "granularity. Stale rollups (base re-ingested since the build) are "
    "bypassed, never served (≈ Sparkline rewriting onto the Druid "
    "rollup index).")
PLAN_CACHE_ENABLED = _entry(
    "sdot.plan.cache.enabled", True,
    "Statement plan cache (pushdown + composite plans keyed on store "
    "version and config fingerprint). Benchmarks disable it so measured "
    "reps time the full rewrite/build/execute path instead of a "
    "statement-cache hit.")
PLAN_MEMO_ENABLED = _entry(
    "sdot.plan.memo.enabled", True,
    "Memoize the planning-cascade outcome per canonical statement "
    "(window extraction, resolution, rewrites, built plan, join "
    "recognition, composite plan — including NEGATIVE recognizer "
    "results), keyed like the plan cache on store version + config "
    "fingerprint plus a lookup-table fingerprint. A warm repeated "
    "statement skips straight from canonical key to the cached "
    "compiled program; distinct from sdot.plan.cache.enabled, which "
    "benchmarks disable. Purely a host-latency optimization: the "
    "memoized plan is bit-identical to a cold re-plan.",
    semantic=False)
PLAN_MEMO_ENTRIES = _entry(
    "sdot.plan.memo.entries", 128,
    "Max memoized planning-cascade outcomes; least-recently-used "
    "statements evict past it.", int, semantic=False)
# --- workload management (wlm/) -----------------------------------------------
WLM_ENABLED = _entry(
    "sdot.wlm.enabled", True,
    "Admission control in front of the engine (wlm/): every query is "
    "classified into a named lane with bounded concurrency and queue "
    "depth; overload sheds with a retryable rejection (HTTP 429 + "
    "Retry-After) instead of melting every in-flight query (≈ Druid "
    "query laning / QueryScheduler).", semantic=False)
WLM_LANES = _entry(
    "sdot.wlm.lanes",
    "interactive:slots=8,queue=64;reporting:slots=4,queue=32;"
    "batch:slots=2,queue=16",
    "Lane layout: 'name:slots=N,queue=N,wait_ms=N,timeout_ms=N,"
    "priority=N;...'. slots = concurrent queries in the lane, queue = "
    "bounded wait-queue depth past which admissions shed, wait_ms = max "
    "queue-wait budget (0 = only the query's own timeout bounds it), "
    "timeout_ms = default QueryContext timeout applied when the client "
    "set none, priority = default admission priority (higher first).",
    semantic=False)
WLM_DEFAULT_LANE = _entry(
    "sdot.wlm.default.lane", "interactive",
    "Lane for queries with no explicit context.lane (before cost-based "
    "demotion is considered).", semantic=False)
WLM_BATCH_COST = _entry(
    "sdot.wlm.batch.cost.threshold", 0.5,
    "Estimated single-chip cost units (parallel/cost.estimate) at or "
    "above which a query without an explicit lane is demoted to the "
    "'batch' lane (≈ Druid HiLoQueryLaningStrategy). 0 disables "
    "cost-based demotion. Per-tenant quotas ride the same config "
    "channel as free-form keys: 'sdot.wlm.quota.<tenant>' = "
    "'concurrent=N,budget=F,refill=F' ('default' is the template for "
    "tenants without an explicit entry).", float, semantic=False)
# --- shared-scan multi-query execution (parallel/sharedscan.py) ---------------
SHAREDSCAN_ENABLED = _entry(
    "sdot.sharedscan.enabled", False,
    "Coalesce concurrent eligible queries (engine-mode GroupBy / "
    "Timeseries / TopN) over the same datasource into ONE fused device "
    "program: each segment wave's column union binds once and every "
    "constituent's filter + aggregation lanes evaluate against the "
    "shared in-HBM bind, then results demultiplex per query (each still "
    "populating the result cache under its own canonical key). Off by "
    "default: solo workloads pay the hold window for nothing.")
WLM_BATCH_WINDOW_MS = _entry(
    "sdot.wlm.batch.window.ms", 8.0,
    "Micro-batch hold window for the shared-scan tier: the first "
    "eligible query on a datasource holds this long for companions "
    "before dispatching (group-commit semantics). Held time counts "
    "against the query's own timeout_millis. The window closes early "
    "when sdot.sharedscan.max.queries constituents have joined.", float)
SHAREDSCAN_MAX_QUERIES = _entry(
    "sdot.sharedscan.max.queries", 8,
    "Constituent cap per coalesced group: the hold window closes early "
    "at this size, bounding fused-program width (compile cost and "
    "output-buffer size grow with every extra query lane).")
SHAREDSCAN_FUSION_ENABLED = _entry(
    "sdot.sharedscan.fusion.enabled", True,
    "Cross-lane fusion planner (planner/fusion.py): canonicalize every "
    "lane's filter tree into a shared sub-expression DAG, lower each "
    "distinct sub-predicate ONCE per fused program (shared masks first, "
    "then per-lane base = row_valid & shared & residual), and thread "
    "the same CSE cache through the solo dense/hashed cores for "
    "queries whose own tree repeats sub-predicates. Bit-identical "
    "answers by construction (masks combine with exact bool ops); any "
    "planning error falls back to unfused lowering. Folded into every "
    "affected compile signature, so toggling recompiles rather than "
    "reusing a mismatched program.")
SHAREDSCAN_FUSION_MAX_NODES = _entry(
    "sdot.sharedscan.fusion.max.nodes", 512,
    "Planner cost guard: per-group cap on distinct predicate nodes the "
    "fusion analysis will canonicalize. A group over the cap plans "
    "unfused (the host-side DAG walk is O(nodes) per execution and "
    "must stay negligible next to the dispatch floor). 0 = uncapped.")
# --- durable segment persistence (persist/) -----------------------------------
PERSIST_PATH = _entry(
    "sdot.persist.path", "",
    "Root directory of the on-disk snapshot store (deep storage). Empty "
    "disables persistence entirely: the segment store is volatile, as the "
    "reference is without its Druid deep-storage tier. Set to a directory "
    "to enable versioned checkpoints, the stream-ingest WAL, and startup "
    "recovery.")
PERSIST_ENABLED = _entry(
    "sdot.persist.enabled", True,
    "Master gate for the persist subsystem when sdot.persist.path is set "
    "(lets an operator keep the path configured but run volatile).")
PERSIST_RECOVER = _entry(
    "sdot.persist.recover.on.start", True,
    "Recover published snapshots + WAL tails into the segment store at "
    "Context creation. Off = the directory is only written, never read "
    "(fresh-start semantics with durability still on).")
PERSIST_WAL_FSYNC = _entry(
    "sdot.persist.wal.fsync", True,
    "fsync the write-ahead journal before a stream_ingest batch is "
    "considered committed. Off trades the kill -9 durability guarantee "
    "for append throughput (an OS crash can lose the un-synced tail; "
    "replay still stops cleanly at the first torn record).",
    semantic=False)
PERSIST_CHECKPOINT_SECONDS = _entry(
    "sdot.persist.checkpoint.interval.seconds", 0.0,
    "Cadence of the background checkpointer folding dirty datasources "
    "(new/re-ingested, or WAL tail past the byte budget) into fresh "
    "snapshots. 0 disables the thread; CHECKPOINT statements and "
    "Context.checkpoint() still work.", float, semantic=False)
PERSIST_CHECKPOINT_MAX_BYTES = _entry(
    "sdot.persist.checkpoint.max.bytes", 0,
    "Byte budget for ONE background checkpoint pass: dirty datasources "
    "snapshot in ascending size order until the pass would exceed it; "
    "the rest stay dirty for the next tick (bounds the I/O burst a "
    "cadence tick can issue). 0 = unbounded.", int, semantic=False)
PERSIST_KEEP_SNAPSHOTS = _entry(
    "sdot.persist.keep.snapshots", 2,
    "Published snapshot versions retained per datasource; older versions "
    "are pruned after each successful publish. Must be >= 1 (the current "
    "version is never pruned).", semantic=False)
PERSIST_VERIFY_CHECKSUMS = _entry(
    "sdot.persist.verify.checksums", True,
    "Verify per-file CRC32 checksums against the manifest during "
    "recovery. A mismatch quarantines that snapshot version and recovery "
    "falls back to the previous one (or the WAL alone) — the engine "
    "always starts.", semantic=False)
PERSIST_GROUP_COMMIT = _entry(
    "sdot.persist.wal.group.commit", True,
    "Route stream-ingest WAL appends through the shared commit queue: "
    "one fsync covers every frame queued by concurrent producers, and "
    "each ACK is released only after its covering fsync (ACK-implies-"
    "durable unchanged, fsync cost amortized). Off = one fsync per "
    "append, the original path.", semantic=False)
PERSIST_APPEND_PARALLEL = _entry(
    "sdot.persist.append.parallel", True,
    "Build a stream-append's dimension/metric columns across a thread "
    "pool (per-column dictionary union + order-preserving remap are "
    "independent, so the result is bit-identical to the serial build). "
    "Only engages past a small batch-row floor.", semantic=False)
PERSIST_COMPACT_SECONDS = _entry(
    "sdot.persist.compact.interval.seconds", 0.0,
    "Cadence of the background compactor rolling a stream-appended tail "
    "of many small segments into time-partitioned segments (atomic "
    "generation swap: snapshot publish + WAL truncate + quiet in-memory "
    "swap, no ingest-version bump — caches and rollup staleness are "
    "untouched because the rows are identical). 0 disables the thread; "
    "PersistManager.compact() still works.", float, semantic=False)
PERSIST_COMPACT_MIN_SEGMENTS = _entry(
    "sdot.persist.compact.min.segments", 8,
    "Segment-count floor below which the compactor leaves a datasource "
    "alone (compacting a handful of segments buys nothing and churns "
    "snapshot versions).", int, semantic=False)
# --- host-tier safety valve ---------------------------------------------------
HOST_GATHER_PAGE_BYTES = _entry(
    "sdot.host.gather.page.bytes", 32 << 20,
    "Byte budget for ONE paged cross-process gather when "
    "Datasource.complete() reassembles a partial store's column on the "
    "host tier; larger columns exchange in multiple bounded pages "
    "instead of one unbounded allgather.")
# --- distributed serving tier (cluster/) --------------------------------------
CLUSTER_NODES = _entry(
    "sdot.cluster.nodes", "",
    "Comma-separated host:port list of historical nodes, index order = "
    "node id. Empty disables the cluster tier (single-process engine). "
    "Every process of one cluster — broker and historicals — must be "
    "given the identical list: the deterministic shard assignment "
    "(cluster/assign.py) is a pure function of this list plus the deep "
    "storage manifests.", semantic=False)
CLUSTER_ROLE = _entry(
    "sdot.cluster.role", "",
    "Role of THIS process in the cluster: 'broker' attaches the "
    "scatter/merge client to the engine; 'historical' is set by the "
    "cluster entrypoint on serving nodes; empty = not clustered.",
    semantic=False)
CLUSTER_NODE_ID = _entry(
    "sdot.cluster.node.id", 0,
    "This historical's index into sdot.cluster.nodes (which address it "
    "serves on and which shards it owns).", int, semantic=False)
CLUSTER_REPLICATION = _entry(
    "sdot.cluster.replication", 2,
    "Copies of each segment shard across historicals (clamped to the "
    "node count). The broker retries a failed shard on each replica "
    "before declaring the shard unreachable.", int, semantic=False)
CLUSTER_SHARDS = _entry(
    "sdot.cluster.shards", 0,
    "Segment shards per datasource the broker scatters over; 0 = one "
    "per node. Semantic: the shard composition fixes the partial-merge "
    "grouping (float accumulation order), so cached results are keyed "
    "on it.", int)
CLUSTER_RPC_TIMEOUT_SECONDS = _entry(
    "sdot.cluster.rpc.timeout.seconds", 30.0,
    "Socket timeout for one broker->historical subquery RPC. A timeout "
    "marks the node down and fails the attempt over to a replica.",
    float, semantic=False)
CLUSTER_RETRY_TRIES = _entry(
    "sdot.cluster.retry.tries", 3,
    "Full passes over a shard's replica set before the broker gives up "
    "on remote execution (then: local fallback if enabled, else fail). "
    "Between passes it sleeps with decorrelated-jitter backoff "
    "(utils/retry.py).", int, semantic=False)
CLUSTER_RETRY_BACKOFF_START_SECONDS = _entry(
    "sdot.cluster.retry.backoff.start.seconds", 0.05,
    "Base delay of the decorrelated-jitter backoff between replica-set "
    "passes.", float, semantic=False)
CLUSTER_RETRY_BACKOFF_CAP_SECONDS = _entry(
    "sdot.cluster.retry.backoff.cap.seconds", 2.0,
    "Delay ceiling of the decorrelated-jitter backoff between "
    "replica-set passes.", float, semantic=False)
CLUSTER_PROBE_INTERVAL_SECONDS = _entry(
    "sdot.cluster.probe.interval.seconds", 1.0,
    "Cadence of the broker's background health prober (GET /readyz on "
    "every node). A failing probe marks the node down — its shards "
    "route to replicas — and a passing one marks it back up. "
    "0 disables probing (nodes are still marked down reactively on "
    "RPC failure).", float, semantic=False)
CLUSTER_SCATTER_THREADS = _entry(
    "sdot.cluster.scatter.threads", 16,
    "Worker threads in the broker's scatter pool (concurrent subquery "
    "RPCs across all in-flight queries).", int, semantic=False)
CLUSTER_LOCAL_FALLBACK = _entry(
    "sdot.cluster.local.fallback", True,
    "When every replica of some shard is unreachable, execute the whole "
    "query on the broker's own engine (it holds a full recovered copy) "
    "instead of failing. Answers are identical; only placement changes.",
    semantic=False)
CLUSTER_PARTIAL_RESULTS = _entry(
    "sdot.cluster.partial.results", False,
    "Degraded mode: when every replica of some shard is unreachable, "
    "answer from the surviving shards and annotate the result with "
    "degraded={missing_shards, coverage_rows} instead of raising "
    "ShardUnavailable / falling back whole-query (this takes precedence "
    "over sdot.cluster.local.fallback for unreachable shards). Degraded "
    "answers are NEVER cached, so cached entries stay exact full "
    "answers and the key needs no new term.", semantic=False)
CLUSTER_BREAKER_FAILURES = _entry(
    "sdot.cluster.breaker.failures", 3,
    "Consecutive subquery failures against one node that open its "
    "circuit breaker (the broker then skips the node without an RPC "
    "until the cooldown elapses). 0 disables breakers.",
    int, semantic=False)
CLUSTER_BREAKER_COOLDOWN_SECONDS = _entry(
    "sdot.cluster.breaker.cooldown.seconds", 5.0,
    "How long an open breaker rejects attempts before letting ONE "
    "half-open probe RPC through; that probe's outcome closes or "
    "re-opens the breaker.", float, semantic=False)
CLUSTER_HEDGE_ENABLED = _entry(
    "sdot.cluster.hedge.enabled", False,
    "Hedged scatter: when a subquery RPC has not answered within the "
    "hedge delay, race a duplicate request to the next replica and take "
    "whichever answers first (the loser is discarded; replicas are "
    "exact copies, so answers are identical either way).",
    semantic=False)
CLUSTER_HEDGE_AFTER_MS = _entry(
    "sdot.cluster.hedge.after.ms", 0.0,
    "Fixed hedge delay in milliseconds; 0 = automatic (the observed "
    "subquery-latency quantile below, once enough samples exist).",
    float, semantic=False)
CLUSTER_HEDGE_QUANTILE = _entry(
    "sdot.cluster.hedge.quantile", 0.95,
    "Latency quantile of recent subquery RPCs used as the automatic "
    "hedge delay when sdot.cluster.hedge.after.ms is 0.",
    float, semantic=False)
CLUSTER_HEDGE_MIN_MS = _entry(
    "sdot.cluster.hedge.min.ms", 10.0,
    "Floor for the automatic hedge delay (keeps the quantile estimate "
    "from hedging every RPC while the sample window is still cold).",
    float, semantic=False)
CLUSTER_PROBE_JITTER = _entry(
    "sdot.cluster.probe.jitter", True,
    "Decorrelated jitter (utils/retry.backoff) on the background "
    "readyz prober's interval so N brokers don't probe a rejoining "
    "historical in lockstep; each tick lands in [0.5x, 1.5x] of "
    "sdot.cluster.probe.interval.seconds.", semantic=False)
# --- elastic topology: plan epochs (cluster/epoch.py) -------------------------
CLUSTER_EPOCH_POLL_SECONDS = _entry(
    "sdot.cluster.epoch.poll.seconds", 1.0,
    "Cadence at which a HISTORICAL polls deep storage for a newer plan "
    "epoch (cluster/epoch.py) and runs its side of the handover — warm "
    "newly owned shards before advertising, or drain-then-fence when "
    "the new epoch drops it. 0 disables the watcher thread (tests "
    "drive node.check_epoch() manually). The broker piggybacks its "
    "epoch check on the readyz prober interval.", float, semantic=False)
CLUSTER_EPOCH_DRAIN_GRACE_SECONDS = _entry(
    "sdot.cluster.epoch.drain.grace.seconds", 0.5,
    "How long a leaving historical keeps serving AFTER it observes the "
    "new epoch fully warm, before it starts draining — absorbs the "
    "window where the broker has not yet polled the same readiness and "
    "still scatters against the old epoch.", float, semantic=False)
CLUSTER_EPOCH_DRAIN_TIMEOUT_SECONDS = _entry(
    "sdot.cluster.epoch.drain.timeout.seconds", 10.0,
    "Upper bound a leaving historical waits for its in-flight "
    "subqueries to finish before fencing anyway (a stuck query must "
    "not pin a retired node forever).", float, semantic=False)
CLUSTER_REBALANCE_STRATEGY = _entry(
    "sdot.cluster.rebalance.strategy", "stable",
    "Shard owner placement: 'stable' (rendezvous hashing over logical "
    "node ids — an N->N+1 epoch moves ~1/(N+1) of the assignments, see "
    "cluster/assign.py) or 'modular' (the legacy CRC rotation, kept as "
    "a kill switch; nearly every owner moves on any topology change). "
    "Placement never changes answers, only which node serves a shard.",
    semantic=False)
CLUSTER_SUBQ_CACHE_ENABLED = _entry(
    "sdot.cluster.subq.cache.enabled", False,
    "Broker-side shard-level subquery result cache: partial results "
    "are cached per (subquery shape, shard identity, ingest version), "
    "so a repeated dashboard storm skips unchanged shards entirely. "
    "Keys carry shard identity — not node identity — so entries "
    "survive epoch transitions; the ingest-version term makes staleness "
    "impossible, so answers are bit-identical with the cache off. "
    "Opt-in: identical repeated queries are already absorbed by the "
    "broker's semantic result cache, and chaos/failover tests rely on "
    "repeats actually exercising the RPC path — enable it for mixed "
    "dashboard workloads whose queries share subquery shapes.",
    semantic=False)
CLUSTER_SUBQ_CACHE_MAX_BYTES = _entry(
    "sdot.cluster.subq.cache.max.bytes", 64 << 20,
    "Byte budget of the broker's shard-level subquery cache (LRU "
    "eviction).", int, semantic=False)
CLUSTER_INGEST_PUSH = _entry(
    "sdot.cluster.ingest.push", True,
    "Distributed ingest: after a stream-ingest batch is journaled and "
    "acknowledged on the broker (durability is ALWAYS local), push it "
    "to the time-matched shard's owners so distributed queries keep "
    "read-your-writes instead of falling back to broker-local serving "
    "until the next checkpoint. Off, or when any owner push fails, the "
    "broker's ingest-version check simply serves the datasource locally "
    "— never a correctness difference, only where the scan runs.",
    semantic=False)
CLUSTER_AUTOSCALE_ENABLED = _entry(
    "sdot.cluster.autoscale.enabled", False,
    "Autoscale hook (cluster/autoscale.py): the broker samples every "
    "historical's WLM queue depth on the prober cadence and calls the "
    "registered spawn/retire callbacks — which publish a new plan "
    "epoch — when the fleet-mean depth crosses the high/low marks. "
    "Without registered callbacks, decisions only increment counters "
    "(dry run).", semantic=False)
CLUSTER_AUTOSCALE_QUEUE_HIGH = _entry(
    "sdot.cluster.autoscale.queue.high", 8.0,
    "Fleet-mean WLM queued-query depth above which the autoscale hook "
    "signals scale-out (spawn a historical, publish an epoch adding "
    "it).", float, semantic=False)
CLUSTER_AUTOSCALE_QUEUE_LOW = _entry(
    "sdot.cluster.autoscale.queue.low", 0.5,
    "Fleet-mean WLM queued-query depth below which the autoscale hook "
    "signals scale-in (drain and retire one historical via a new "
    "epoch). Must be well under the high mark or the fleet flaps.",
    float, semantic=False)
CLUSTER_AUTOSCALE_COOLDOWN_SECONDS = _entry(
    "sdot.cluster.autoscale.cooldown.seconds", 30.0,
    "Minimum wall-clock spacing between autoscale decisions; epoch "
    "handovers in progress also suppress new signals.",
    float, semantic=False)
# --- deterministic fault injection (fault/) -----------------------------------
FAULT_PLAN = _entry(
    "sdot.fault.plan", "",
    "JSON FaultPlan ({\"seed\": S, \"rules\": [...]}) activating named "
    "injection sites across cluster RPC, persist I/O, the cold tier, "
    "and WLM admission — see docs/CHAOS.md for the site catalog and "
    "rule schema. Empty (default) = every site is a zero-cost no-op. "
    "Injected faults only provoke the recovery paths; strict-mode "
    "answers remain exact, so results stay cacheable.", semantic=False)
# --- out-of-core tiered storage (tier/) ---------------------------------------
TIER_ENABLED = _entry(
    "sdot.tier.enabled", False,
    "Recover datasources as TIERED stores: column bytes stay in the "
    "persist/ snapshot (cold tier) and fault on demand into a "
    "byte-budgeted hot set instead of loading eagerly at boot "
    "(tier/loader.py; requires sdot.persist.path). Consulted ONCE at "
    "recovery — flipping it mid-session changes nothing until the next "
    "Context, so cached results within a session are unaffected; the "
    "wave-composition effects of tiering key off the per-query "
    "sdot.tier.wave.io.bytes (semantic) instead.", semantic=False)
TIER_BUDGET_BYTES = _entry(
    "sdot.tier.budget.bytes", 2 << 30,
    "Byte budget of the hot set (per process — on a cluster historical "
    "this bounds the node's owned-shard residency). Chunks over budget "
    "evict by query-history popularity, oldest-touch first; chunks "
    "pinned by in-flight queries never evict, so peak residency is "
    "budget + in-flight bytes.", int, semantic=False)
TIER_VERIFY_CHECKSUMS = _entry(
    "sdot.tier.verify.checksums", True,
    "Verify each cold blob's CRC32 against the manifest on the FIRST "
    "fault that touches it (recovery itself only checks structure, "
    "keeping boot O(manifest)). A mismatch quarantines the snapshot "
    "version and re-recovers per PERSIST semantics.", semantic=False)
TIER_PREFETCH_ENABLED = _entry(
    "sdot.tier.prefetch.enabled", True,
    "Run the cold-tier prefetcher threads: the wave loop enqueues wave "
    "i+2's chunks while wave i computes on device, hiding cold loads "
    "behind dispatch. Purely a latency optimization — demand faults "
    "serve everything when disabled.", semantic=False)
TIER_PREFETCH_THREADS = _entry(
    "sdot.tier.prefetch.threads", 2,
    "Prefetcher worker threads draining the cold-load queue.",
    int, semantic=False)
TIER_DECODED_CACHE_BYTES = _entry(
    "sdot.tier.decoded.cache.bytes", 128 << 20,
    "Byte budget of the decode-ahead cache: decoded arrays for hot "
    "ENCODED chunks, accounted at DECODED size on top of the encoded "
    "hot set (not against sdot.tier.budget.bytes; combined residency "
    "is budget + decoded cache). The prefetcher decodes into it and "
    "demand faults serve from it, taking decode off the critical path "
    "(counters \"decode_ms_saved\" in stats[\"tier\"]). Decoded "
    "entries evict before any encoded payload. 0 disables decode-"
    "ahead; raw (unencoded) stores are unaffected.", int,
    semantic=False)
TIER_WAVE_IO_BYTES = _entry(
    "sdot.tier.wave.io.bytes", 256 << 20,
    "Per-wave host-I/O byte cap on a tiered scan (the wave planner's "
    "I/O term, parallel/cost.py:tier_io_budget): forces enough waves "
    "that prefetch can overlap loads with compute. 0 disables the "
    "term. Semantic: changes the wave composition and with it float "
    "accumulation order.", int)
# --- compressed columnar encoding (encode/) -----------------------------------
ENCODE_ENABLED = _entry(
    "sdot.encode.enabled", False,
    "Write snapshot column blobs ENCODED (bit-packed dictionary codes, "
    "RLE runs, frame-of-reference+delta time columns — encode/codecs.py) "
    "with a per-column chooser at checkpoint/compaction time. Snapshots "
    "without an encoding block load as raw little-endian unchanged; a "
    "tiered recovery faults encoded bytes, so the hot-set budget holds "
    "compression-ratio x more data. Decoded arrays are bit-identical to "
    "the raw path; the flag is still folded into compile signatures "
    "defensively.", semantic=False)
ENCODE_MIN_RATIO = _entry(
    "sdot.encode.min.ratio", 1.2,
    "Minimum whole-column compression ratio (raw bytes / estimated "
    "encoded bytes) the chooser demands before it encodes a column at "
    "all — below it the column stays raw little-endian (encoding that "
    "barely shrinks only adds decode latency).", float, semantic=False)
ENCODE_RLE_MAX_RUN_FRAC = _entry(
    "sdot.encode.rle.max.run.frac", 0.5,
    "RLE eligibility cutoff: runs/rows above this fraction disqualifies "
    "the RLE candidate outright (near-unique columns degenerate to one "
    "run per row, where RLE is larger than raw).", float, semantic=False)


# Families of runtime-shaped keys (tenant / datasource suffixes) that
# cannot be declared one-by-one with _entry(). This tuple IS the declared
# contract for them: the sdlint contracts pass accepts any read of a key
# under these prefixes, and anything else must be an _entry. Add a prefix
# here (with a pointer to the consuming module) before introducing a new
# dynamic family.
DYNAMIC_KEY_PREFIXES = (
    "sdot.wlm.quota.",          # per-tenant quota grammar (wlm/quota.py)
    "sdot.datasource.option.",  # per-session datasource option overrides
                                # (Config.datasource_option_overrides)
)


class Config:
    """A mutable key-value session config over the registered entries.

    Unknown ``sdot.*`` keys are accepted (forward compatibility), mirroring the
    reference importing every ``spark.sparklinedata.*`` SparkConf key into the
    session conf (``SPLSessionState.scala:90-103``).
    """

    DATASOURCE_OVERRIDE_PREFIX = "sdot.datasource.option."

    def __init__(self, overrides: Optional[Dict[str, Any]] = None):
        self._values: Dict[str, Any] = {}
        if overrides:
            for k, v in overrides.items():
                self.set(k, v)

    def set(self, key: str, value: Any) -> None:
        entry = _REGISTRY.get(key)
        if entry is not None and isinstance(value, str) and not isinstance(entry.default, str):
            value = entry.parse(value)
        self._values[key] = value

    def fingerprint(self) -> tuple:
        """Hashable snapshot of the SEMANTIC overrides — result/plan
        caches key on it so a session config change (timezone, HLL
        precision, ...) can never serve results computed under the old
        settings. Keys declared ``semantic=False`` (admission quotas,
        lane layouts, history sizing) are excluded: they shape scheduling
        and observability, never results, and folding them in would
        invalidate every cache on each operational tuning step. Unknown
        keys are kept — forward compatibility must fail toward
        correctness, not cache retention."""
        out = []
        for k, v in self._values.items():
            e = _REGISTRY.get(k)
            if e is not None and not e.semantic:
                continue
            if k.startswith("sdot.wlm.quota."):
                continue    # dynamic family, admission-only
            out.append((k, repr(v)))
        return tuple(sorted(out))

    def get(self, entry_or_key) -> Any:
        if isinstance(entry_or_key, ConfigEntry):
            return self._values.get(entry_or_key.key, entry_or_key.default)
        entry = _REGISTRY.get(entry_or_key)
        if entry is not None:
            return self._values.get(entry.key, entry.default)
        return self._values.get(entry_or_key)

    def is_set(self, entry_or_key) -> bool:
        """Whether the key was EXPLICITLY set this session (even to its
        default value) — per-backend default resolution (cost.unit_cost)
        must never override an operator's explicit choice."""
        key = entry_or_key.key if isinstance(entry_or_key, ConfigEntry) \
            else entry_or_key
        return key in self._values

    def datasource_option_overrides(self) -> Dict[str, Any]:
        """Per-session overrides of datasource options (tier 3)."""
        p = self.DATASOURCE_OVERRIDE_PREFIX
        return {k[len(p):]: v for k, v in self._values.items() if k.startswith(p)}

    def prefixed(self, prefix: str) -> Dict[str, Any]:
        """Every explicitly-set key under ``prefix`` (free-form config
        families like ``sdot.wlm.quota.<tenant>`` ride the unknown-key
        channel and enumerate themselves this way)."""
        return {k: v for k, v in self._values.items()
                if k.startswith(prefix)}

    def copy(self) -> "Config":
        c = Config()
        c._values = dict(self._values)
        return c

    @staticmethod
    def registry() -> Dict[str, ConfigEntry]:
        return dict(_REGISTRY)
