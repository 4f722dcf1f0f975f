"""Session context — the framework's entry point.

≈ the reference's session/extension layer: ``SPLSessionState`` +
``ModuleLoader`` (``SPLSessionState.scala:80-132``,
``SparklineDataModule.scala:70-87``) wire the parser, logical rules, and
physical strategy into a Spark session; here ``Context`` wires the SQL front
end, planner, engine, metadata catalog, and config into one object.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax

from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.result import QueryResult
from spark_druid_olap_tpu.segment.ingest import (
    ingest_csv,
    ingest_dataframe,
    ingest_parquet,
)
from spark_druid_olap_tpu.segment.store import SegmentStore
from spark_druid_olap_tpu.utils.config import Config


def _enable_x64_once():
    # On CPU, native 64-bit routes (i64 sums, f64 compares) are exact and
    # cheap. TPU backends must stay 32-bit (f64 unsupported, i64 emulated):
    # the lane/limb routes carry exactness there. SDOT_FORCE_32BIT=1 keeps
    # 32-bit even on CPU (TPU-dtype simulation/debugging).
    import os
    if os.environ.get("SDOT_FORCE_32BIT"):
        return
    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_x64", True)


class Context:
    def __init__(self, config: Optional[Dict] = None, mesh=None,
                 auto_mesh: bool = False):
        _enable_x64_once()
        from spark_druid_olap_tpu.utils import compile_cache
        compile_cache.configure()
        self.config = Config(config)
        self.store = SegmentStore()
        if mesh is None and len(jax.devices()) > 1:
            from spark_druid_olap_tpu.utils.config import MESH_AUTO
            if auto_mesh or bool(self.config.get(MESH_AUTO)):
                from spark_druid_olap_tpu.parallel.mesh import make_mesh
                mesh = make_mesh()
        self.mesh = mesh
        from spark_druid_olap_tpu.parallel.executor import QueryEngine
        self.engine = QueryEngine(self.store, self.config, mesh)
        from spark_druid_olap_tpu.metadata.catalog import Catalog
        self.catalog = Catalog(self.store)
        from spark_druid_olap_tpu.metadata.history import QueryHistory
        from spark_druid_olap_tpu.utils.config import (QUERY_HISTORY,
                                                       QUERY_HISTORY_SIZE)
        # disabled history keeps the registry but records nothing
        # (maxlen=0 deque): every record() call stays a cheap no-op
        self.history = QueryHistory(
            self.config.get(QUERY_HISTORY_SIZE)
            if self.config.get(QUERY_HISTORY) else 0)
        # named lookup tables for the SQL LOOKUP(col, 'name') function
        # (≈ Druid registered lookups backing the lookup extraction fn)
        self.lookups: Dict[str, Dict[str, Optional[str]]] = {}
        # materialized rollup registry: name -> mv.registry.RollupDef;
        # the planner consults it for automatic rewrite (mv/match.py)
        self.rollups: Dict[str, object] = {}
        # module extension points (≈ SparklineDataModule/ModuleLoader)
        from spark_druid_olap_tpu.utils import host_eval as _he
        self.functions = _he.EXTRA_FUNCTIONS
        self.spec_rules = []
        self.statement_handlers = []
        self.modules = []
        from spark_druid_olap_tpu.utils.config import MODULES
        mods_csv = self.config.get(MODULES)
        if mods_csv:
            from spark_druid_olap_tpu.utils.modules import install_from_config
            self.modules = install_from_config(self, mods_csv)
        # durable persistence (persist/): deep-storage snapshots + ingest
        # WAL + startup recovery; None when sdot.persist.path is unset
        self.persist = None
        from spark_druid_olap_tpu.utils.config import (
            PERSIST_ENABLED, PERSIST_PATH, PERSIST_RECOVER)
        ppath = self.config.get(PERSIST_PATH)
        if ppath and self.config.get(PERSIST_ENABLED):
            from spark_druid_olap_tpu.persist.manager import PersistManager
            self.persist = PersistManager(self, ppath)
            if self.config.get(PERSIST_RECOVER):
                self.persist.recover()
            self.persist.start_background()
        # distributed serving tier (cluster/): a broker attaches the
        # scatter/merge client to its engine; historicals are built by
        # cluster/historical.py (they set sdot.cluster.role=historical
        # and never attach a client — no recursive scatter)
        self.cluster = None
        from spark_druid_olap_tpu.utils.config import (
            CLUSTER_NODES, CLUSTER_ROLE)
        if self.config.get(CLUSTER_NODES) \
                and self.config.get(CLUSTER_ROLE) == "broker":
            from spark_druid_olap_tpu.cluster.broker import ClusterClient
            self.cluster = ClusterClient(self)
            self.engine.cluster = self.cluster

    def reshard(self, devices=None) -> None:
        """Rebuild the engine's device mesh over the currently-live (or
        given) devices — topology elasticity after chip loss/restore
        (≈ the reference re-planning on ZooKeeper server-list changes)."""
        self.engine.reshard(devices)
        self.mesh = self.engine.mesh

    def install_module(self, module) -> None:
        """Install an extension module programmatically (≈ adding to
        spark.sparklinedata.modules)."""
        module.install(self)
        self.modules.append(module)

    def register_lookup(self, name: str, mapping: Dict) -> None:
        """Register a named value-translation map usable as
        ``LOOKUP(col, 'name')`` in SQL (≈ Druid lookup registration)."""
        self.lookups[name] = {str(k): (None if v is None else str(v))
                              for k, v in mapping.items()}

    # -- ingest / registration ------------------------------------------------
    def _ingest_kwargs(self, kwargs):
        """Session default for segment sizing (sdot.segment.target.rows)
        when the caller doesn't pass target_rows explicitly."""
        if "target_rows" not in kwargs:
            from spark_druid_olap_tpu.utils.config import SEGMENT_ROWS
            kwargs = {**kwargs,
                      "target_rows": self.config.get(SEGMENT_ROWS)}
        return kwargs

    def ingest_dataframe(self, name, df, **kwargs):
        ds = ingest_dataframe(name, df, **self._ingest_kwargs(kwargs))
        self.store.register(ds)
        return ds

    def ingest_parquet(self, name, path, **kwargs):
        ds = ingest_parquet(name, path, **self._ingest_kwargs(kwargs))
        self.store.register(ds)
        return ds

    def ingest_csv(self, name, path, **kwargs):
        ds = ingest_csv(name, path, **self._ingest_kwargs(kwargs))
        self.store.register(ds)
        return ds

    def ingest_parquet_stream(self, name, path, **kwargs):
        """Out-of-core Parquet ingest (row-group streaming; see
        segment/stream_ingest.py) — for datasets whose raw pandas form
        would not fit in host memory."""
        from spark_druid_olap_tpu.segment.stream_ingest import (
            ingest_parquet_stream)
        ds = ingest_parquet_stream(name, path, **self._ingest_kwargs(kwargs))
        self.store.register(ds)
        return ds

    def stream_ingest(self, name, df, **kwargs):
        """Streaming append (≈ Druid realtime ingest): create the
        datasource on the first batch, append rows after. With
        persistence on (sdot.persist.path) each batch is journaled to
        the write-ahead log and fsynced BEFORE it becomes queryable, so
        a committed batch survives kill -9 (persist/wal.py). Returns the
        new immutable Datasource value.

        When an ``ingest`` WLM lane is configured, each batch takes a
        lane slot for its local apply — producers share the same
        admission fabric as queries instead of starving them. On a
        broker, an acked batch is additionally pushed to the
        time-matched shard's owners (cluster/broker.py) so distributed
        reads keep read-your-writes; the push is an optimization, never
        part of the durability or ACK path."""
        kwargs = self._ingest_kwargs(kwargs)
        wlm = getattr(self.engine, "wlm", None)
        ticket = wlm.admit_ingest() if wlm is not None else None
        cl = self.cluster
        token = cl.ingest_begin(name) if cl is not None else None
        acked_df = None
        try:
            if self.persist is not None:
                ds = self.persist.stream_ingest(name, df, kwargs)
            else:
                from spark_druid_olap_tpu.segment.append import (
                    apply_stream_ingest)
                ds = apply_stream_ingest(self, name, df, kwargs)
            acked_df = df
            return ds
        finally:
            if token is not None:
                cl.ingest_finish(token, name, acked_df, kwargs)
            if ticket is not None:
                wlm.release(ticket)

    def checkpoint(self, name: Optional[str] = None):
        """Publish snapshot(s) to deep storage (requires
        sdot.persist.path). ``name=None`` checkpoints every complete
        datasource. Returns the checkpoint summaries."""
        if self.persist is None:
            raise RuntimeError(
                "persistence is disabled; set sdot.persist.path")
        if name is not None:
            return [self.persist.checkpoint(name)]
        return self.persist.checkpoint_all()

    def close(self) -> None:
        """Stop background machinery (the persist checkpointer, the
        cluster client's prober + scatter pool). Safe to call more than
        once; the context remains usable for queries."""
        if self.persist is not None:
            self.persist.stop()
        if self.cluster is not None:
            self.cluster.close()
            self.cluster = None
            self.engine.cluster = None

    def register_star_schema(self, star_schema) -> None:
        self.catalog.register_star_schema(star_schema)

    # -- query ----------------------------------------------------------------
    def execute(self, q: S.QuerySpec) -> QueryResult:
        """Execute a raw engine QuerySpec (≈ ``ON DRUIDDATASOURCE ... EXECUTE
        QUERY <json>``, reference ``PlanUtil.logicalPlan:49-66``)."""
        r = self.engine.execute(q)
        self.history.record(q, dict(self.engine.last_stats))
        return r

    def sql(self, query: str, query_id: Optional[str] = None,
            lane: Optional[str] = None, tenant: Optional[str] = None,
            priority: Optional[int] = None) -> QueryResult:
        try:
            from spark_druid_olap_tpu.sql.session import run_sql
        except ImportError as e:
            raise NotImplementedError(
                "SQL front end not available in this build") from e
        return run_sql(self, query, query_id=query_id, lane=lane,
                       tenant=tenant, priority=priority)

    def explain(self, query: str) -> str:
        try:
            from spark_druid_olap_tpu.sql.session import explain_sql
        except ImportError as e:
            raise NotImplementedError(
                "SQL front end not available in this build") from e
        return explain_sql(self, query)
