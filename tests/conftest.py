"""Test fixtures.

Mirrors the reference test strategy (SURVEY.md §4): the reference spins up a
real multi-*process* single-node Druid cluster in the test JVM
(``DruidTestCluster``); our analog is a virtual 8-device CPU mesh in the test
process (``xla_force_host_platform_device_count``), so multi-chip sharding
paths execute for real without TPU hardware. UTC pinning mirrors
``AbstractTest.scala:85-88``.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()
os.environ["TZ"] = "UTC"

import jax  # noqa: E402

# Tests always run on the virtual 8-device CPU mesh, whatever the
# environment says.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# hermetic: the suite compiles fresh and writes nothing into the checkout's
# persistent compile cache (utils/compile_cache.py points every Context at
# it); six xdist workers would otherwise race one directory
jax.config.update("jax_enable_compilation_cache", False)

import dataclasses  # noqa: E402

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pytest  # noqa: E402

from spark_druid_olap_tpu.utils import config as _config  # noqa: E402

# Execution-path tests re-run identical specs across a module-scoped
# engine and assert on per-run engine stats (mode / sharded / dispatch
# counts); a semantic-cache hit would answer without executing and erase
# those stats. Pin the result cache OFF by default for the suite — cache
# semantics get dedicated coverage in test_result_cache.py, which turns
# it back on per-context.
_config._REGISTRY["sdot.cache.enabled"] = dataclasses.replace(
    _config.CACHE_ENABLED, default=False)
_config.CACHE_ENABLED = _config._REGISTRY["sdot.cache.enabled"]


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


def make_sales_df(n=20_000, seed=7) -> pd.DataFrame:
    """Synthetic star-ish flat table: a small TPC-H-shaped sales fact."""
    r = np.random.default_rng(seed)
    start = np.datetime64("2015-01-01")
    days = r.integers(0, 730, n)
    ts = start + days.astype("timedelta64[D]")
    return pd.DataFrame({
        "ts": ts.astype("datetime64[ns]"),
        "region": r.choice(["east", "west", "north", "south"], n),
        "product": r.choice([f"p{i:03d}" for i in range(50)], n),
        "flag": r.choice(["A", "N", "R"], n, p=[0.5, 0.3, 0.2]),
        "status": r.choice(["O", "F"], n),
        "qty": r.integers(1, 51, n).astype(np.int64),
        "price": np.round(r.uniform(1.0, 1000.0, n), 2),
        "discount": np.round(r.uniform(0.0, 0.1, n), 2),
        "due": (ts + r.integers(5, 60, n).astype("timedelta64[D]"))
        .astype("datetime64[ns]"),
    })


@pytest.fixture(scope="session")
def sales_df():
    return make_sales_df()


@pytest.fixture(scope="session")
def sales_ds(sales_df):
    from spark_druid_olap_tpu.segment.ingest import ingest_dataframe
    return ingest_dataframe("sales", sales_df, time_column="ts",
                            target_rows=4096)


@pytest.fixture(scope="session")
def store(sales_ds):
    from spark_druid_olap_tpu.segment.store import SegmentStore
    st = SegmentStore()
    st.register(sales_ds)
    return st


@pytest.fixture(scope="session")
def engine(store):
    from spark_druid_olap_tpu.parallel.executor import QueryEngine
    return QueryEngine(store)


@pytest.fixture(scope="session")
def mesh_engine(store):
    from spark_druid_olap_tpu.parallel.executor import QueryEngine
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    from spark_druid_olap_tpu.utils.config import Config, COST_MODEL_ENABLED
    # cost model off = always-shard (its documented behavior): these fixtures
    # exist to exercise the collective paths even on tiny test data
    cfg = Config({COST_MODEL_ENABLED.key: False})
    return QueryEngine(store, config=cfg, mesh=make_mesh())


def assert_frames_equal(got: pd.DataFrame, want: pd.DataFrame, sort_by=None,
                        rtol=1e-4, atol=1e-6):
    """Differential-test comparator ≈ ``isTwoDataFrameEqual``
    (reference AbstractTest.scala:192-243): sort both, compare column-wise
    with float tolerance."""
    assert sorted(got.columns) == sorted(want.columns), \
        f"columns differ: {list(got.columns)} vs {list(want.columns)}"
    if sort_by is None:
        sort_by = [c for c in want.columns
                   if want[c].dtype == object or
                   str(want[c].dtype).startswith(("datetime", "int", "str"))]
    if sort_by:
        got = got.sort_values(sort_by).reset_index(drop=True)
        want = want.sort_values(sort_by).reset_index(drop=True)
    assert len(got) == len(want), f"row counts {len(got)} vs {len(want)}"
    for c in want.columns:
        g = got[c].to_numpy()
        w = want[c].to_numpy()
        if np.issubdtype(w.dtype, np.floating):
            np.testing.assert_allclose(g.astype(np.float64), w, rtol=rtol,
                                       atol=atol, err_msg=f"column {c}")
        elif np.issubdtype(w.dtype, np.datetime64):
            np.testing.assert_array_equal(
                g.astype("datetime64[ms]"), w.astype("datetime64[ms]"),
                err_msg=f"column {c}")
        elif w.dtype == object:
            # str-normalize BOTH sides so null spellings (None/nan) compare
            np.testing.assert_array_equal(
                pd.Series(g).fillna("<null>").astype(str).to_numpy(),
                pd.Series(w).fillna("<null>").astype(str).to_numpy(),
                err_msg=f"column {c}")
        else:
            np.testing.assert_array_equal(g, w, err_msg=f"column {c}")
