"""Device-loss resilience.

When the backend dies mid-session (the chip drops off the host),
statements must keep producing CORRECT results through the host tier,
the loss must be surfaced in stats, and the engine must re-attach on a
later statement once the device answers again. ≈ the reference's
ZK-watch metadata invalidation re-planning against live servers
(CuratorConnection.scala:77-136).
"""

import jax
import numpy as np
import pandas as pd
import pytest

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.parallel.executor import (QueryEngine,
                                                    _is_backend_loss)


@pytest.fixture()
def ctx():
    rng = np.random.default_rng(8)
    n = 20_000
    df = pd.DataFrame({
        "ts": (np.datetime64("2021-01-01")
               + rng.integers(0, 100, n).astype("timedelta64[D]"))
        .astype("datetime64[ns]"),
        "region": rng.choice(["east", "west", "north", "south"], n),
        "qty": rng.integers(1, 100, n).astype(np.int64),
    })
    c = sdot.Context({"sdot.engine.backend.retry.seconds": 3600.0})
    c.ingest_dataframe("sales", df, time_column="ts")
    c._test_df = df
    return c


SQL = ("select region, sum(qty) as s, count(*) as n from sales "
       "group by region order by region")


def _want(df):
    return df.groupby("region").agg(s=("qty", "sum"),
                                    n=("qty", "size")).reset_index()


def _check(got, df):
    want = _want(df)
    assert got["s"].tolist() == want["s"].tolist()
    assert got["n"].tolist() == want["n"].tolist()


def test_backend_loss_demotes_then_reattaches(ctx, monkeypatch):
    df = ctx._test_df
    # 1. healthy: engine mode
    _check(ctx.sql(SQL).to_pandas(), df)
    assert ctx.history.entries()[-1].stats["mode"] == "engine"

    # 2. kill the (fake) backend: every array bind raises a lost
    #    device's terminal error
    orig = QueryEngine._bind_arrays

    def dead(self, *a, **k):
        raise jax.errors.JaxRuntimeError(
            "UNAVAILABLE: TPU backend connection lost mid-session")

    monkeypatch.setattr(QueryEngine, "_bind_arrays", dead)
    got = ctx.sql(SQL).to_pandas()
    _check(got, df)                       # correct results continue
    st = ctx.history.entries()[-1].stats
    assert st["mode"].startswith("host (backend_lost"), st["mode"]

    # 3. still down, within cooldown: statements skip the device without
    #    touching it (no new dispatch attempts against a dead backend)
    calls = []
    monkeypatch.setattr(QueryEngine, "_bind_arrays",
                        lambda self, *a, **k: calls.append(1) or dead(self))
    got = ctx.sql(SQL).to_pandas()
    _check(got, df)
    assert ctx.history.entries()[-1].stats["mode"] \
        .startswith("host (backend_lost")
    assert not calls, "cooldown must prevent re-dispatch to a dead backend"

    # 4. backend returns + cooldown elapses: the probe re-attaches and
    #    the next statement runs engine-mode again (device caches were
    #    invalidated at loss, so arrays re-upload)
    monkeypatch.setattr(QueryEngine, "_bind_arrays", orig)
    ctx.engine._backend_retry_at = 0.0
    got = ctx.sql(SQL).to_pandas()
    _check(got, df)
    assert ctx.history.entries()[-1].stats["mode"] == "engine"


def test_backend_loss_classifier():
    assert _is_backend_loss(jax.errors.JaxRuntimeError(
        "UNAVAILABLE: failed to connect to all addresses"))
    assert _is_backend_loss(RuntimeError("DEADLINE_EXCEEDED: dispatch"))
    assert _is_backend_loss(OSError("Socket closed"))
    assert not _is_backend_loss(ValueError("UNAVAILABLE"))   # wrong type
    assert not _is_backend_loss(RuntimeError("shape mismatch [4] vs [8]"))
    # a compiler's refusal is never device loss, whatever its text says
    from spark_druid_olap_tpu.ops.pallas_wave import WaveCompileError
    assert not _is_backend_loss(WaveCompileError(
        "refused by the tpu compiler: transport layout unavailable"))
    assert not _is_backend_loss(jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: Invalid relayout "
        "(connection of vector layouts)"))
    assert not _is_backend_loss(RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space vmem ... transport"))


def test_backend_loss_on_sharded_mesh(monkeypatch):
    """Loss during mesh execution demotes and recovers the same way."""
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    rng = np.random.default_rng(3)
    n = 10_000
    df = pd.DataFrame({
        "ts": np.repeat(np.datetime64("2021-01-01"), n)
        .astype("datetime64[ns]"),
        "region": rng.choice(["a", "b", "c"], n),
        "qty": rng.integers(1, 50, n).astype(np.int64),
    })
    ctx = sdot.Context({"sdot.querycostmodel.enabled": False,
                        "sdot.engine.backend.retry.seconds": 3600.0},
                       mesh=make_mesh())
    ctx.ingest_dataframe("m", df, time_column="ts")
    sql = "select region, sum(qty) as s from m group by region order by region"
    want = df.groupby("region")["qty"].sum().tolist()
    assert ctx.sql(sql).to_pandas()["s"].tolist() == want
    assert ctx.history.entries()[-1].stats.get("sharded") is True

    orig = QueryEngine._bind_arrays

    def dead(self, *a, **k):
        raise jax.errors.JaxRuntimeError("UNAVAILABLE: ICI link down")

    monkeypatch.setattr(QueryEngine, "_bind_arrays", dead)
    assert ctx.sql(sql).to_pandas()["s"].tolist() == want
    assert ctx.history.entries()[-1].stats["mode"] \
        .startswith("host (backend_lost")
    monkeypatch.setattr(QueryEngine, "_bind_arrays", orig)
    ctx.engine._backend_retry_at = 0.0
    assert ctx.sql(sql).to_pandas()["s"].tolist() == want
    assert ctx.history.entries()[-1].stats["mode"] == "engine"
