"""Round-3 advisor findings, regression-locked (the advisor's file,
ADVICE.md, was deleted by PR 30; the findings live on as these tests).

1. (retired with the probe tooling it covered, PR 22)
2. low — EXPLAIN's late-materialization line is labelled an estimate
   (the execution-time decision additionally sees routes/sharding).
3. low — the staged-filter split and int_set_membership share ONE
   "lowers to a compare chain?" predicate: large near-contiguous sets
   are NOT staged; small scattered sets ARE.
4. low — the per-datasource pattern-selectivity cache is a bounded LRU.
5. low — negative plan-cache entries are a dedicated type, never a
   structural tuple sentinel.
"""

import numpy as np
import pandas as pd
import pytest

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.ir import expr as E
from spark_druid_olap_tpu.ir import spec as S
from spark_druid_olap_tpu.ops import expr_compile as EC
from spark_druid_olap_tpu.parallel import cost as C
from spark_druid_olap_tpu.parallel.executor import QueryEngine


# -- 3. shared chain-lowering predicate --------------------------------------

def _staged(vals) -> bool:
    f = S.InFilter("x", E.FrozenIntSet(np.asarray(sorted(vals), np.int64)))
    cheap, exp = QueryEngine._split_filter_staged(f)
    return exp is not None


def test_staged_split_matches_chain_lowering():
    # large but near-contiguous: one run -> compare chain -> NOT staged
    contiguous = list(range(1000, 1200))
    assert EC.int_set_lowers_to_chain(np.asarray(contiguous, np.int64))
    assert not _staged(contiguous)
    # small but scattered (30 singleton runs > _CHAIN_MAX_RANGES, span
    # 30x the count): lowers as a gather -> IS staged
    scattered = [i * 1000 for i in range(30)]
    assert not EC.int_set_lowers_to_chain(np.asarray(scattered, np.int64))
    assert _staged(scattered)
    # tiny scattered set (<= 24 runs): chain again -> NOT staged
    tiny = [i * 1000 for i in range(20)]
    assert EC.int_set_lowers_to_chain(np.asarray(tiny, np.int64))
    assert not _staged(tiny)


def test_chain_predicate_agrees_with_membership_lowering():
    """int_set_runs is the single source of truth: when it yields runs,
    membership compiles without any gather (verified by lowering to HLO
    and asserting no gather/while appears)."""
    import jax

    vals = np.asarray(list(range(100, 400)), np.int64)  # one dense run
    assert EC.int_set_lowers_to_chain(vals)

    def f(x):
        return EC.int_set_membership(x, vals)

    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((128,), np.int32)).as_text()
    assert "gather" not in txt and "while" not in txt


# -- 2 + 4. pattern cache bound / explain estimate label ---------------------

def test_pattern_frac_cache_is_bounded():
    df = pd.DataFrame({
        "d": pd.Series(["apple", "banana", "cherry", "date"] * 4,
                       dtype="object"),
        "m": np.arange(16.0),
    })
    ctx = sdot.Context()
    ds = ctx.ingest_dataframe("pat", df)
    for i in range(C._PATTERN_FRAC_BOUND + 50):
        f = S.PatternFilter("d", "contains", f"pfx{i}")
        C._pattern_fraction(f, ds)
    assert len(ds._pattern_frac_cache) <= C._PATTERN_FRAC_BOUND
    # hot entries survive: re-touch one, insert more, it stays
    f0 = S.PatternFilter("d", "contains", "apple")
    C._pattern_fraction(f0, ds)
    for i in range(C._PATTERN_FRAC_BOUND - 1):
        C._pattern_fraction(S.PatternFilter("d", "contains", f"z{i}"), ds)
    assert ("d", "contains", "apple") in ds._pattern_frac_cache


def test_explain_compaction_line_is_estimate():
    rng = np.random.default_rng(0)
    n = 40_000
    df = pd.DataFrame({
        "k": rng.integers(0, 50, n).astype(str),
        "sel": rng.integers(0, 100, n),
        "v": rng.normal(size=n),
    })
    ctx = sdot.Context(config={"sdot.engine.scan.compact.min.rows": 0})
    ctx.ingest_dataframe("exp_est", df)
    txt = ctx.explain(
        "select k, sum(v) from exp_est where sel < 3 group by k")
    if "late-materialize" in txt:
        assert "(estimate)" in txt


# -- 5. negative plan-cache entries are a dedicated type ---------------------

def test_negative_plan_entry_not_tuple_sentinel():
    from spark_druid_olap_tpu.planner import host_exec
    from spark_druid_olap_tpu.sql.session import _NegativePlan

    df = pd.DataFrame({"k": ["a", "b"], "v": [1.0, 2.0]})
    ctx = sdot.Context()
    ctx.ingest_dataframe("neg", df)
    # a statement the builder deterministically rejects: a session
    # Python UDF has no device compilation path (a plain equi self-join
    # now runs ENGINE mode via the round-5 disambiguation + composite
    # pushdown, so it no longer demotes)
    ctx.functions["negfn"] = lambda a, b: float(a) + float(b)
    sql = ("select k, count(*) as n from neg where negfn(v, v) > 0 "
           "group by k order by k")
    r1 = ctx.sql(sql)
    assert ctx.history.entries()[-1].stats["mode"].startswith("host")
    plan_cache = getattr(ctx, "_result_cache", {}).get("plan", {})
    negs = [v for v in plan_cache.values() if isinstance(v, _NegativePlan)]
    tuples = [v for v in plan_cache.values() if isinstance(v, tuple)]
    assert negs, "expected a negative plan-cache entry"
    assert not tuples, "bare-tuple sentinel must be gone"
    # second run hits the negative entry and still answers identically
    r2 = ctx.sql(sql)
    assert r1.to_pandas().equals(r2.to_pandas())
