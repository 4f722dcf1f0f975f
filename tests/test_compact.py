"""Late materialization (compact-then-aggregate) correctness.

The scan programs evaluate the filter on the full arrays, sort surviving
row positions to a static prefix, and run group-key building / value
derivation / aggregation at O(survivors) (executor._plan_compact_m,
CompactScanContext). These tests force the path at test scale via
`sdot.engine.scan.compact.min.rows` and diff against the uncompacted
engine: identical results, including the re-run when a budget is too
small for its survivors.

The budget is the shape's, not the statement's (executor._run_budgeted):
a compacting program reports how many rows survived, the shape remembers
the most it has seen, and the next statement of that shape — whatever
its literal values — is planned from that count; the independence
estimate serves only the first sight, and where one chip runs one wave
even there a filter-only count stands in for the estimate's program.

The survivors' arrays reach the prefix in one of two forms, chosen from
static shapes and the backend's unit costs (ops.scan.carries_by_sort):
as payloads of the compaction sort, or by a gather each. The CPU's
constants always gather, so the cases below pin each form through
`sdot.querycostmodel.gather.seconds.per.probe` — a constant only this
stage and the HLL registers' form rule read, and the latter scatters on
the CPU whatever a probe costs — and hold the two forms to bit-equal
answers.
"""

import numpy as np
import pandas as pd
import pytest

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.parallel.executor import _budget_for


def _df(n=6000, seed=7):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "ts": pd.Timestamp("2020-01-01")
        + pd.to_timedelta(rng.integers(0, 90, n), unit="D"),
        "region": rng.choice(["east", "west", "north", "south"], n),
        "sku": rng.choice([f"sku{i:03d}" for i in range(50)], n),
        "qty": rng.integers(0, 100, n),
        "price": np.round(rng.random(n) * 50, 2),
    })


FORMS = ("sort", "gather")
PROBE_KEY = "sdot.querycostmodel.gather.seconds.per.probe"


def _pin(c, form):
    """``form`` None: no compaction; "sort" / "gather": compaction at
    test scale in that form (a probe priced at a second makes every
    payload cheaper, one priced at nothing makes every gather cheaper)."""
    c.config.set("sdot.engine.scan.compact", form is not None)
    if form is not None:
        c.config.set("sdot.engine.scan.compact.min.rows", 0)
        c.config.set(PROBE_KEY, {"sort": 1.0, "gather": 1e-15}[form])
    return c


def _ctx(compact, df=None):
    """``compact``: False / True (the backend's own form) or a form."""
    c = sdot.Context()
    if compact in FORMS:
        _pin(c, compact)
    else:
        c.config.set("sdot.engine.scan.compact", compact)
        if compact:
            c.config.set("sdot.engine.scan.compact.min.rows", 0)
    c.ingest_dataframe("sales", _df() if df is None else df,
                       time_column="ts", target_rows=1024)
    return c


QUERIES = [
    # selective selector filter -> small-K dense groupby
    "select region, sum(qty) as s, count(*) as n from sales "
    "where sku = 'sku007' group by region order by region",
    # IN filter + expression agg
    "select region, sum(qty * 2) as s2 from sales "
    "where sku in ('sku001','sku002','sku003') group by region "
    "order by region",
    # filtered global aggregate incl. min/max/avg
    "select min(qty) as mn, max(qty) as mx, avg(price) as ap, "
    "count(*) as n from sales where sku = 'sku042'",
    # time-bucketed groupby under a selective filter
    "select date_trunc('month', ts) as m, sum(qty) as s from sales "
    "where region = 'east' and sku = 'sku010' group by 1 order by 1",
    # ordered limit (device top-k epilogue) under compaction
    "select sku, sum(qty) as s from sales where region = 'west' "
    "group by sku order by s desc limit 5",
]


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_compacted_matches_uncompacted(qi):
    sql = QUERIES[qi]
    a = _ctx(True).sql(sql).to_pandas()
    b = _ctx(False).sql(sql).to_pandas()
    pd.testing.assert_frame_equal(a, b, check_dtype=False, atol=1e-6)


def test_compaction_engaged_and_stats():
    c = _ctx(True)
    c.sql("select region, sum(qty) as s from sales where sku = 'sku007' "
          "group by region")
    st = c.history.entries()[-1].stats
    assert st["mode"] == "engine"
    assert st.get("compact_m", 0) > 0


# one shape, its values drawn: qty < 1 keeps ~1 % of the rows, < 8 ~8 %
DRAW_SQL = ("select region, count(*) as n, sum(price) as p from sales "
            "where qty < {} group by region order by region")


def _send(c, sql):
    got = c.sql(sql).to_pandas()
    return got, dict(c.history.entries()[-1].stats)


def _draw_ctx():
    c = _ctx(True)
    c.config.set("sdot.cache.enabled", False)
    return c


def _compacting_programs(c):
    """Scan programs built under a budget so far (the budget's place in
    a signature: last in the dense tier's, third from last in the
    hashed tier's)."""
    late = {"agg": -1, "hashagg": -3}
    return [sig for sig in c.engine._programs
            if sig[0] in late and sig[late[sig[0]]]]


@pytest.mark.parametrize("waves", ["one_wave", "several_waves"])
def test_second_run_plans_from_the_first_count(waves):
    """A compacting run reports its survivors (``compact_live``) and the
    next run of the shape is planned from them (``compact_from``). One
    chip, one wave: the FIRST sight already is — a filter-only count
    (a dispatch of its own, once a shape) stands in for the estimate's
    program, which is never built. Several waves observe with the
    estimate's program."""
    if waves == "one_wave":
        c, sql = _draw_ctx(), DRAW_SQL.format(3)
    else:
        c, sql = _wave_ctx(True), WAVE_SQL
        c.config.set("sdot.cache.enabled", False)
    _, first = _send(c, sql)
    assert first["compact_m"] > 0 and 0 < first["compact_live"]
    assert first["compact_live"] <= first["compact_m"]
    if waves == "one_wave":
        assert first["compact_from"] == "observed"
        assert first["n_dispatch"] == 2
    else:
        assert first["compact_from"] == "estimate"
        assert first["waves"] > 1
    (n_live, m), = c.engine._compact_seen.values()
    assert n_live == first["compact_live"]
    _, second = _send(c, sql)
    assert second["compact_from"] == "observed"
    assert second["compact_live"] == first["compact_live"]
    assert second["compact_m"] == m
    assert m in (first["compact_m"], _budget_for(n_live))
    assert second["n_dispatch"] == second["waves"]
    assert len(_compacting_programs(c)) == 1 + (m != first["compact_m"])


def test_draws_of_one_shape_share_the_entry_and_one_program():
    """The entry is keyed by the program's signature without its budget:
    no text, no literal value."""
    c, ref = _draw_ctx(), _ctx(False)
    records = []
    for v in (3, 2, 4):
        got, st = _send(c, DRAW_SQL.format(v))
        pd.testing.assert_frame_equal(
            got, ref.sql(DRAW_SQL.format(v)).to_pandas(),
            check_dtype=False, atol=1e-6)
        records.append(st)
    assert len(c.engine._compact_seen) == 1
    assert len(_compacting_programs(c)) == 1
    assert [st["program"]["built"] for st in records] == [True, False, False]
    assert len({st["program"]["sig"] for st in records}) == 1
    assert len({st["compact_m"] for st in records}) == 1
    assert [st["compact_from"] for st in records] == ["observed"] * 3
    (n_live, _), = c.engine._compact_seen.values()
    assert n_live == max(st["compact_live"] for st in records)


@pytest.mark.parametrize("draws", [(4, 7, 4), (7, 2, 7), (7, 1, 5, 3, 7)])
def test_budget_that_held_is_kept(draws):
    """Sticky: a count that fits the budget in force re-plans nothing, so
    draws that straddle a power of two (qty < 4 asks for 512 rows,
    qty < 7 for 1024, qty < 2 for 256) do not flip the shape between
    programs — nor does a draw far under the most the shape has seen."""
    c = _draw_ctx()
    seen = [_send(c, DRAW_SQL.format(v))[1] for v in draws]
    m = seen[0]["compact_m"]
    assert {_budget_for(st["compact_live"]) for st in seen} != {m}
    for st in seen:
        assert st["compact_m"] == m and "compact_overflow" not in st
        assert st["compact_live"] <= m
    assert len(_compacting_programs(c)) == 1
    assert [st["program"]["built"] for st in seen[1:]] \
        == [False] * (len(draws) - 1)


def test_quarter_full_budget_is_sized_anew_once(monkeypatch):
    """A count a quarter of the budget would hold re-plans (the first
    sight's case on the chip: q3's estimate asks for 2^20, its count for
    2^16) — downwards once, on the largest count seen, never after."""
    from spark_druid_olap_tpu.parallel import cost as C
    monkeypatch.setattr(C, "_filter_selectivity", lambda f, ds: 0.1)
    c = _wave_ctx(True)
    c.config.set("sdot.cache.enabled", False)
    _, first = _send(c, WAVE_SQL)             # several waves: the estimate's
    assert first["compact_from"] == "estimate"
    assert _budget_for(first["compact_live"]) * 4 <= first["compact_m"]
    for _ in range(2):
        _, st = _send(c, WAVE_SQL)
        assert st["compact_m"] == _budget_for(first["compact_live"])
        assert st["compact_from"] == "observed"
    assert len(_compacting_programs(c)) == 2


def test_concurrent_statements_lose_no_count():
    """Statements run in parallel and report to one shape: the entry
    ends at the largest count any of them saw, under a budget that holds
    it (a lost update would leave a smaller count, or a budget sized for
    one)."""
    import os
    import sys
    import threading
    eng = sdot.Context().engine
    counts = [int(c) for c in
              np.random.default_rng(17).integers(1, 50_000, 4000)]
    workers = 2 * (os.cpu_count() or 4)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda part: [eng._note_survivors("shape", 1 << 20, n)
                                 for n in part],
            args=(counts[i::workers],)) for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    n_live, m = eng._compact_seen["shape"]
    assert n_live == max(counts)
    assert n_live <= m <= 4 * _budget_for(n_live)


def _overflow_one_wave(monkeypatch):
    c = _draw_ctx()
    _, small = _send(c, DRAW_SQL.format(1))   # the shape learns ~1 %
    return c, _ctx(False), DRAW_SQL.format(8), small["compact_m"]


def _overflow_several_waves(monkeypatch):
    from spark_druid_olap_tpu.parallel import cost as C
    monkeypatch.setattr(C, "_filter_selectivity", lambda f, ds: 1e-6)
    c = _wave_ctx(True)
    c.config.set("sdot.cache.enabled", False)
    return c, _wave_ctx(False), WAVE_SQL, 64


def _overflow_to_uncompacted(monkeypatch):
    c = _draw_ctx()
    _, small = _send(c, DRAW_SQL.format(1))
    return c, _ctx(False), DRAW_SQL.format(1000), small["compact_m"]


@pytest.mark.parametrize("case", ["one_wave", "several_waves",
                                  "to_uncompacted"])
def test_overflow_reruns_at_the_counted_size_then_remembers(case,
                                                            monkeypatch):
    """A budget its survivors exceed must not produce wrong results: the
    '__live__' channel says how many there were, the statement is run
    again under the budget that count asks for — twice the count, or
    uncompacted where nothing would be removed — and answers as the
    uncompacted engine does, bit for bit. A per-wave budget that lies
    (estimate ~0 survivors) aborts the compacted wave run and re-runs
    the whole scan. The SHAPE remembers: the next send goes straight to
    that size, whose program is built already."""
    c, ref, sql, m0 = {"one_wave": _overflow_one_wave,
                       "several_waves": _overflow_several_waves,
                       "to_uncompacted": _overflow_to_uncompacted}[case](
        monkeypatch)
    got, st = _send(c, sql)
    want = ref.sql(sql).to_pandas()
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    assert (st.get("waves", 1) > 1) == (case == "several_waves"), st
    assert st["compact_overflow"] > 0
    assert st["program"]["built"] is True
    if case == "to_uncompacted":
        assert "compact_m" not in st and "compact_live" not in st
    else:
        assert st["compact_m"] >= _budget_for(st["compact_live"]) > m0
        assert st["compact_from"] == "observed"
    if case == "one_wave":
        assert st["compact_overflow"] == st["compact_live"] - m0
        assert st["compact_m"] == _budget_for(st["compact_live"])
    again, st2 = _send(c, sql)
    pd.testing.assert_frame_equal(again, want, check_exact=True)
    assert "compact_overflow" not in st2
    assert st2["program"]["built"] is False
    assert st2.get("compact_m") == st.get("compact_m")
    assert st2["n_dispatch"] == st2.get("waves", 1)


def test_never_compacting_statement_keeps_its_program(monkeypatch):
    """A dense statement the gate never lets compact reports no count,
    so it stays on the estimate and on the program it has: its signature
    is the one without a budget, with and without an entry for its
    shape."""
    c = sdot.Context()                       # the gate's own min.rows
    c.config.set("sdot.cache.enabled", False)
    c.ingest_dataframe("sales", _df(), time_column="ts", target_rows=1024)
    sql = DRAW_SQL.format(3)
    shapes, shape_of = [], c.engine._compact_shape
    monkeypatch.setattr(
        c.engine, "_compact_shape",
        lambda *a: shapes.append(shape_of(*a)) or shapes[-1])
    _, first = _send(c, sql)
    assert "compact_m" not in first and "compact_from" not in first
    assert not c.engine._compact_seen
    sig, = c.engine._programs
    assert sig[0] == "agg" and sig[-1] is None
    # an entry under its shape, as if a compacting run had reported
    c.engine._note_survivors(shapes[-1], 128, 100)
    _, second = _send(c, sql)
    assert second["program"] == {**first["program"], "built": False}
    assert "compact_m" not in second
    assert list(c.engine._programs) == [sig]


def test_staged_expensive_membership_matches():
    """A large integer IN-set (gather-lowered membership) is staged
    after compaction; results must match the uncompacted engine."""
    import numpy as np
    rng = np.random.default_rng(11)
    keys = sorted(rng.choice(5000, 60, replace=False).tolist())
    inlist = ", ".join(str(k) for k in keys)
    sql = (f"select region, count(*) as n, sum(qty) as s from sales "
           f"where sku = 'sku007' and qty * 100 + 1 in ({inlist}) "
           f"group by region order by region")
    a = _ctx(True).sql(sql).to_pandas()
    b = _ctx(False).sql(sql).to_pandas()
    pd.testing.assert_frame_equal(a, b, check_dtype=False)


def test_hashed_tier_compaction_matches():
    """High-cardinality (hashed-tier) group-by under a selective filter:
    late materialization engages and matches the uncompacted engine."""
    c1 = _ctx(True)
    c1.config.set("sdot.engine.groupby.dense.max.keys", 8)  # force hashed
    c2 = _ctx(False)
    c2.config.set("sdot.engine.groupby.dense.max.keys", 8)
    sql = ("select sku, sum(qty) as s, count(*) as n from sales "
           "where region = 'east' and qty = 7 "
           "group by sku order by sku limit 30")
    a = c1.sql(sql).to_pandas()
    b = c2.sql(sql).to_pandas()
    pd.testing.assert_frame_equal(a, b, check_dtype=False)
    st = c1.history.entries()[-1].stats
    assert st.get("hashed")
    assert st.get("compact_m", 0) > 0 or st.get("compact_overflow", 0) > 0


@pytest.mark.parametrize("sortedrun", ["on", "off"])
def test_hashed_table_follows_the_budget(sortedrun):
    """At most ``compact_m`` rows reach the table, so it is no wider than
    ``initial_slots(compact_m)`` — where the uncompacted statement sizes
    it from min(key space, selected rows) — and the answers are equal."""
    from spark_druid_olap_tpu.ops import hash_groupby as H
    df = _df(60_000, seed=3)
    df["sku"] = np.random.default_rng(3).integers(0, 40_000, len(df)) \
        .astype(str)
    sql = ("select sku, sum(qty) as s, count(*) as n from sales "
           "where qty = 7 group by sku order by s desc, sku limit 20")
    frames, records = [], []
    for compact in (True, False):
        c = _ctx(compact, df)
        _hashed(sortedrun)(c, None)
        frames.append(c.sql(sql).to_pandas())
        records.append(dict(c.history.entries()[-1].stats))
    pd.testing.assert_frame_equal(*frames, check_exact=True)
    st, plain = records
    assert st["hashed"] and plain["hashed"] and "compact_m" not in plain
    assert st["hash_rows"] == st["compact_m"] >= st["compact_live"] > 0
    assert st["hash_slots"] <= H.initial_slots(st["compact_m"])
    assert st["hash_slots"] < plain["hash_slots"]


def test_sketches_under_compaction_match():
    """HLL / theta count-distinct registers build from the compacted
    context; estimates must track the uncompacted engine exactly (same
    register contents, not just within sketch error)."""
    sql = ("select region, approx_count_distinct(sku) as d from sales "
           "where sku in ('sku001','sku002','sku003','sku004','sku005') "
           "group by region order by region")
    a = _ctx(True).sql(sql).to_pandas()
    b = _ctx(False).sql(sql).to_pandas()
    pd.testing.assert_frame_equal(a, b, check_dtype=False)


def test_compaction_all_rows_filtered_out():
    """A filter matching zero rows under compaction: empty result (or
    the global identity row), not garbage from the padded prefix."""
    c = _ctx(True)
    r = c.sql("select region, sum(qty) as s from sales "
              "where sku = 'sku001' and qty > 1000000 group by region")
    assert len(r) == 0
    g = c.sql("select count(*) as n, sum(qty) as s from sales "
              "where sku = 'sku001' and qty > 1000000").to_pandas()
    assert int(g["n"][0]) == 0


def test_sharded_compaction_matches(eight_device_mesh=None):
    """Per-shard late materialization on the 8-device mesh: results
    match single-device, and a shard-local overflow retries globally."""
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    df = _df(12000)
    mesh_ctx = sdot.Context(mesh=make_mesh())
    mesh_ctx.config.set("sdot.engine.scan.compact.min.rows", 0)
    mesh_ctx.ingest_dataframe("sales", df, time_column="ts",
                              target_rows=1024)
    plain = sdot.Context()
    plain.config.set("sdot.engine.scan.compact", False)
    plain.ingest_dataframe("sales", df, time_column="ts",
                           target_rows=1024)
    sql = ("select region, sum(qty) as s, count(*) as n from sales "
           "where sku = 'sku007' group by region order by region")
    import dataclasses as _dc
    from spark_druid_olap_tpu.ir import spec as S
    # force the sharded path via query context
    from spark_druid_olap_tpu.planner import builder as B
    from spark_druid_olap_tpu.sql.parser import parse_select
    pq = B.build(mesh_ctx, parse_select(sql))
    q = pq.specs[0]
    q = _dc.replace(q, context=_dc.replace(
        q.context or S.QueryContext(), prefer_sharded=True))
    r = mesh_ctx.engine.execute(q).to_pandas()
    st = dict(mesh_ctx.engine.last_stats)
    assert st["sharded"] is True
    want = plain.sql(sql).to_pandas()
    got = r.sort_values("region").reset_index(drop=True)[want.columns]
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    assert st.get("compact_m", 0) > 0 or st.get("compact_overflow", 0) > 0


# -- wave-mode late materialization (VERDICT r3 item 9) -----------------------

def _wave_ctx(compact: bool, n=60_000):
    rng = np.random.default_rng(13)
    df = pd.DataFrame({
        "region": rng.choice(["east", "west", "north", "south"], n),
        "sku": rng.choice([f"sku{i:03d}" for i in range(50)], n),
        "qty": rng.integers(0, 100, n),
        "price": np.round(rng.random(n) * 50, 2),
    })
    c = sdot.Context()
    c.config.set("sdot.engine.scan.compact", compact)
    if compact:
        c.config.set("sdot.engine.scan.compact.min.rows", 0)
    # tiny per-wave byte budget -> multiple waves at test scale
    c.config.set("sdot.engine.wave.max.bytes", 1 << 18)
    c.ingest_dataframe("wsales", df, target_rows=4096)
    return c


WAVE_SQL = ("select region, sum(qty) as s, min(price) as mn, "
            "count(*) as n from wsales where sku = 'sku007' "
            "group by region order by region")


def test_wave_mode_compaction_matches():
    a_ctx = _wave_ctx(True)
    a = a_ctx.sql(WAVE_SQL).to_pandas()
    st = a_ctx.history.entries()[-1].stats
    assert st["mode"] == "engine"
    assert st.get("waves", 1) > 1, f"wave mode not engaged: {st}"
    assert st.get("compact_m", 0) > 0, \
        f"compaction not engaged in wave mode: {st}"
    b = _wave_ctx(False).sql(WAVE_SQL).to_pandas()
    pd.testing.assert_frame_equal(a, b, check_dtype=False, atol=1e-6)


# -- the two forms of late materialization ------------------------------------
#
# Every compacting shape above, in both forms: each form against the
# uncompacted program, the two forms against each other bit for bit, and
# the record naming the form that ran and how many arrays it brought.

def _sql_case(sql, prepare=None):
    def run(form, monkeypatch):
        c = _ctx(form or False)
        if prepare is not None:
            prepare(c, monkeypatch)
        got = c.sql(sql).to_pandas()
        return got, dict(c.history.entries()[-1].stats)
    return run


def _hashed(sortedrun):
    def prepare(c, monkeypatch):
        c.config.set("sdot.engine.groupby.dense.max.keys", 8)
        c.config.set("sdot.engine.groupby.hash.sortedrun", sortedrun)
    return prepare


def _lying_estimate(c, monkeypatch):
    from spark_druid_olap_tpu.parallel import cost as C
    monkeypatch.setattr(C, "_filter_selectivity", lambda f, ds: 1e-5)


def _sharded_case(form, monkeypatch):
    """Per-shard late materialization on the 8-device mesh: N and M are
    a shard's, so the form is chosen from per-shard shapes."""
    import dataclasses as _dc
    from spark_druid_olap_tpu.ir import spec as S
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    from spark_druid_olap_tpu.planner import builder as B
    from spark_druid_olap_tpu.sql.parser import parse_select
    c = _pin(sdot.Context(mesh=make_mesh()), form)
    c.ingest_dataframe("sales", _df(12000), time_column="ts",
                       target_rows=1024)
    q = B.build(c, parse_select(
        "select region, sum(qty) as s, avg(price) as ap, count(*) as n "
        "from sales where sku = 'sku007' group by region "
        "order by region")).specs[0]
    q = _dc.replace(q, context=_dc.replace(
        q.context or S.QueryContext(), prefer_sharded=True))
    got = c.engine.execute(q).to_pandas()
    st = dict(c.engine.last_stats)
    assert st["sharded"] is True
    return got.sort_values("region").reset_index(drop=True), st


def _wave_case(form, monkeypatch):
    c = _pin(_wave_ctx(False), form)
    got = c.sql(WAVE_SQL).to_pandas()
    st = dict(c.history.entries()[-1].stats)
    assert st.get("waves", 1) > 1, f"wave mode not engaged: {st}"
    return got, st


def _nullable_time_case(form, monkeypatch):
    """A nullable column and a sub-day time column: the validity array
    rides as int8 and comes back the mask it was, `__time_ms__` rides
    beside the day column (4 arrays: ts, __time_ms__, price and
    __nulls__price)."""
    rng = np.random.default_rng(5)
    df = _df(seed=5)
    df["ts"] = pd.Timestamp("2020-01-01") + pd.to_timedelta(
        rng.integers(0, 90 * 86400, len(df)), unit="s")
    df["price"] = df["price"].where(rng.random(len(df)) > 0.3)
    c = _ctx(form or False, df)
    got = c.sql("select extract(hour from ts) as h, count(price) as np, "
                "sum(price) as p, count(*) as n from sales "
                "where sku = 'sku007' group by 1 order by 1").to_pandas()
    assert 0 < got["np"].sum() < got["n"].sum()
    return got, dict(c.history.entries()[-1].stats)


HASHED_SQL = ("select sku, sum(qty) as s, sum(price) as p, count(*) as n "
              "from sales where region = 'east' and qty = 7 "
              "group by sku order by sku limit 30")

# name -> (runner, arrays the compacted body reads; None: the estimate
# lies, the count says every row survives, and the statement is answered
# uncompacted without a compacting program ever being built)
FORM_CASES = {
    "dense_selector": (_sql_case(QUERIES[0]), 2),
    "dense_in_expr": (_sql_case(QUERIES[1]), 2),
    "dense_global_minmax": (_sql_case(QUERIES[2]), 2),
    "dense_time_bucket": (_sql_case(QUERIES[3]), 3),
    "hashed_scatter": (_sql_case(HASHED_SQL, _hashed("off")), 3),
    "hashed_sorted_run": (_sql_case(HASHED_SQL, _hashed("on")), 3),
    "staged_membership": (_sql_case(
        "select region, count(*) as n, sum(qty) as s from sales "
        "where sku = 'sku007' and qty * 100 + 1 in ("
        + ", ".join(str(k) for k in range(1, 5000, 83)) + ") "
        "group by region order by region"), 2),
    "sketches": (_sql_case(
        "select region, approx_count_distinct(sku) as d from sales "
        "where sku in ('sku001','sku002','sku003','sku004','sku005') "
        "group by region order by region"), 2),
    "all_rows_filtered": (_sql_case(
        "select count(*) as n, sum(qty) as s, max(price) as mx from sales "
        "where sku = 'sku001' and qty > 98 and qty < 1"), 2),
    "estimate_lies": (_sql_case(
        "select region, count(*) as n, sum(price) as p from sales "
        "where qty >= 0 group by region order by region",
        _lying_estimate), None),
    "nullable_time": (_nullable_time_case, 4),
    "sharded8": (_sharded_case, 3),
    "wave": (_wave_case, 3),
}


@pytest.fixture(scope="module")
def form_runs():
    """(case, form) -> (frame, record), each run once for the module:
    the per-form and the form-against-form tests read the same runs."""
    return {}


def _form_run(form_runs, case, form, monkeypatch):
    if (case, form) not in form_runs:
        form_runs[case, form] = FORM_CASES[case][0](form, monkeypatch)
    return form_runs[case, form]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("case", sorted(FORM_CASES))
def test_form_matches_uncompacted(case, form, form_runs, monkeypatch):
    got, st = _form_run(form_runs, case, form, monkeypatch)
    want, plain = _form_run(form_runs, case, None, monkeypatch)
    assert "compact_m" not in plain and "compact_carry" not in plain
    pd.testing.assert_frame_equal(got, want, check_dtype=False, atol=1e-6)
    cols = FORM_CASES[case][1]
    if cols is None:
        assert "compact_overflow" not in st and st["n_dispatch"] == 2
        assert "compact_m" not in st and "compact_carry" not in st
    else:
        assert st.get("compact_m", 0) > 0, st
        assert (st["compact_carry"], st["compact_cols"]) == (form, cols)


@pytest.mark.parametrize("case", sorted(FORM_CASES))
def test_forms_bit_equal(case, form_runs, monkeypatch):
    """The prefix holds the same rows in the same order either way, so
    every float sum adds in the same order: not a bit may differ."""
    a, _ = _form_run(form_runs, case, "sort", monkeypatch)
    b, _ = _form_run(form_runs, case, "gather", monkeypatch)
    pd.testing.assert_frame_equal(a, b, check_exact=True)


# -- the stage alone: ops.scan.compact_scan -----------------------------------

def test_shape_rule_picks_the_form():
    """Sort where N * payload < M * probe: on the v5e's constants where
    M * 13 > N (q3 carries, a 2^15 budget over 6.0M rows gathers); on
    the CPU's never — a budget is at most half the rows."""
    import jax
    from spark_druid_olap_tpu.ops.scan import carries_by_sort
    from spark_druid_olap_tpu.parallel import cost as C
    from spark_druid_olap_tpu.utils import config as CF
    pay = float(CF.COST_SORT_PAYLOAD_ROW.default)      # the v5e's
    probe = float(CF.COST_GATHER_PROBE.default)
    assert carries_by_sort(4_001_792, 1 << 20, pay, probe)        # q3
    assert not carries_by_sort(6_002_688, 1 << 15, pay, probe)
    edge = probe / pay                  # M * edge > N carries
    assert 5 < edge < 25
    assert carries_by_sort(1_000_000, int(1_000_000 / edge) + 1000,
                           pay, probe)
    assert not carries_by_sort(1_000_000, int(1_000_000 / edge) - 1000,
                               pay, probe)
    assert jax.default_backend() == "cpu"
    cfg = sdot.Context().config
    pay = C.unit_cost(cfg, CF.COST_SORT_PAYLOAD_ROW)
    probe = C.unit_cost(cfg, CF.COST_GATHER_PROBE)
    for n, m in ((4_001_792, 1 << 20), (6_002_688, 1 << 15), (1000, 500)):
        assert not carries_by_sort(n, m, pay, probe)


def _stage_arrays(n_seg=4, rows=512, seed=3):
    from spark_druid_olap_tpu.ops import scan as SC
    rng = np.random.default_rng(seed)
    shape = (n_seg, rows)
    return {
        "code": rng.integers(-100, 100, shape).astype(np.int16),
        "val": rng.random(shape).astype(np.float32),
        SC.NULL_VALID_PREFIX + "val": rng.random(shape) > 0.4,
        SC.TIME_MS_KEY: rng.integers(0, 86_400_000, shape).astype(np.int32),
        "cheap_only": rng.integers(0, 10, shape).astype(np.int32),
        SC.ROW_VALID_KEY: rng.random(shape) > 0.05,
    }


def _stage_body(ctx, base):
    return {"code": ctx.col("code"), "val": ctx.col("val"),
            "nulls": ctx.null_valid("val"), "ms": ctx.time_ms(),
            "base": base}


def _stage(arrays, mask, m, sort):
    """The stage run eagerly over ``arrays``: a payload priced at nothing
    carries by sort, at a second gathers."""
    from spark_druid_olap_tpu.ops import scan as SC
    ctx = SC.ScanContext(None, arrays, 0, 0)
    cctx, base, n_live = SC.compact_scan(
        ctx, mask, m, _stage_body, 1e-15 if sort else 1.0, 1e-9)
    return _stage_body(cctx, base), n_live, cctx.carried()


@pytest.mark.parametrize("live", ["under", "exact", "over"])
def test_stage_prefix_and_base(live):
    """Both forms hold the survivors in row order in the [M] prefix; the
    validity array rides as int8 and returns a bool mask; `base` — now an
    iota compare — equals the old read ``flat[keep]``; the survivors are
    counted whether or not they fit."""
    import jax.numpy as jnp
    m = 256
    arrays = _stage_arrays()
    n = arrays["code"].size
    want_live = {"under": 100, "exact": m, "over": m + 37}[live]
    rng = np.random.default_rng(9)
    mask = np.zeros(n, bool)
    mask[rng.choice(n, want_live, replace=False)] = True
    mask = mask.reshape(arrays["code"].shape)
    dev = {k: jnp.asarray(v) for k, v in arrays.items()}
    # what the parent program read: survivors first under a stable sort
    keep = np.argsort(~mask.reshape(-1), kind="stable")[:m]
    old_base = mask.reshape(-1)[keep]
    k = min(want_live, m)
    outs = {}
    for sort in (True, False):
        out, n_live, carried = _stage(dev, jnp.asarray(mask), m, sort)
        assert carried == ("sort" if sort else "gather", 4)
        assert int(n_live) == want_live
        base = np.asarray(out["base"])
        assert base.dtype == np.bool_
        np.testing.assert_array_equal(base, old_base)
        assert out["nulls"].dtype == jnp.bool_
        assert out["code"].dtype == jnp.int32       # widened as col() does
        for key, src in (("code", "code"), ("val", "val"),
                         ("nulls", "__nulls__val"), ("ms", "__time_ms__")):
            np.testing.assert_array_equal(
                np.asarray(out[key])[:k], arrays[src].reshape(-1)[keep][:k])
        outs[sort] = out
    for key in ("code", "val", "nulls", "ms"):     # live rows, bit for bit
        np.testing.assert_array_equal(np.asarray(outs[True][key])[:k],
                                      np.asarray(outs[False][key])[:k])


def test_stage_carries_only_what_the_body_reads():
    """The cheap filter's column and the row-validity array are read
    before compaction only: they do not ride."""
    import jax
    import jax.numpy as jnp
    from spark_druid_olap_tpu.ops import scan as SC
    arrays = {k: jnp.asarray(v) for k, v in _stage_arrays().items()}

    def run(arrays):
        ctx = SC.ScanContext(None, arrays, 0, 0)
        mask = ctx.row_valid() & (ctx.col("cheap_only") < 3)
        cctx, base, _ = SC.compact_scan(ctx, mask, 128, _stage_body,
                                        1e-15, 1e-9)
        assert sorted(cctx.taken) == sorted(
            ["code", "val", "__nulls__val", "__time_ms__"])
        return _stage_body(cctx, base)

    text = jax.jit(run).lower(arrays).as_text()
    assert text.count("stablehlo.sort") == 1
    assert "gather" not in text


def test_read_outside_the_carried_set_raises_at_trace_time():
    """A column the recording pass did not see must fail loudly when the
    program is traced — never fall back to a silent gather."""
    import jax
    import jax.numpy as jnp
    from spark_druid_olap_tpu.ops import scan as SC
    arrays = {k: jnp.asarray(v) for k, v in _stage_arrays().items()}

    def run(arrays):
        ctx = SC.ScanContext(None, arrays, 0, 0)
        cctx, base, _ = SC.compact_scan(ctx, ctx.row_valid(), 128,
                                        _stage_body, 1e-15, 1e-9)
        return cctx.col("cheap_only")

    with pytest.raises(LookupError, match="cheap_only.*did not ride"):
        jax.jit(run).lower(arrays)
    # the gather form has no carried set: the same read is a gather
    def gathers(arrays):
        ctx = SC.ScanContext(None, arrays, 0, 0)
        cctx, base, _ = SC.compact_scan(ctx, ctx.row_valid(), 128,
                                        _stage_body, 1.0, 1e-9)
        return cctx.col("cheap_only")
    assert jax.jit(gathers)(arrays).shape == (128,)


def test_gate_prices_the_form_the_program_runs(monkeypatch):
    """``_plan_compact_m`` charges each touched column the cheaper of a
    sort payload over the rows and a gather of the budget, on the v5e's
    constants: TPC-H q3's shape at SF1 (hashed, 4.0M rows, a 2^20
    budget) compacts; q5's (6.0M rows through the fused kernel, an 'ffl'
    route, which streams a row faster than a sort orders it) does not —
    at the old `fused.seconds.per.row` the payload price would have let
    it in."""
    from types import SimpleNamespace as NS
    from spark_druid_olap_tpu.parallel import cost as C
    from spark_druid_olap_tpu.utils import config as CF
    eng = sdot.Context().engine
    for e in (CF.COST_SORT_ROW, CF.COST_SORT_PAYLOAD_ROW,
              CF.COST_GATHER_PROBE, CF.COST_SCATTER_UPDATE,
              CF.COST_SCATTER_UPDATE_BIG, CF.COST_FUSED_ROW):
        eng.config.set(e.key, e.default)        # the chip's, on any backend

    def ds(n_seg):
        return NS(segments=[NS(num_rows=1_000_448)] * n_seg)

    monkeypatch.setattr(C, "_filter_selectivity", lambda f, d: 0.097)
    q3 = eng._plan_compact_m(ds(4), range(4), object(), False,
                             n_keys=1 << 21, n_ops=3)
    assert q3 == 1 << 20
    monkeypatch.setattr(C, "_filter_selectivity", lambda f, d: 0.0634)
    ffl = {k: NS(tag="ffl", outputs=lambda k: [("x", 2, "f32")])
           for k in ("revenue", "__rows__")}
    assert eng._plan_compact_m(ds(6), range(6), object(), False,
                               routes=ffl, n_keys=25) is None
    eng.config.set(CF.COST_FUSED_ROW.key, 2.3e-9)
    eng.config.set(CF.COST_SORT_ROW.key, 2.2e-10)
    assert eng._plan_compact_m(ds(6), range(6), object(), False,
                               routes=ffl, n_keys=25) == 1 << 20


# -- the mask a statement compacts on -------------------------------------------
#
# A filter with a cheap conjunct compacts on its cheap conjuncts and stages
# the gather-heavy rest after the compaction; a filter of staged conjuncts
# alone (an executed IN subquery's keys, past 1,024 of them a sorted set
# the program binary-searches or probes) is its own mask; and a mask no
# estimate can price — such a set, or a value list over a column with no
# dictionary — is sized by the filter-only count at its first sight.

def _keyed_df(n=60_000, seed=21):
    """``_df`` with ``k``: each row's own integer key."""
    df = _df(n, seed=seed)
    df["k"] = np.random.default_rng(seed).permutation(n)
    return df


def _keyed_ctx(compact, df, picks, hashed=False):
    c = _ctx(compact, df)
    c.config.set("sdot.cache.enabled", False)
    c.ingest_dataframe("picks", pd.DataFrame({"pk": picks}))
    if hashed:
        c.config.set("sdot.engine.groupby.dense.max.keys", 8)
    return c


KEYED_SQL = ("select region, sku, count(*) as n, sum(qty) as s from sales "
             "where k in (select pk from picks) group by region, sku "
             "order by region, sku")


def _picks(n_keys, n_rows=60_000, seed=23):
    return np.sort(np.random.default_rng(seed).choice(
        n_rows, n_keys, replace=False))


@pytest.mark.parametrize("tier", ["dense", "hashed"])
def test_wide_set_is_its_own_compaction_mask(tier):
    """1,500 keys of 60,000: a sorted set the split stages, and the
    statement's only conjunct. It compacts on that set — counted at its
    first sight, since no estimate prices it — and answers as the
    uncompacted program does."""
    df, picks = _keyed_df(), _picks(1500)
    c = _keyed_ctx(True, df, picks, hashed=tier == "hashed")
    got, st = _send(c, KEYED_SQL)
    want = _keyed_ctx(False, df, picks, hashed=tier == "hashed") \
        .sql(KEYED_SQL).to_pandas()
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    assert st.get("hashed", False) == (tier == "hashed")
    assert st["compact_mask"] == "staged"
    assert st["compact_live"] == len(picks) <= st["compact_m"]
    assert st["compact_from"] == "observed"
    _, again = _send(c, KEYED_SQL)
    assert again["compact_m"] == st["compact_m"]
    assert again["program"]["built"] is False


def test_staged_mask_under_a_short_budget_reruns(monkeypatch):
    """A count that lies low leaves a budget its survivors exceed: the
    program's own count re-runs the statement at the size it asks for,
    with the same answer."""
    from spark_druid_olap_tpu.parallel.executor import QueryEngine
    df, picks = _keyed_df(), _picks(1500)
    want = _keyed_ctx(False, df, picks).sql(KEYED_SQL).to_pandas()
    c = _keyed_ctx(True, df, picks)
    monkeypatch.setattr(QueryEngine, "_count_survivors",
                        lambda self, *a: 10)
    got, st = _send(c, KEYED_SQL)
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    assert st["compact_overflow"] == len(picks) - _budget_for(10)
    assert st["compact_mask"] == "staged"
    assert st["compact_live"] == len(picks)
    assert st["compact_m"] == _budget_for(len(picks))


def test_short_integer_list_is_counted_and_compacts():
    """72 keys: a value list the split calls cheap, over a column with no
    dictionary, where the estimate guesses 100 distinct values and would
    call it unselective — TPC-H q18's outer at SF1. Counted at its first
    sight, it compacts on its cheap mask."""
    df, picks = _keyed_df(), _picks(72)
    sql = KEYED_SQL.replace("(select pk from picks)",
                            "(" + ", ".join(map(str, picks)) + ")")
    c = _keyed_ctx(True, df, picks)
    got, st = _send(c, sql)
    pd.testing.assert_frame_equal(
        got, _keyed_ctx(False, df, picks).sql(sql).to_pandas(),
        check_exact=True)
    assert st["compact_mask"] == "cheap"
    assert st["compact_from"] == "observed" and st["n_dispatch"] == 2
    assert st["compact_live"] == len(picks)


def test_dictionary_list_keeps_the_estimate():
    """A value list over a dictionary column is priced by the estimate,
    as before: 40 of 50 skus is unselective by it, so the statement is
    neither counted nor compacted."""
    c = _ctx(True)
    c.config.set("sdot.cache.enabled", False)
    skus = ", ".join(f"'sku{i:03d}'" for i in range(40))
    _, st = _send(c, "select region, sum(qty) as s from sales where sku "
                     f"in ({skus}) group by region order by region")
    assert "compact_m" not in st and "compact_mask" not in st
    assert st["n_dispatch"] == 1 and not c.engine._compact_seen


def _filters():
    from spark_druid_olap_tpu.ir import expr as E
    from spark_druid_olap_tpu.ir import spec as S
    cheap = S.SelectorFilter("sku", "sku007")
    wide = S.InFilter("k", E.FrozenIntSet(_picks(1500)))
    return cheap, wide, S.LogicalFilter("and", (cheap, wide))


@pytest.mark.parametrize("case", ["none", "cheap_only", "cheap_and_staged"])
def test_compaction_filters_keep_the_split(case):
    """Every filter with a cheap conjunct, or none at all, compacts as it
    did: on the split's cheap part, the staged part after it."""
    from spark_druid_olap_tpu.parallel import executor as X
    cheap, _, both = _filters()
    f = {"none": None, "cheap_only": cheap, "cheap_and_staged": both}[case]
    assert X._compaction_filters(f) == X.QueryEngine._split_filter_staged(f)
    assert not X._all_staged(f)


def test_compaction_filters_make_a_staged_filter_its_own_mask():
    from spark_druid_olap_tpu.ir import spec as S
    from spark_druid_olap_tpu.parallel import executor as X
    cheap, wide, _ = _filters()
    assert X.QueryEngine._split_filter_staged(wide) == (None, wide)
    assert X._compaction_filters(wide) == (wide, None)
    two = S.LogicalFilter("and", (wide, wide))
    assert X._compaction_filters(two) == (two, None)
    assert X._all_staged(wide) and X._all_staged(two)
