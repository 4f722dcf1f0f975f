"""The deployment ``tpch_sf1_hicard`` (TPC-H's ranking reports Q10, Q15,
Q18 with every text-keyed cache off) on the served path, at SF 0.01.

One store, one ``SqlServer`` built with the ``settings`` of
``benchmarks/configs/tpch_sf1_hicard.json``; every statement goes through
``POST /sql`` with the harness's own client and is compared by the
harness's own ``check_frames`` with the benchmark's own references. What
is held here: the answers; that EVERY send of a text executes its
subqueries (no plan cache, no memo, no ``served_from``); the outer
record's ``subqueries`` / ``subquery_rows`` / ``subquery_fetch_bytes``
and the inner record's ``parent``; the span shape (a ``subquery`` span
beside ``plan.rewrite``, no ``dispatch`` under a ``plan.*`` span, phases
that add up); that engine defaults (caches on) change no answer; and
that the statement file and the references are the originals.
"""

import importlib.util
import json
import os
import uuid

import pandas as pd
import pytest

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.tools import tpch
from spark_druid_olap_tpu.utils import phases as PH

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
CLASSES = ("q10", "q15", "q18")
SF = 0.01
TARGET_ROWS = 1 << 14       # lineitem in 4 segments: q15's quarter prunes


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


client = _load("hicard_harness_client", "harness", "client.py")
compare = _load("hicard_harness_compare", "harness", "compare.py")
REF = _load("hicard_reference", "references", "tpch_hicard.py")
CONFIG = _json("configs", "tpch_sf1_hicard.json")
STATEMENTS = _json("statements", "tpch_hicard3.json")["classes"]
Q18_LOW = STATEMENTS["q18"]["sql"].replace("> 300", "> 150")


class Served:
    """A Context over the module's frames behind a SqlServer on port 0."""

    def __init__(self, settings):
        from spark_druid_olap_tpu.server.http import SqlServer
        self.ctx = sdot.Context(settings)
        tables, flat = tpch.setup_context(self.ctx, sf=SF,
                                          target_rows=TARGET_ROWS)
        self.tables = tables
        self.nr = tpch.nation_region_views(tables)
        self.data = {**tables, **self.nr, "tpch_flat": flat}
        self.server = SqlServer(self.ctx, "127.0.0.1", 0).start()

    def send(self, sql):
        """(frame, the statement's record, its subqueries' records)."""
        qid = uuid.uuid4().hex
        _, _, status, body = client.post_sql(self.server.port, sql,
                                             queryId=qid)
        assert status == 200, body[:300]
        history = client.history(self.server.port)
        outer = [r for r in history if r.get("query_id") == qid]
        assert len(outer) == 1, [r.get("sql") for r in history[-6:]]
        inners = [r for r in history if r.get("parent") == qid]
        return client.body_frame(body), outer[0], inners

    def close(self):
        self.server.stop()
        self.ctx.close()


@pytest.fixture(scope="module")
def served():
    s = Served(CONFIG["settings"])
    yield s
    s.close()


def _check(cls, got, want):
    g = CONFIG["guarantees"]
    return compare.check_frames(cls, got, want, approx=(),
                                rtol=g["float_rtol"])


def _big_orders(served, thresh):
    qty = served.tables["lineitem"].groupby("l_orderkey").l_quantity.sum()
    return int((qty > thresh).sum())


def _quarter_suppliers(served):
    li = served.tables["lineitem"]
    li = li[(li.l_shipdate >= pd.Timestamp("1996-01-01"))
            & (li.l_shipdate < pd.Timestamp("1996-04-01"))]
    return int(li.l_suppkey.nunique())


# -- the answers ---------------------------------------------------------------

@pytest.mark.parametrize("cls", CLASSES)
def test_served_answer_equals_reference(served, cls):
    st = STATEMENTS[cls]
    assert st["approx"] == []
    ref_file, ref_fn = st["reference"].split(":")
    assert ref_file == "tpch_hicard"
    got, rec, inners = served.send(st["sql"])
    _check(cls, got, getattr(REF, ref_fn)(served.data))
    for r in [rec] + inners:
        assert r["mode"] == CONFIG["guarantees"]["mode"], r
        assert not r.get("backend_lost")


def test_q18_lower_threshold_served(served):
    """tests/test_tpch22.py::test_q18_lower_threshold through HTTP:
    QUANTITY lowered until hundreds of order keys pass the HAVING."""
    want = REF.oracle_q18(served.data, thresh=150)
    n_keys = _big_orders(served, 150)
    assert n_keys >= 100 and len(want) == 100, (n_keys, len(want))
    got, rec, inners = served.send(Q18_LOW)
    # o_totalprice ties at the LIMIT boundary are not expected at this
    # scale; the reference and the engine then cut the same 100 rows
    _check("q18_low", got, want)
    assert rec["subqueries"] == 1 and rec["subquery_rows"] == n_keys
    assert len(inners) == 1 and inners[0]["sql"] == "<subquery>"


@pytest.mark.parametrize("thresh,mask", [(250, "cheap"), (190, "staged")])
def test_q18_outer_compacts_on_its_in_list(served, thresh, mask):
    """q18's outer has one conjunct, the inner's order keys. With the
    gate's row minimum lowered to this scale it compacts on that list,
    counted at its first sight (no estimate prices keys over a column
    with no dictionary): under 1,024 keys a value list (SF1's 56-71 are
    one), over them a sorted set the split stages, then the filter is its
    own mask."""
    key = "sdot.engine.scan.compact.min.rows"
    was = served.ctx.config.get(key)
    served.ctx.config.set(key, 0)
    try:
        sql = STATEMENTS["q18"]["sql"].replace("> 300", f"> {thresh}")
        got, rec, _ = served.send(sql)
        again, rec2, _ = served.send(sql)
    finally:
        served.ctx.config.set(key, was)
    want = REF.oracle_q18(served.data, thresh=thresh)
    _check("q18", got, want)
    _check("q18", again, want)
    qty = served.tables["lineitem"].groupby("l_orderkey").l_quantity.sum()
    keys = qty[qty > thresh].index
    assert (len(keys) > 1024) == (mask == "staged")
    lines = int(served.data["tpch_flat"].o_orderkey.isin(keys).sum())
    for r in (rec, rec2):
        assert r["compact_mask"] == mask
        assert r["compact_from"] == "observed"
        assert r["compact_live"] == lines <= r["compact_m"]
    assert rec["n_dispatch"] == 3 and rec2["n_dispatch"] == 2


# -- every send executes its subqueries ----------------------------------------

@pytest.mark.parametrize("cls", CLASSES)
def test_every_send_executes_its_subqueries(served, cls):
    sql = STATEMENTS[cls]["sql"]
    want = getattr(REF, "oracle_" + cls)(served.data)
    for _ in range(3):
        got, rec, inners = served.send(sql)
        _check(cls, got, want)
        assert "served_from" not in rec, rec["served_from"]
        assert not rec.get("plan_cached")
        assert "plan_memo" not in rec        # the memo is off, not missed
        if cls == "q10":
            assert not rec.get("subqueries") and not inners
            assert "subquery" not in rec["phases"]
            assert rec["n_dispatch"] >= 1
            continue
        assert rec["subqueries"] == 1 if cls == "q18" \
            else rec["subqueries"] >= 1
        assert rec["n_dispatch"] >= 2
        assert len(inners) == rec["subqueries"]
        for r in inners:
            assert r["mode"] == "engine" and "served_from" not in r
            # (an engine-assisted subtree's record — q15's first send on
            # a server — carries the engine's stats, not the session's)
            assert r["sql"] != "<subquery>" or r["n_dispatch"] >= 1


@pytest.mark.parametrize("cls", ("q15", "q18"))
def test_subquery_counters(served, cls):
    sql = STATEMENTS[cls]["sql"]
    served.send(sql)            # a first send may also fill the assist cache
    _, rec, inners = served.send(sql)
    if cls == "q18":
        # the inner's frame AFTER its HAVING: the orders over 300 units
        rows = _big_orders(served, 300)
    else:
        # the inlined subquery is the scalar `select max(...)`: one row
        # (its 'suppliers with revenue in the quarter' frame is the
        # derived table inside it)
        rows = 1
    assert rec["subqueries"] == 1 and rec["subquery_rows"] == rows
    assert 0 < rec["subquery_fetch_bytes"] <= rec["fetch_bytes"]
    assert rec["subquery_fetch_bytes"] \
        == sum(r["fetch_bytes"] for r in inners)


def test_q15_assisted_derived_table_counts_once(served):
    """The host finish of q15's composite plan evaluates the scalar over
    its derived table through ``host_exec.try_engine``: executed (and
    counted, with its rows) when the assist cache does not hold it."""
    served.ctx._result_cache["assist"].clear()
    _, rec, inners = served.send(STATEMENTS["q15"]["sql"])
    assert rec["subqueries"] == 2
    assert rec["subquery_rows"] == 1 + _quarter_suppliers(served)
    assert sorted(r["sql"] for r in inners) \
        == ["(engine-assisted subtree)", "<subquery>"]
    assert 0 < rec["subquery_fetch_bytes"] <= rec["fetch_bytes"]
    _, rec, _ = served.send(STATEMENTS["q15"]["sql"])
    assert rec["subqueries"] == 1 and rec["subquery_rows"] == 1


# -- the span shape --------------------------------------------------------------

def _ancestors(spans, i):
    while spans[i][3] >= 0:
        i = spans[i][3]
        yield spans[i][0]


@pytest.mark.parametrize("cls", CLASSES)
def test_span_shape(served, cls):
    sql = STATEMENTS[cls]["sql"]
    unphased = []
    for _ in range(3):
        _, rec, inners = served.send(sql)
        spans, phases = rec["spans"], rec["phases"]
        assert {s[0] for s in spans} <= set(PH.PHASES)
        dispatches = [i for i, s in enumerate(spans) if s[0] == "dispatch"]
        assert len(dispatches) == rec["n_dispatch"]
        for i in dispatches:
            above = list(_ancestors(spans, i))
            assert not any(a.startswith("plan.") for a in above), above
            assert spans[i][3] == 0 or "subquery" in above, above
        top = [s for s in spans if s[0] == "subquery" and s[3] == 0]
        if cls == "q10":
            assert not top and "subquery" not in phases
        else:
            assert len(top) == rec["subqueries"]
            # the flat view: the root's children by name, each lifted
            # subquery a phase of its own and taken out of the ONE
            # sibling it ran inside (plan.rewrite, or result for an
            # engine-assisted subtree), which stays one row
            kids = [s for s in spans if s[3] == 0 and s[2] is not None]
            want = {}
            for s in kids:
                want[s[0]] = want.get(s[0], 0.0) + s[2]
            for t in top:
                around = [s for s in kids if s[0] != "subquery"
                          and s[1] <= t[1] and t[1] + t[2] <= s[1] + s[2]]
                assert len(around) == 1, (t, around)
                want[around[0][0]] -= t[2]
            for name, ms in phases.items():
                assert ms == pytest.approx(want[name] / 1000.0, abs=0.01)
            assert sum(1 for s in kids if s[0] == "plan.rewrite") == 1
            # planning alone is left in plan.rewrite: it no longer holds
            # the inner's dispatch
            inner_dispatch = sum(
                s[2] for i, s in enumerate(spans) if s[0] == "dispatch"
                and "subquery" in _ancestors(spans, i)) / 1000.0
            assert inner_dispatch > 0
            assert phases["subquery"] >= inner_dispatch
            for r in inners:
                assert r["parent"] == rec["query_id"]
                assert "spans" not in r and "query_id" not in r
        total = rec["total_ms"]
        assert sum(phases.values()) <= total + 0.05     # nothing twice
        unphased.append(total - sum(phases.values()))
    # gaps between spans are host noise under a loaded test run: the
    # quietest of the three sends shows what no span covers
    total = rec["total_ms"]
    assert min(unphased) <= max(1.0, 0.05 * total), (unphased, total)


def test_lifted_span_hangs_under_the_root_and_leaves_its_phase():
    tok = PH.begin()
    with PH.phase("plan.rewrite"):
        with PH.phase("plan.star"):
            with PH.lifted("subquery"):
                with PH.phase("dispatch"):
                    with PH.lifted("subquery"):
                        with PH.phase("bind"):
                            pass
    with PH.phase("dispatch"):
        pass
    phases = PH.end(tok)
    spans = tok.stmt.spans
    # nothing is closed and opened again: one row a span, the first
    # subquery under the root, its own subquery under it
    assert [(s[0], s[3]) for s in spans] == [
        ("sql", -1), ("plan.rewrite", 0), ("plan.star", 1),
        ("subquery", 0), ("dispatch", 3), ("subquery", 3), ("bind", 5),
        ("dispatch", 0)]
    assert all(s[2] is not None for s in spans)
    assert tok.stmt.stack == [0]
    assert set(phases) == {"plan.rewrite", "subquery", "dispatch"}
    assert phases["subquery"] == pytest.approx(spans[3][2] / 1000.0)
    assert phases["plan.rewrite"] == pytest.approx(
        (spans[1][2] - spans[3][2]) / 1000.0)
    assert phases["dispatch"] == pytest.approx(spans[7][2] / 1000.0)
    assert 0 <= phases["plan.rewrite"]
    assert sum(phases.values()) <= spans[0][2] / 1000.0


def test_lifted_span_without_a_statement_is_a_no_op():
    with PH.lifted("subquery") as p:
        assert p.st is None


# -- a cache changes no answer -----------------------------------------------------

@pytest.fixture(scope="module")
def defaults():
    """Engine defaults: plan cache and memo on — and the result cache,
    which tests/conftest.py pins off for the suite, on as shipped."""
    s = Served({"sdot.cache.enabled": True})
    yield s
    s.close()


@pytest.mark.parametrize("cls", CLASSES)
def test_engine_defaults_give_the_same_frames(served, defaults, cls):
    sql = STATEMENTS[cls]["sql"]
    want, _, _ = served.send(sql)
    first, rec1, _ = defaults.send(sql)
    again, rec2, _ = defaults.send(sql)
    for got in (first, again):
        pd.testing.assert_frame_equal(got, want)
    # and the second send was answered from what the first left behind
    assert rec2.get("served_from") or rec2.get("plan_cached") \
        or (rec2.get("plan_memo") or {}).get("hit"), rec2
    assert not rec2.get("subqueries")
    assert rec1["mode"] == rec2["mode"] == "engine"


# -- the files are the originals -------------------------------------------------

@pytest.mark.parametrize("cls", CLASSES)
def test_statement_text_is_tools_tpch(cls):
    assert STATEMENTS[cls]["sql"] == tpch.QUERIES[cls]
    assert set(STATEMENTS) == set(CLASSES)


@pytest.mark.parametrize("cls", CLASSES)
def test_reference_returns_its_originals_frame(served, cls):
    if cls == "q10":
        from test_tpch import oracle_q10 as original
    else:
        import test_tpch22
        original = getattr(test_tpch22, "oracle_" + cls)
    want = original(served.tables, served.nr)
    got = getattr(REF, "oracle_" + cls)(served.data)
    assert len(want) > 0 or cls == "q18"
    pd.testing.assert_frame_equal(got, want, check_exact=False,
                                  rtol=1e-12, atol=0.0)
    if cls == "q18":
        pd.testing.assert_frame_equal(
            REF.oracle_q18(served.data, thresh=150),
            original(served.tables, served.nr, thresh=150),
            check_exact=False, rtol=1e-12, atol=0.0)


# -- a reference computes answers and nothing else --------------------------------

def test_reference_file_calls_nothing_of_the_program():
    """``benchmarks/references/tpch_hicard.py`` is plain pandas over the
    generated frames: it imports nothing of the engine, reads no command
    line and runs nothing when it is loaded."""
    import ast
    with open(os.path.join(BENCH, "references", "tpch_hicard.py")) as f:
        tree = ast.parse(f.read())
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names}
    imported |= {n.module.split(".")[0] for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)}
    assert imported == {"pandas"}
    assert all(isinstance(n, (ast.Import, ast.ImportFrom, ast.FunctionDef))
               or (isinstance(n, ast.Expr)
                   and isinstance(n.value, ast.Constant))
               for n in tree.body), "something runs at import"


# -- the deployment names what it needs of the program ----------------------------

def test_settings_name_a_module_the_program_has(served):
    """``sdot.modules`` of the configuration is installed when the
    Context is created, and contributes nothing."""
    from spark_druid_olap_tpu.utils import modules
    assert [type(m) for m in served.ctx.modules] \
        == [modules.HighCardinalityGroupBy]
    assert served.ctx.spec_rules == [] \
        and served.ctx.statement_handlers == []


def test_a_program_without_the_module_refuses_the_deployment():
    """What the commit before this deployment does with its settings: the
    Context is not created, so no statement is ever compiled."""
    settings = dict(CONFIG["settings"])
    settings["sdot.modules"] = settings["sdot.modules"] + "OfALaterProgram"
    with pytest.raises(AttributeError):
        sdot.Context(settings)
