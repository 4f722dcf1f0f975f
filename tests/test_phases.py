"""Span tree + planning-cascade memo + decode-ahead tier tests.

Five layers:

1. **Profiler unit semantics** (utils/phases.py): begin/end exactness,
   nested-begin merge, no-op outside an accumulator, stash folding, the
   span tree (parents, starts, durations, self time), the child-span
   rule, and the always-on overhead micro-budget (< 1% of wall enforced
   as a per-timing ceiling far below the ~1.7 ms dispatch floor), the
   statement's thread CPU time beside its wall time, and the process's
   collections landing in the statements they overlap.
2. **Stats contract** — every executed statement carries
   ``stats["phases"]`` whose names all come from the PHASES registry,
   and the key disappears when ``sdot.phases.enabled`` is off.
3. **Memo behavior** — a warm repeat of the identical statement (plan
   cache OFF, memo ON) skips the planning phases entirely and reports
   ``plan_memo == {"hit": True}``; any ingest, semantic config flip,
   CLEAR METADATA, or rollup DDL invalidates the memo (store-version /
   fingerprint keyed, exactly like the plan caches).
5. **The record's span tree** — on a dense statement, a hashed
   statement and an eight-tile fused group (interpret mode, tiny
   store): child spans never become keys of ``phases``,
   ``total_ms - sum(phases) >= 0``, the leader carries ``dispatch`` with
   its three children and the followers ``coalesce.hold`` +
   ``coalesce.ride``; the server's root, the query id, and the same
   spans in a ``jax.profiler`` capture.
4. **Decode-ahead differential** — over an encoded tiered store the
   second pass serves decoded chunks from the decoded-side cache
   (``decode_ms_saved > 0``) with bit-identical answers.
"""

import contextlib
import gc
import json
import os
import threading
import time
import urllib.request

import jax
import numpy as np
import pandas as pd
import pytest

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.utils import phases as PH


# -- 1. profiler unit semantics ----------------------------------------------

def _drain():
    """Make sure a failed test can't leak an open accumulator/stash."""
    PH.end(PH._acc())
    PH.clear_stash()


@pytest.fixture(autouse=True)
def _clean_profiler():
    _drain()
    yield
    _drain()


def test_begin_end_exactness():
    tok = PH.begin()
    assert tok is not None
    with PH.phase("plan.build"):
        time.sleep(0.01)
    PH.add("dispatch", 0.5)
    PH.add("dispatch", 0.25)
    out = PH.end(tok)
    assert set(out) == {"plan.build", "dispatch"}
    assert out["dispatch"] == pytest.approx(750.0)      # ms conversion
    assert out["plan.build"] >= 9.0                     # sleep floor


def test_nested_begin_merges_into_outer():
    tok = PH.begin()
    inner = PH.begin()                  # nested query (union branch)
    assert inner is None
    with PH.phase("bind"):
        pass
    assert PH.end(inner) is None        # inner close is a no-op
    out = PH.end(tok)
    assert "bind" in out                # inner phase merged into outer


def test_phase_and_add_are_noops_without_accumulator():
    with PH.phase("bind"):              # no begin(): background thread
        pass
    PH.add("dispatch", 1.0)
    tok = PH.begin()
    assert PH.end(tok) == {}            # nothing leaked in


def test_nested_phase_is_a_span_not_a_phase():
    """The child-span rule: a phase nested in another is in the tree
    only, so the flat view never counts an interval twice."""
    tok = PH.begin()
    with PH.phase("plan.build"):
        with PH.phase("plan.rollup"):
            time.sleep(0.005)
    out = PH.end(tok)
    assert set(out) == {"plan.build"} and out["plan.build"] >= 4.0
    by = {sp[0]: sp for sp in tok.stmt.spans}
    assert by["plan.rollup"][2] >= 4000.0                   # us
    assert tok.stmt.spans[by["plan.rollup"][3]][0] == "plan.build"


def _self_us(spans, i):
    """Duration of span ``i`` minus the union of its children."""
    kids = sorted((sp[1], sp[1] + sp[2]) for sp in spans if sp[3] == i)
    covered, end = 0.0, float("-inf")
    for a, b in kids:
        covered += max(0.0, b - max(a, end))
        end = max(end, b)
    return spans[i][2] - covered


def test_span_tree_of_a_nested_sequence():
    """Parents, starts, durations and self time of a known sequence:
    sql > [bind, dispatch > [dispatch.launch, dispatch.wait > tier.fault
    (pre-measured)], decode]."""
    tok = PH.begin()
    with PH.phase("bind"):
        time.sleep(0.002)
    with PH.phase("dispatch"):
        with PH.phase("dispatch.launch"):
            time.sleep(0.001)
        with PH.phase("dispatch.wait"):
            time.sleep(0.004)
            PH.add("tier.fault", 0.003)        # ends now, began 3 ms ago
        time.sleep(0.002)                      # dispatch's own time
    with PH.phase("decode"):
        pass
    PH.end(tok)
    spans = tok.stmt.spans
    names = [sp[0] for sp in spans]
    assert names == ["sql", "bind", "dispatch", "dispatch.launch",
                     "dispatch.wait", "tier.fault", "decode"]
    parents = [sp[3] for sp in spans]
    assert parents == [-1, 0, 0, 2, 2, 4, 0]
    assert spans[0][1] == 0.0 and all(sp[2] is not None for sp in spans)
    for i, sp in enumerate(spans[1:], 1):      # a child lies in its parent
        par = spans[sp[3]]
        assert par[1] - 1.0 <= sp[1]
        assert sp[1] + sp[2] <= par[1] + par[2] + 1.0, (sp, par)
    starts = [sp[1] for sp in spans if sp[0] != "tier.fault"]
    assert starts == sorted(starts)            # opened in program order
    assert spans[5][2] == pytest.approx(3000.0)
    wait_end = spans[4][1] + spans[4][2]       # add() ends at the call,
    assert wait_end - 2000.0 <= spans[5][1] + spans[5][2] <= wait_end + 1.0
    # self time: dispatch = its sleep(2 ms); the root = the gaps
    assert 1900.0 <= _self_us(spans, 2) <= spans[2][2] - 4900.0
    assert _self_us(spans, 4) == pytest.approx(spans[4][2] - 3000.0,
                                               abs=1.0)
    assert 0.0 <= _self_us(spans, 0) < spans[0][2] - 8900.0


def test_phases_and_spans_agree_by_name():
    """Every key of ``phases`` is the sum of the root's children of that
    name, and every child of the root is a key."""
    PH.stash("parse", 0.001)
    tok = PH.begin()
    for _ in range(2):
        with PH.phase("bind"):
            time.sleep(0.001)
        with PH.phase("dispatch"):
            with PH.phase("bind"):             # a wave's overlapped bind
                pass
    PH.add("tier.decode", 0.002)
    out = PH.end(tok)
    spans = tok.stmt.spans
    top = {}
    for sp in spans[1:]:
        if sp[3] == 0:
            top[sp[0]] = top.get(sp[0], 0.0) + sp[2] / 1000.0
    assert set(out) == set(top) == {"parse", "bind", "dispatch",
                                    "tier.decode"}
    for k in out:
        assert out[k] == pytest.approx(top[k], abs=1e-3)
    assert sum(1 for sp in spans if sp[0] == "bind") == 4
    # the stashed parse began before begin(): the root starts with it
    assert min(sp[1] for sp in spans) >= 0.0


def test_server_root_keeps_handler_spans_out_of_phases():
    """``open_root`` (the HTTP handler) owns the root; the session's
    begin()..end() only opens the flat view, so spans before and after
    it are in the tree and not in ``phases``."""
    root = PH.open_root("http.request", "q-1")
    assert PH.open_root("http.request") is None    # one per thread
    with PH.phase("http.read"):
        pass
    tok = PH.begin()
    assert tok is not None and tok.stmt is root
    with PH.phase("bind"):
        pass
    out = PH.end(tok)
    assert root.spans[0][2] is None                # the root is still open
    with PH.phase("http.encode"):
        pass
    PH.close_root(root)
    PH.close_root(root)                            # idempotent
    assert set(out) == {"bind"}
    assert [sp[0] for sp in root.spans] == [
        "http.request", "http.read", "bind", "http.encode"]
    assert all(sp[3] == 0 for sp in root.spans[1:])
    assert root.spans[0][2] >= root.spans[-1][1] + root.spans[-1][2]
    assert root.qid == "q-1" and PH._acc() is None
    assert PH.begin() is not None                  # the thread is clean


def test_stash_folds_into_next_begin_and_clears():
    PH.stash("parse", 0.2)
    tok = PH.begin()
    out = PH.end(tok)
    assert out["parse"] == pytest.approx(200.0)
    PH.stash("parse", 0.2)
    PH.clear_stash()                    # statement boundary drops it
    tok = PH.begin()
    assert PH.end(tok) == {}


def test_end_is_idempotent():
    tok = PH.begin()
    first = PH.end(tok)
    assert PH.end(tok) == first         # finally-block double close
    tok2 = PH.begin()                   # and a fresh begin still works
    assert tok2 is not None
    PH.end(tok2)


def test_disabled_begin_returns_none():
    tok = PH.begin(enabled=False)
    assert tok is None
    PH.add("dispatch", 1.0)
    assert PH.end(tok) is None


def test_overhead_micro_budget():
    """Always-on budget, spans on: one span is two perf_counter_ns
    reads, a list append, a dict update and an inactive
    TraceAnnotation. 50 us per span is ~15x observed cost and keeps
    the ~25 spans of a real statement under 1.5 ms worst case — far
    below 1% of the multi-ms host path it instruments."""
    n = 10_000
    tok = PH.begin()
    t0 = time.perf_counter()
    for _ in range(n // 2):
        with PH.phase("dispatch"):             # a top-level phase ...
            with PH.phase("dispatch.wait"):    # ... and a child span
                pass
    per = (time.perf_counter() - t0) / n
    PH.end(tok)
    assert len(tok.stmt.spans) == n + 1
    assert per < 50e-6, f"{per * 1e6:.1f}us per span"


def _spin_cpu(seconds):
    """Run on the CPU until this thread has used ``seconds`` of it."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("work,on_cpu", [
    (lambda: time.sleep(0.05), False),
    (lambda: _spin_cpu(0.02), True),
], ids=["sleep", "spin"])
def test_span_cpu_tells_work_from_waiting(work, on_cpu):
    """The root's CPU time is its thread's: a sleep inside the statement
    reads as off-CPU (wall far above CPU), a busy loop as on-CPU."""
    tok = PH.begin()
    with PH.phase("bind"):
        work()
    PH.end(tok)
    wall, cpu = tok.stmt.spans[0][2], tok.stmt.cpu_us
    assert 0.0 <= cpu <= wall and tok.stmt.wait_cpu_us == 0.0
    if on_cpu:
        assert cpu >= 20000.0                       # us
    else:
        assert wall >= 50000.0 and cpu < 5000.0


def test_wait_cpu_counts_each_designed_wait_once():
    """``wait_cpu_us`` is the CPU inside the spans that wait by design,
    a wait inside another counted once; work outside them is not in
    it, and a wait cut after the fact carries none."""
    tok = PH.begin()
    with PH.phase("bind"):
        _spin_cpu(0.01)
    with PH.phase("dispatch.wait"):
        _spin_cpu(0.01)
        with PH.phase("dispatch.fetch"):
            _spin_cpu(0.03)
    PH.add("coalesce.ride", 0.5)
    PH.end(tok)
    st = tok.stmt
    assert 40000.0 <= st.wait_cpu_us < 55000.0          # us: not 70 ms
    assert st.wait_cpu_us + 10000.0 <= st.cpu_us <= st.spans[0][2]


def test_cpu_and_gc_reach_the_record_when_the_root_closes():
    """A record published while its root is open holds the three keys
    null, and gets them when the root closes; one published after the
    close gets them at once. No span row grows a column."""
    root = PH.open_root("http.request")
    tok = PH.begin()
    with PH.phase("bind"):
        PH.add("tier.fault", 0.001)
    PH.end(tok)
    early = {}
    tok.stmt.publish(early)
    assert early == {"cpu_us": None, "wait_cpu_us": None, "gc": None}
    PH.close_root(root)
    late = {}
    tok.stmt.publish(late)
    assert early == late == {"cpu_us": root.cpu_us, "gc": root.gc,
                             "wait_cpu_us": root.wait_cpu_us}
    assert set(late) == {"cpu_us", "wait_cpu_us", "gc"}
    assert late["gc"].keys() == {"ms", "collections", "max_gen"}
    assert 0.0 <= late["cpu_us"] <= root.spans[0][2]
    assert all(len(sp) == 4 for sp in root.spans)


def test_gc_hook_waits_for_a_statement_with_spans(monkeypatch):
    """The ``gc.callbacks`` hook goes in with the first statement that
    has spans, once; with spans off the process runs without it."""
    hooked = PH._on_gc in gc.callbacks
    if hooked:
        gc.callbacks.remove(PH._on_gc)
    monkeypatch.setattr(PH, "_gc_hooked", False)
    try:
        PH.end(PH.begin(enabled=False))
        assert PH._on_gc not in gc.callbacks
        PH.end(PH.begin())
        PH.end(PH.begin())
        assert gc.callbacks.count(PH._on_gc) == 1
    finally:
        while PH._on_gc in gc.callbacks:
            gc.callbacks.remove(PH._on_gc)
        if hooked:
            gc.callbacks.append(PH._on_gc)


@contextlib.contextmanager
def _no_automatic_gc():
    """Only the collections a test forces."""
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def test_gc_on_another_thread_lands_in_the_statement_it_stalls():
    """A collection forced on another thread during a statement is in
    that record's ``gc``; one outside every statement is in none."""
    junk = [[i] for i in range(100_000)]        # something to traverse
    with _no_automatic_gc():
        gc.collect()                            # outside: before
        tok = PH.begin()
        t = threading.Thread(target=gc.collect)
        t.start()
        t.join(60)
        assert not t.is_alive()
        PH.end(tok)
        gc.collect()                            # outside: between
        after = PH.begin()
        PH.end(after)
    stalled, clean = tok.stmt.gc, after.stmt.gc
    assert stalled["collections"] == 1 and stalled["max_gen"] == 2
    assert 0.0 < stalled["ms"] <= tok.stmt.spans[0][2] / 1000.0
    assert clean == {"ms": 0.0, "collections": 0, "max_gen": None}
    del junk


def test_gc_overlap_counts_only_the_part_inside():
    """The ring's arithmetic: a collection straddling the root's start
    counts its part inside, one that ended before it counts not."""
    PH.end(PH.begin())                  # the hook is in
    with _no_automatic_gc():
        gc.collect()
        start, end, gen = PH._gc_ring[(PH._gc_n - 1) % PH._GC_RING]
        mid = (start + end) // 2
        assert PH._gc_overlap(end, end + 10**9)["collections"] == 0
        half = PH._gc_overlap(mid, end + 10**9)
        assert half["collections"] == 1 and half["max_gen"] == gen == 2
        assert half["ms"] == pytest.approx((end - mid) / 1e6)


# -- 2/3. session stats contract + memo --------------------------------------

def _sales_df(n=2000):
    r = np.random.default_rng(7)
    return pd.DataFrame({
        "ts": pd.date_range("2024-01-01", periods=n, freq="min"),
        "region": r.choice(["east", "west", "north"], n),
        "qty": r.integers(1, 50, n),
        "price": r.uniform(1.0, 9.0, n),
    })


Q = ("SELECT region, SUM(qty) AS total FROM sales "
     "GROUP BY region ORDER BY region")


@pytest.fixture()
def ctx():
    c = sdot.Context({"sdot.cache.enabled": False,
                      "sdot.plan.cache.enabled": False})
    c.ingest_dataframe("sales", _sales_df(), time_column="ts")
    try:
        yield c
    finally:
        c.close()


def _last_stats(c):
    return c.history.entries()[-1].stats


def test_stats_phases_contract(ctx):
    ctx.sql(Q)
    st = _last_stats(ctx)
    ph = st["phases"]
    assert set(ph) <= set(PH.PHASES), set(ph) - set(PH.PHASES)
    # the cold cascade must actually show up, end to end (cache.lookup
    # is absent here — the fixture runs with the result cache off)
    for name in ("plan.memo", "plan.window", "plan.resolve", "plan.build",
                 "wlm.admit", "bind", "dispatch"):
        assert name in ph, (name, ph)
    assert all(v >= 0.0 for v in ph.values())
    assert st["plan_memo"] == {"hit": False}
    ctx.config.set("sdot.cache.enabled", True)
    ctx.sql(Q)
    assert "cache.lookup" in _last_stats(ctx)["phases"]


def test_phases_disabled_by_config(ctx):
    ctx.config.set("sdot.phases.enabled", False)
    ctx.sql(Q)
    assert "phases" not in _last_stats(ctx)


def test_memo_hit_skips_planning_phases(ctx):
    # a test-unique statement: the parse memo is process-global (keyed
    # on SQL text), so Q parsed by another test would hide the cold
    # "parse" phase this test pins down
    q = Q.replace("AS total", "AS total_memo")
    r1 = ctx.sql(q)
    cold = _last_stats(ctx)
    r2 = ctx.sql(q)
    warm = _last_stats(ctx)
    assert cold["plan_memo"] == {"hit": False}
    assert warm["plan_memo"] == {"hit": True}
    # plan cache is OFF — the skips below are the memo's own doing
    for name in ("plan.window", "plan.resolve", "plan.rewrite",
                 "plan.build"):
        assert name in cold["phases"], name
        assert name not in warm["phases"], (name, warm["phases"])
    # parse is memoized too: the warm rep never re-runs the parser
    assert "parse" in cold["phases"]
    assert "parse" not in warm["phases"]
    # execution still happened (memo serves plans, not results)
    assert "dispatch" in warm["phases"]
    np.testing.assert_array_equal(r1.data["total_memo"],
                                  r2.data["total_memo"])


def test_memo_disabled_replans_every_time(ctx):
    ctx.config.set("sdot.plan.memo.enabled", False)
    ctx.sql(Q)
    ctx.sql(Q)
    st = _last_stats(ctx)
    assert "plan_memo" not in st
    assert "plan.build" in st["phases"]      # cascade re-ran


def test_memo_invalidated_by_ingest(ctx):
    ctx.sql(Q)
    ctx.sql(Q)
    assert _last_stats(ctx)["plan_memo"] == {"hit": True}
    ctx.ingest_dataframe("sales", _sales_df(500), time_column="ts")
    ctx.sql(Q)
    assert _last_stats(ctx)["plan_memo"] == {"hit": False}


def test_memo_invalidated_by_semantic_config_flip(ctx):
    ctx.sql(Q)
    ctx.sql(Q)
    assert _last_stats(ctx)["plan_memo"] == {"hit": True}
    # sdot.join.enabled is semantic (in the config fingerprint); the
    # flip changes no answer for this single-table aggregate
    ctx.config.set("sdot.join.enabled", False)
    ctx.sql(Q)
    assert _last_stats(ctx)["plan_memo"] == {"hit": False}
    # an operational (semantic=False) flip must NOT invalidate
    ctx.sql(Q)
    assert _last_stats(ctx)["plan_memo"] == {"hit": True}
    ctx.config.set("sdot.phases.enabled", True)
    ctx.sql(Q)
    assert _last_stats(ctx)["plan_memo"] == {"hit": True}


def test_memo_invalidated_by_clear_metadata(ctx):
    other = _sales_df(100)
    ctx.ingest_dataframe("other", other, time_column="ts")
    ctx.sql(Q)
    ctx.sql(Q)
    assert _last_stats(ctx)["plan_memo"] == {"hit": True}
    # dropping ANY datasource bumps the store version the memo key folds
    ctx.sql("CLEAR METADATA other")
    ctx.sql(Q)
    assert _last_stats(ctx)["plan_memo"] == {"hit": False}


def test_memo_invalidated_by_rollup_ddl(ctx):
    ctx.sql(Q)
    ctx.sql(Q)
    assert _last_stats(ctx)["plan_memo"] == {"hit": True}
    ctx.sql("CREATE ROLLUP sales_cube ON sales DIMENSIONS (region) "
            "AGGREGATIONS (sum(qty), count(*)) GRANULARITY day")
    ctx.sql(Q)
    st = _last_stats(ctx)
    assert st["plan_memo"] == {"hit": False}
    # the re-plan is what lets the fresh rollup engage at all
    assert str(st.get("rollup", "")).startswith("rollup:")


def test_negative_outcomes_are_memoized(ctx):
    """A statement the builder rejects (host fallback) must also plan
    only once: the second run replays the negative outcome from the
    memo without re-running the rewrite/build phases."""
    neg = ("SELECT region, SUM(qty) / (SELECT MAX(price) FROM sales "
           "WHERE region = s.region) AS odd FROM sales s "
           "GROUP BY region, qty, price ORDER BY region LIMIT 3")
    r1 = ctx.sql(neg)
    cold = _last_stats(ctx)
    r2 = ctx.sql(neg)
    warm = _last_stats(ctx)
    assert warm["plan_memo"] == {"hit": True}
    if str(cold["mode"]).startswith("host"):
        assert str(warm["mode"]).startswith("host")
    assert "plan.build" not in warm["phases"]
    np.testing.assert_array_equal(r1.data["odd"], r2.data["odd"])


# -- 5. the record's span tree ------------------------------------------------

CHILD_SPANS = {"sql", "http.request", "http.accept", "http.read",
               "http.encode",
               "http.write", "dispatch.launch", "dispatch.wait",
               "dispatch.fetch"}


def _check_record(st):
    """What holds for every record: the tree is well formed, the flat
    view is the root's children, no child span is a key of it, and it
    never exceeds the statement's wall time."""
    spans, ph = st["spans"], st["phases"]
    assert spans[0][3] == -1 and spans[0][0] in ("sql", "http.request")
    assert {sp[0] for sp in spans} <= set(PH.PHASES)
    assert all(0 <= sp[3] < i for i, sp in enumerate(spans) if i)
    assert not set(ph) & CHILD_SPANS, ph
    top = {sp[0] for sp in spans[1:] if sp[3] == 0 and sp[2] is not None}
    assert set(ph) <= top, (set(ph) - top)
    for i, sp in enumerate(spans):
        if sp[0].startswith("dispatch."):
            assert spans[sp[3]][0] == "dispatch", (sp, spans[sp[3]])
    assert st["total_ms"] - sum(ph.values()) >= -0.05, (st["total_ms"], ph)
    assert isinstance(st["t0_ns"], int)


def _children(spans, name):
    """[[child names] for every span called ``name``]."""
    return [[c[0] for c in spans if c[3] == i]
            for i, sp in enumerate(spans) if sp[0] == name]


def test_span_rule_dense_statement(ctx):
    ctx.sql(Q)
    ctx.sql(Q)                                  # warm: no compile, memo
    st = _last_stats(ctx)
    _check_record(st)
    assert st["spans"][0][0] == "sql"           # no server, no handler
    assert st["spans"][0][2] is not None        # end() closed the root
    for name in ("plan.engine", "bind", "dispatch", "decode"):
        assert name in st["phases"], (name, st["phases"])
    assert _children(st["spans"], "dispatch") == [
        ["dispatch.launch", "dispatch.wait", "dispatch.fetch"]]
    assert st["fetch_bytes"] > 0 and st["n_dispatch"] == 1
    assert "query_id" not in st                 # ctx.sql was given none


def test_span_rule_hashed_statement(ctx):
    """q3's shape: a group-by whose key space takes the hashed tier."""
    ctx.config.set("sdot.engine.groupby.dense.max.keys", 64)
    q = ("SELECT region, qty, SUM(price) AS rev FROM sales "
         "GROUP BY region, qty ORDER BY rev DESC LIMIT 10")
    ctx.sql(q, query_id="hashed-1")
    ctx.sql(q, query_id="hashed-2")
    st = _last_stats(ctx)
    _check_record(st)
    assert st["mode"] == "engine" and st.get("hashed"), st
    assert st["query_id"] == "hashed-2"
    for name in ("plan.engine", "dispatch", "merge", "decode"):
        assert name in st["phases"], (name, st["phases"])
    kids = _children(st["spans"], "dispatch")
    assert len(kids) == st["n_dispatch"] >= 1
    for k in kids:                              # an overlapped bind may
        assert [c for c in k if c != "bind"] == [   # sit inside a wave's
            "dispatch.launch", "dispatch.wait", "dispatch.fetch"], kids


# One wave pipeline (QueryEngine._waves) runs every tier, so every tier
# leaves the SAME tree: per program launch one ``dispatch`` with exactly
# one launch, wait and fetch; wave i+1 binds inside wave i's ``dispatch``
# (the transfer overlaps the compute); one wave binds before its launch.

LWF = ["dispatch.launch", "dispatch.wait", "dispatch.fetch"]
LBWF = ["dispatch.launch", "bind", "dispatch.wait", "dispatch.fetch"]
WAVE_TIERS = {
    # tier: (settings, statements fired together, a second program a wave)
    "dense": ({}, [Q], False),
    "hashed_direct": ({"sdot.engine.groupby.dense.max.keys": 8},
                      ["SELECT region, qty, SUM(price) AS rev FROM sales "
                       "GROUP BY region, qty"], False),
    "hashed_table": ({"sdot.engine.groupby.dense.max.keys": 8,
                      "sdot.engine.groupby.hash.compact.min.slots": 1},
                     ["SELECT region, qty, SUM(price) AS rev FROM sales "
                      "GROUP BY region, qty"], True),
    "fused_group": ({"sdot.sharedscan.enabled": True,
                     "sdot.wlm.batch.window.ms": 2000},
                    ["SELECT region, SUM(qty) AS q, COUNT(*) AS n "
                     "FROM sales GROUP BY region",
                     "SELECT SUM(price) AS p FROM sales WHERE qty < 24"],
                    False),
}


@pytest.mark.parametrize("waves", [1, 3])
@pytest.mark.parametrize("tier", sorted(WAVE_TIERS))
def test_every_tier_leaves_the_same_dispatch_tree(tier, waves):
    settings, sqls, second = WAVE_TIERS[tier]
    c = sdot.Context({"sdot.cache.enabled": False, **settings})
    if waves > 1:       # a budget under one segment: a wave a segment
        c.config.set("sdot.engine.wave.max.bytes", 1)
    c.ingest_dataframe("sales", _sales_df(3 * 1024), time_column="ts",
                       target_rows=1024)
    try:
        for _ in range(2):                      # cold, then warm
            c.history.clear()
            barrier = threading.Barrier(len(sqls))

            def fire(sql):
                barrier.wait()
                c.sql(sql)
            ts = [threading.Thread(target=fire, args=(q,)) for q in sqls]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        recs = [r.stats for r in c.history.entries()]
    finally:
        c.close()
    assert len(recs) == len(sqls)
    for st in recs:
        _check_record(st)
    st = max(recs, key=lambda st: st["n_dispatch"])   # the group's leader
    assert st["waves"] == waves, st
    if tier == "fused_group":
        assert st["sharedscan"]["queries"] == len(sqls), st["sharedscan"]
    else:
        assert bool(st.get("hashed")) == tier.startswith("hashed"), st
    want = []
    for i in range(waves):
        want.append(LBWF if i + 1 < waves else LWF)
        if second:
            want.append(LWF)
    kids = _children(st["spans"], "dispatch")
    assert kids == want, kids
    assert len(kids) == st["n_dispatch"]


@contextlib.contextmanager
def _interpret_env():
    """The wave kernel through ``pl.pallas_call(interpret=True)``, for
    the storm only (test_pallas_wave._interpret_env says why)."""
    old = os.environ.get("SDOT_PALLAS")
    os.environ["SDOT_PALLAS"] = "interpret"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("SDOT_PALLAS", None)
        else:
            os.environ["SDOT_PALLAS"] = old


TILES = [
    "SELECT region, SUM(qty) AS q, COUNT(*) AS n FROM sales GROUP BY region",
    "SELECT region, SUM(price) AS p FROM sales WHERE qty >= 10 "
    "GROUP BY region",
    "SELECT SUM(qty) AS q FROM sales WHERE region = 'east'",
    "SELECT SUM(price) AS p, COUNT(*) AS n FROM sales WHERE qty < 24",
    "SELECT region, MAX(qty) AS m FROM sales GROUP BY region",
    "SELECT region, SUM(qty) AS q FROM sales WHERE region <> 'west' "
    "GROUP BY region",
    "SELECT COUNT(*) AS n FROM sales WHERE qty >= 40",
    "SELECT region, MIN(price) AS lo FROM sales GROUP BY region",
]


@pytest.fixture(scope="module")
def storm_records():
    """One refresh of eight tiles fired together at a coalescing
    context: the eight history records of ONE fused group."""
    c = sdot.Context({"sdot.cache.enabled": False,
                      "sdot.sharedscan.enabled": True,
                      "sdot.wlm.batch.window.ms": 2000})
    c.ingest_dataframe("sales", _sales_df(), time_column="ts")
    errs = []

    def fire(sql, barrier):
        try:
            barrier.wait()
            c.sql(sql)
        except BaseException as e:  # noqa: BLE001 — asserted below
            errs.append(e)

    try:
        with _interpret_env():
            for _ in range(2):                  # cold, then warm
                c.history.clear()
                barrier = threading.Barrier(len(TILES))
                ts = [threading.Thread(target=fire, args=(q, barrier))
                      for q in TILES]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
        assert not errs, errs
        return [r.stats for r in c.history.entries()]
    finally:
        c.close()


def test_span_rule_fused_group(storm_records):
    assert len(storm_records) == len(TILES)
    gids = {st["sharedscan"]["group"] for st in storm_records}
    assert len(gids) == 1 and all(
        st["sharedscan"]["queries"] == len(TILES) for st in storm_records)
    for st in storm_records:
        _check_record(st)
        assert "coalesce.hold" in st["phases"], st["phases"]


def test_fused_leader_dispatches_followers_ride(storm_records):
    leaders = [st for st in storm_records
               if st["sharedscan"]["role"] == "leader"]
    followers = [st for st in storm_records
                 if st["sharedscan"]["role"] == "follower"]
    assert len(leaders) == 1 and len(followers) == len(TILES) - 1
    lead = leaders[0]
    # the repair: the single-wave launch is inside a dispatch phase
    for name in ("coalesce.hold", "coalesce.plan", "bind", "dispatch",
                 "demux"):
        assert name in lead["phases"], (name, lead["phases"])
    assert "coalesce.ride" not in lead["phases"]
    assert _children(lead["spans"], "dispatch") == [
        ["dispatch.launch", "dispatch.wait", "dispatch.fetch"]]
    assert lead["n_dispatch"] == 1 and lead["fetch_bytes"] > 0
    order = [sp[0] for sp in lead["spans"] if sp[3] == 0
             and sp[0] in ("coalesce.hold", "coalesce.plan", "dispatch")]
    assert order == ["coalesce.hold", "coalesce.plan", "dispatch"]
    close_ns = []
    for st in followers:
        names = {sp[0] for sp in st["spans"]}
        assert {"coalesce.hold", "coalesce.ride"} <= set(st["phases"])
        assert not names & {"dispatch", "dispatch.launch", "coalesce.plan",
                            "bind", "demux"}, names
        assert st["n_dispatch"] == 0 and st["fetch_bytes"] == 0
        hold = next(sp for sp in st["spans"] if sp[0] == "coalesce.hold")
        ride = next(sp for sp in st["spans"] if sp[0] == "coalesce.ride")
        assert hold[1] + hold[2] == pytest.approx(ride[1], abs=1.0)
        close_ns.append(st["t0_ns"] + ride[1] * 1e3)
    # every member's hold ends at the group's one close, the leader's too
    hold = next(sp for sp in lead["spans"] if sp[0] == "coalesce.hold")
    close_ns.append(lead["t0_ns"] + (hold[1] + hold[2]) * 1e3)
    assert max(close_ns) - min(close_ns) < 1e6, close_ns   # within 1 ms


@pytest.fixture()
def served(ctx):
    from spark_druid_olap_tpu.server.http import SqlServer
    srv = SqlServer(ctx, "127.0.0.1", 0).start(background=True)
    try:
        yield srv
    finally:
        srv.stop()


def _post_sql(port, sql, **extra):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sql",
        data=json.dumps({"sql": sql, **extra}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def _history(port):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/history",
                                timeout=60) as resp:
        return json.loads(resp.read())["history"]


def _completed(port, qid):
    """The record of ``qid`` once its handler has closed the root (the
    client has its answer a moment before the handler's last line)."""
    for _ in range(200):
        rec = next(r for r in _history(port) if r.get("query_id") == qid)
        if rec["spans"][0][2] is not None:
            return rec
        time.sleep(0.01)
    raise AssertionError(f"root of {qid} never closed: {rec['spans']}")


def test_query_id_in_record_equals_response(served):
    minted = _post_sql(served.port, Q)["queryId"]
    given = _post_sql(served.port, Q, queryId="dash-7.tile-3")["queryId"]
    assert given == "dash-7.tile-3" and minted != given
    ids = [r.get("query_id") for r in _history(served.port)]
    assert ids[-2:] == [minted, given]


def test_http_root_and_its_children(served):
    _post_sql(served.port, Q)
    qid = _post_sql(served.port, Q)["queryId"]
    rec = _completed(served.port, qid)
    _check_record(rec)
    spans = rec["spans"]
    assert spans[0][0] == "http.request"
    top = [sp[0] for sp in spans if sp[3] == 0]
    assert top[:2] == ["http.accept", "http.read"]
    assert top[-2:] == ["http.encode", "http.write"]
    assert "dispatch" in top and "decode" in top
    assert not any(k.startswith("http.") for k in rec["phases"])
    # total_ms is the session's: the handler's spans lie around it
    read, enc = spans[2], next(sp for sp in spans if sp[0] == "http.encode")
    assert (enc[1] - (read[1] + read[2])) / 1000.0 >= rec["total_ms"] - 0.05
    assert spans[0][2] >= enc[1] + enc[2]


def test_root_cpu_at_most_wall_served_and_not(served, ctx):
    """The record's ``cpu_us`` is the root's CPU, never above its wall,
    and ``wait_cpu_us`` is part of it — under the server and without."""
    qid = _post_sql(served.port, Q)["queryId"]
    ctx.sql(Q)
    for rec in (_completed(served.port, qid), _last_stats(ctx)):
        wall = rec["spans"][0][2]
        assert wall is not None and rec["gc"]["collections"] >= 0
        assert 0.0 <= rec["wait_cpu_us"] <= rec["cpu_us"] <= wall


class _Stamps(dict):
    """The server's accept stamps, counting every one written."""

    def __init__(self):
        super().__init__()
        self.written = 0

    def __setitem__(self, sock, ns):
        self.written += 1
        super().__setitem__(sock, ns)


@pytest.mark.parametrize("on", [True, False], ids=["spans_on", "spans_off"])
def test_accept_stamped_only_with_spans_on(served, ctx, on):
    """The accept is stamped, and the record gets its CPU and GC keys,
    only while spans are on: with them off the server's path is the
    parent's."""
    served._httpd.accepted = stamps = _Stamps()
    ctx.config.set("sdot.phases.enabled", on)
    try:
        _post_sql(served.port, Q)
    finally:
        ctx.config.set("sdot.phases.enabled", True)
    rec = ctx.history.entries()[-1].stats
    assert stamps.written == int(on) and not stamps
    keys = {"spans", "cpu_us", "wait_cpu_us", "gc"}
    assert keys & set(rec) == (keys if on else set())


def test_profiler_capture_carries_the_spans(served, tmp_path):
    """Any ``jax.profiler`` capture of the process shows the spans as
    ``sdot:<name>`` host events with ``qid`` and ``t0_ns`` stats, and
    ``t0_ns`` (the span's own ``perf_counter_ns``) is one fixed offset
    from the capture's clock — the anchor that lays a record's spans
    onto the device lines of the same trace."""
    _post_sql(served.port, Q)                   # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        qids = [_post_sql(served.port, Q)["queryId"] for _ in range(3)]
        rec = _completed(served.port, qids[-1])
    finally:
        jax.profiler.stop_trace()
    files = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert files, list(tmp_path.rglob("*"))
    data = jax.profiler.ProfileData.from_file(str(files[0]))
    events = [(ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
              for plane in data.planes for line in plane.lines
              for ev in line.events if ev.name.startswith("sdot:")]
    waits = [e for e in events if e[0] == "sdot:dispatch.wait"]
    assert {e[3]["qid"] for e in waits} == set(qids), waits
    assert {e[0] for e in events} >= {
        "sdot:http.request", "sdot:http.encode", "sdot:http.write",
        "sdot:bind", "sdot:dispatch", "sdot:dispatch.launch",
        "sdot:dispatch.fetch", "sdot:decode"}
    # (but for a thread descheduled between its clock read and the
    # annotation's, on a loaded machine: the outer twentieth is left out)
    offsets = sorted(e[3]["t0_ns"] - e[1] for e in events)
    trim = len(offsets) // 20
    core = offsets[trim:len(offsets) - trim]
    assert core[-1] - core[0] < 1e6, (core[0], core[-1])
    # and the record's spans are those events: same start, same length
    off = core[len(core) // 2]
    # (the root and http.read begin before the body names the query)
    mine = {}                                   # name -> events in order
    for e in sorted((e for e in events if e[3].get("qid") == qids[-1]),
                    key=lambda e: e[1]):
        mine.setdefault(e[0], []).append(e)
    assert len(mine) >= 8, sorted(mine)
    for name, start_us, dur_us, _ in rec["spans"][1:]:
        evs = mine.get("sdot:" + name)
        if not evs:                             # stashed / add(): no event
            continue
        ev = evs.pop(0)                         # a name may repeat (result)
        assert ev[1] + off == pytest.approx(
            rec["t0_ns"] + start_us * 1e3, abs=1e6), name
        assert ev[2] == pytest.approx(dur_us * 1e3, abs=1e6), name


# -- 4. decode-ahead tiered serves --------------------------------------------

QUERIES = (Q,
           "SELECT region, COUNT(*) AS n, SUM(price) AS rev FROM sales "
           "GROUP BY region ORDER BY region")


def test_decode_ahead_saves_decode_time_bit_identical(tmp_path):
    root = str(tmp_path / "enc")
    seed = sdot.Context({"sdot.persist.path": root,
                         "sdot.encode.enabled": True})
    seed.ingest_dataframe("sales", _sales_df(20_000), time_column="ts",
                          target_rows=4096)
    seed.checkpoint("sales")
    seed.close()

    eager = sdot.Context({"sdot.persist.path": root})
    want = [eager.sql(q) for q in QUERIES]
    eager.close()

    # device-array cache off: every pass re-binds from the tier, so the
    # second pass actually exercises the demand-serve path under test
    ctx = sdot.Context({"sdot.persist.path": root,
                        "sdot.cache.enabled": False,
                        "sdot.plan.cache.enabled": False,
                        "sdot.engine.device.cache.bytes": 0,
                        "sdot.tier.enabled": True,
                        "sdot.tier.budget.bytes": 1 << 20,
                        "sdot.tier.wave.io.bytes": 1 << 18})
    try:
        for _ in range(2):
            got = [ctx.sql(q) for q in QUERIES]
            for w, g in zip(want, got):
                assert list(w.columns) == list(g.columns)
                for c in w.columns:
                    np.testing.assert_array_equal(w.data[c], g.data[c])
        st = ctx.persist.tier.stats_snapshot()
        assert st["decoded_budget_bytes"] > 0
        # the second pass served already-decoded chunks: the demand path
        # skipped real decode work, and the saving is measured
        assert st["decode_ms_saved"] > 0.0, st
        assert st["decoded_cache_bytes"] <= st["decoded_budget_bytes"]
        # decoded-side accounting never pollutes the encoded hot set
        assert st["hot_bytes"] <= st["budget_bytes"]
    finally:
        ctx.close()


def test_decoded_cache_disabled_by_zero_budget(tmp_path):
    root = str(tmp_path / "enc0")
    seed = sdot.Context({"sdot.persist.path": root,
                         "sdot.encode.enabled": True})
    seed.ingest_dataframe("sales", _sales_df(8_000), time_column="ts",
                          target_rows=4096)
    seed.checkpoint("sales")
    seed.close()
    ctx = sdot.Context({"sdot.persist.path": root,
                        "sdot.cache.enabled": False,
                        "sdot.tier.enabled": True,
                        "sdot.tier.budget.bytes": 1 << 20,
                        "sdot.tier.decoded.cache.bytes": 0})
    try:
        for _ in range(2):
            ctx.sql(Q)
        st = ctx.persist.tier.stats_snapshot()
        assert st["decoded_budget_bytes"] == 0
        assert st["decode_ms_saved"] == 0.0
        assert st["decoded_cache_entries"] == 0
    finally:
        ctx.close()
