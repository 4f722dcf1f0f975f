"""Per-statement span tree: the one mechanism that says where a
statement's host time went.

Every statement pays well under a millisecond of scan on the device;
everything else is host work spread across HTTP, parsing, a recognizer
cascade, caches, the shared-scan coalescer, binding, launch, wait,
fetch and decode.  This module attributes that time to *named spans*
with two monotonic-clock reads and one list append per span, cheap
enough to stay always-on (< 1% of wall, enforced by
tests/test_phases.py).

Usage::

    root = PH.open_root("http.request", accepted_ns=a)   # server only
    tok = PH.begin()                 # open the statement's accumulator
    with PH.phase("plan.build"):
        ...
    PH.add("tier.fault", seconds)    # pre-measured interval ending now
    with PH.lifted("subquery"):      # the root's child, wherever it runs
        ...
    phases = PH.end(tok)             # {"plan.build": ms, ...}
    tok.stmt.spans, tok.stmt.t0_ns   # the tree, for the record
    tok.stmt.publish(stats)          # CPU and GC: now or at the close
    PH.close_root(root)

Semantics:

- A statement is one tree of spans ``[name, start_us, dur_us, parent]``
  on ``time.perf_counter_ns()``: ``start_us`` counts from the root's
  start (``t0_ns``), ``parent`` is the index of the span that was open
  on the same thread when this one began, the root is index 0 with
  parent -1.  The root is ``http.request`` when the server's handler
  opened it (``open_root``) and ``sql`` when ``begin()`` had to.  A span
  still open has ``dur_us`` None; readers skip it.  A root the server
  opens starts where its accept returned (``accepted_ns``), and its
  first child ``http.accept`` covers the hand-off up to the handler's
  first line (the connection's thread started, request line and headers
  read), so the root's self time is what it was before.
- When the root closes, three record keys are written into the record
  the statement was ``publish``-ed to (null until then):
  ``cpu_us``, the thread's CPU time (``time.thread_time_ns()``) over
  the root — under the server from the start of the thread the accept
  began, so the clock is read once, at the close, after the answer was
  written; ``wait_cpu_us``, its CPU inside the spans that wait by
  design (``WAITS``: the device, the coalescer, admission), read at the
  open and close of each that ``phase()`` times, one inside another
  once (an ``add()`` wait was parked and counts none); and ``gc``,
  ``{"ms", "collections", "max_gen"}`` of the process's collections
  that overlap the root's interval.  Wall minus CPU is time the thread
  did not run: outside the designed waits, a GIL turn, a lock, the OS
  scheduler, a collection by another thread.  No other span reads the
  CPU clock: on a TPU v5e host a read is a system call, and there the
  clock advances in 10 ms ticks, so a short span's own reading says
  nothing and only a mean over many statements estimates CPU.
- A collection holds the GIL and so stalls every Python thread: one
  started on any thread counts.  A ``gc.callbacks`` hook, installed
  when the first statement with spans opens, keeps each collection's
  start, end and generation in a ring of ``_GC_RING`` entries; it
  writes no profiler annotation.  Its end is read in the hook's second
  call, which may first hand the GIL to a waiting thread: under
  contention a collection can read up to one switch interval long.
- ``stats["phases"]`` is the flat view ``{name: ms}`` of the spans that
  are *direct children of the root* while the accumulator is open.  A
  span nested in another (``dispatch.launch`` in ``dispatch``,
  ``plan.star`` in ``plan.build``, ``tier.fault`` in ``bind``) and a
  span outside ``begin()``..``end()`` (``http.*``) appear in the tree
  only, so the phases of a statement never overlap and
  ``total_ms - sum(phases)`` is never negative.
- ``lifted(name)`` is ``phase(name)`` for work that runs INSIDE a span
  it does not belong to: an inlined subquery executes while the outer
  statement's ``plan.rewrite`` is open.  Nothing is closed: the span's
  ``parent`` is the nearest open span of the same name, else the root,
  whatever was open when it began (so ``parent`` says what a span is
  part of; a lifted span begins and ends inside a sibling's interval).
  The first ``subquery`` of a statement is then a direct child of the
  root and a phase of its own, a subquery's own subquery nests inside
  it, and its time is taken out of the phase of the root's child it ran
  inside: ``phases["plan.rewrite"]`` is that span's duration minus the
  subqueries', planning only, and no time is counted twice.
- The state is thread-local.  ``begin()`` returns ``None`` when an
  accumulator is already open (nested query execution, e.g. a window
  statement re-entering the select path) — inner spans then land in the
  outer statement and the inner ``end(None)`` is a no-op.
- ``phase()``/``add()`` outside any open statement are no-ops, so
  background threads (tier prefetcher) and non-query entry points can
  share the instrumented call sites for free.
- ``stash(name, seconds)`` records time measured *before* the
  accumulator could be opened (statement parse happens before the
  select path begins); the next ``begin()`` on the same thread folds
  the stash in.  ``clear_stash()`` drops leftovers so one statement's
  parse can never leak into the next.
- Every ``phase()`` also enters a ``jax.profiler.TraceAnnotation``
  named ``"sdot:" + name`` carrying ``qid`` and ``t0_ns`` (the span's
  own ``perf_counter_ns`` start), so any profiler capture of the process
  shows the same spans on the clock of the device lines; with no
  capture running the annotation is a flag test.  ``add()`` and
  ``http.accept`` learn of their interval after the fact and write no
  annotation; the root's annotation begins where it was opened.

The ``PHASES`` registry below is the single source of truth for span
names; sdlint cross-checks every ``PH.phase("...")``/``PH.add("...")``
call site against it and against the docs/STATS.md phase table.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

# name -> one-line meaning (kept a pure literal: sdlint parses it)
PHASES = {
    "sql": "root of a statement run without the server (ctx.sql)",
    "http.request": "root of a POST /sql: accept to the handler's last line",
    "http.accept": "accept -> handler body: thread start, request headers",
    "http.read": "request body read + json.loads",
    "http.encode": "result -> DataFrame -> JSON rows / Arrow bytes",
    "http.write": "status line, headers and body written to the socket",
    "parse": "SQL text -> AST (memoized; counted when actually run)",
    "plan.memo": "planning-cascade memo lookup",
    "plan.window": "window-function extraction",
    "plan.resolve": "database/alias-scope/lookup resolution",
    "plan.rewrite": "derived-table merge, decorrelation, subquery inlining",
    "plan.build": "SELECT -> PlannedQuery spec build",
    "plan.rollup": "materialized-rollup rewrite match",
    "plan.star": "star-join collapse over the FROM list",
    "plan.join": "general-join recognition",
    "plan.composite": "composite (host-assist) plan build",
    "plan.engine": "engine-side aggregation plan (dims, routes, segments)",
    "subquery": "one execution of an inlined subquery / engine-assisted subtree",
    "wlm.admit": "workload-manager admission",
    "cache.lookup": "result-cache probe",
    "coalesce.hold": "shared scan: joining a group -> the group's close",
    "coalesce.ride": "shared scan follower: group close -> outcome delivered",
    "coalesce.plan": "shared scan leader: lanes, fusion plan, program key",
    "compile": "program build + jit (per signature, first run only)",
    "tier.fault": "tiered-store faults on the demand path",
    "tier.decode": "encoded-chunk decode on the demand path",
    "bind": "host->device array binding",
    "bind.operands": "filter literals -> the program's small operand",
    "dispatch": "device execution + result fetch",
    "dispatch.launch": "enqueue: the compiled program + its outputs' D2H",
    "dispatch.wait": "block_until_ready on what the launch returned",
    "dispatch.fetch": "rest of the device->host copy + unpack on the host",
    "merge": "hashed tier: cross-wave / cross-chip partial merge on host",
    "decode": "solo finals -> QueryResult (dictionary decode, epilogue)",
    "demux": "shared-scan per-lane demux/decode",
    "sketch": "a sketch column's host part: estimate of what was fetched",
    "result": "engine results -> the statement's frame (host finish)",
    "epilogue": "window post-pass and result epilogue",
}

_tls = threading.local()

# the spans that wait by design: their CPU is read (``wait_cpu_us``)
WAITS = frozenset(("dispatch.wait", "dispatch.fetch", "coalesce.hold",
                   "coalesce.ride", "wlm.admit"))

# the last _GC_RING collections of the process, in order:
# (start_ns, end_ns, generation) on perf_counter_ns; _gc_n counts them all
_GC_RING = 4096
_gc_ring: List[Optional[tuple]] = [None] * _GC_RING
_gc_n = 0
_gc_start = 0
_gc_hooked = False
_gc_lock = threading.Lock()


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ring entry a collection."""
    global _gc_n, _gc_start
    if phase == "start":
        _gc_start = time.perf_counter_ns()
    elif _gc_start:                     # not one begun before the hook
        _gc_ring[_gc_n % _GC_RING] = (_gc_start, time.perf_counter_ns(),
                                      info["generation"])
        _gc_n += 1
        _gc_start = 0


def _hook_gc() -> None:
    """Install ``_on_gc`` once, when the first statement opens."""
    global _gc_hooked
    with _gc_lock:
        if not _gc_hooked:
            gc.callbacks.append(_on_gc)
            _gc_hooked = True


def _gc_overlap(t0_ns: int, t1_ns: int) -> dict:
    """The ring's collections that overlap [t0_ns, t1_ns]: their
    overlap in ms, their count, the highest generation (None: none)."""
    ns, n, top = 0, 0, None
    i = _gc_n
    while i > max(0, _gc_n - _GC_RING):
        i -= 1
        start, end, gen = _gc_ring[i % _GC_RING]
        if end <= t0_ns:
            break                       # every older one ended earlier
        if start < t1_ns:
            ns += min(end, t1_ns) - max(start, t0_ns)
            n += 1
            top = gen if top is None else max(top, gen)
    return {"ms": ns / 1e6, "collections": n, "max_gen": top}


class _Stmt:
    """One statement's span tree; lives with the history record."""

    __slots__ = ("spans", "stack", "t0_ns", "qid", "acc", "note", "cpu0",
                 "wait_cpu_ns", "waiting", "cpu_us", "wait_cpu_us", "gc",
                 "recs")

    def __init__(self, name: str, qid: Optional[str], t0_ns: int,
                 cpu0: int, opened_ns: Optional[int] = None) -> None:
        if not _gc_hooked:
            _hook_gc()
        self.spans: List[list] = [[name, 0.0, None, -1]]
        self.stack = [0]
        self.t0_ns = t0_ns
        self.qid = qid
        self.acc: Optional[_Acc] = None     # the open accumulator
        self.cpu0 = cpu0                    # thread_time_ns at the start
        self.wait_cpu_ns = 0
        self.waiting = False                # a designed wait is open
        # the record's keys, None until the root closes
        self.cpu_us: Optional[float] = None
        self.wait_cpu_us: Optional[float] = None
        self.gc: Optional[dict] = None
        self.recs: List[dict] = []          # the records published to
        self.note = TraceAnnotation("sdot:" + name, qid=qid or "",
                                    t0_ns=opened_ns or t0_ns)
        self.note.__enter__()

    def publish(self, stats: dict) -> None:
        """Give the record ``stats`` the root's CPU and collections: now
        if the root has closed, else null until it closes."""
        self.recs.append(stats)
        self._fill(stats)

    def _fill(self, stats: dict) -> None:
        stats.update({"cpu_us": self.cpu_us,
                      "wait_cpu_us": self.wait_cpu_us, "gc": self.gc})

    def close(self) -> None:
        if self.spans[0][2] is None:
            cpu = time.thread_time_ns()
            end = time.perf_counter_ns()
            self.cpu_us = (cpu - self.cpu0) / 1e3
            self.wait_cpu_us = self.wait_cpu_ns / 1e3
            self.gc = _gc_overlap(self.t0_ns, end)
            for stats in self.recs:
                # first: a reader that sees the root closed finds them
                self._fill(stats)
            self.spans[0][2] = (end - self.t0_ns) / 1e3
            self.note.__exit__(None, None, None)
        if getattr(_tls, "st", None) is self:
            _tls.st = None


class _Acc(dict):
    """``{name: seconds}`` of one begin()..end(), the token of both."""

    __slots__ = ("stmt", "owns_root", "t0_ns")


def _acc() -> Optional[_Acc]:
    st = getattr(_tls, "st", None)
    return st.acc if st is not None else None


def open_root(name: str, qid: Optional[str] = None,
              accepted_ns: Optional[int] = None) -> Optional[_Stmt]:
    """Open a statement whose root span starts now (the server's
    handler); None when this thread already has one.

    ``accepted_ns``: the ``perf_counter_ns`` at which the server's
    accept returned the connection this thread was started for.  The
    root then starts there, its CPU counts from the thread's start (the
    clock is not read here), and its first child ``http.accept`` covers
    the accept up to now."""
    if getattr(_tls, "st", None) is not None:
        return None
    if accepted_ns is None:
        st = _tls.st = _Stmt(name, qid, time.perf_counter_ns(),
                             time.thread_time_ns())
        return st
    now = time.perf_counter_ns()
    st = _tls.st = _Stmt(name, qid, accepted_ns, 0, opened_ns=now)
    st.spans.append(["http.accept", 0.0, (now - accepted_ns) / 1e3, 0])
    return st


def close_root(st: Optional[_Stmt]) -> None:
    """Close the root opened by open_root(); ``close_root(None)`` and a
    second close are no-ops."""
    if st is not None:
        st.close()


def begin(enabled: bool = True, qid: Optional[str] = None) -> Optional[_Acc]:
    """Open a per-query accumulator; None if nested or disabled."""
    stash = getattr(_tls, "stash", None)
    _tls.stash = None
    if not enabled:
        return None
    st = getattr(_tls, "st", None)
    if st is not None and st.acc is not None:
        return None
    acc = _Acc()
    acc.owns_root = st is None
    # where the statement's own clock starts: a stashed parse began
    # before this call
    acc.t0_ns = time.perf_counter_ns()
    if stash:
        acc.t0_ns = min(acc.t0_ns, min(s for s, _ in stash.values()))
    if st is None:
        st = _tls.st = _Stmt("sql", qid, acc.t0_ns, time.thread_time_ns())
    elif qid is not None:
        st.qid = qid
    acc.stmt = st
    st.acc = acc
    if stash:
        for k, (start, dt) in stash.items():
            acc[k] = acc.get(k, 0.0) + dt
            st.spans.append([k, (start - st.t0_ns) / 1e3, dt * 1e6, 0])
    return acc


def end(tok: Optional[_Acc]) -> Optional[Dict[str, float]]:
    """Close the accumulator opened by begin(); returns {name: ms}.

    Idempotent and nested-safe: ``end(None)`` is a no-op returning
    None, and closing twice (finally blocks) is harmless.
    """
    if tok is None:
        return None
    if tok.stmt.acc is tok:
        tok.stmt.acc = None
        if tok.owns_root:
            tok.stmt.close()
    return {k: v * 1000.0 for k, v in tok.items()}


class _Phase:
    __slots__ = ("name", "st", "row", "t0", "note")

    def __init__(self, name: str) -> None:
        self.name = name
        self.st = None

    def __enter__(self) -> "_Phase":
        st = self.st = getattr(_tls, "st", None)
        if st is not None:
            self.t0 = t0 = time.perf_counter_ns()
            self.row = [self.name, (t0 - st.t0_ns) / 1e3, None,
                        st.stack[-1]]
            st.stack.append(len(st.spans))
            st.spans.append(self.row)
            self.note = TraceAnnotation("sdot:" + self.name,
                                        qid=st.qid or "", t0_ns=t0)
            self.note.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        st = self.st
        if st is not None:
            self.note.__exit__(None, None, None)
            dt = time.perf_counter_ns() - self.t0
            self.row[2] = dt / 1e3
            st.stack.pop()
            if self.row[3] == 0 and st.acc is not None:
                st.acc[self.name] = st.acc.get(self.name, 0.0) + dt / 1e9
            self.st = None


class _Wait(_Phase):
    """A phase in ``WAITS``: the thread's CPU inside it joins the
    statement's ``wait_cpu_us``, unless it opens inside another wait."""

    __slots__ = ("cpu0",)

    def __enter__(self) -> "_Wait":
        _Phase.__enter__(self)
        st = self.st
        self.cpu0 = None
        if st is not None and not st.waiting:
            st.waiting = True
            self.cpu0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> None:
        if self.cpu0 is not None:
            st = self.st
            st.wait_cpu_ns += time.thread_time_ns() - self.cpu0
            st.waiting = False
        _Phase.__exit__(self)


class _Lifted(_Phase):
    """A phase that is no part of the spans open around it (see
    ``lifted``)."""

    __slots__ = ()

    def __enter__(self) -> "_Lifted":
        _Phase.__enter__(self)
        st = self.st
        if st is not None:
            spans = st.spans
            self.row[3] = next((i for i in reversed(st.stack[:-1])
                                if spans[i][0] == self.name), 0)
        return self

    def __exit__(self, *exc) -> None:
        st = self.st
        _Phase.__exit__(self)       # books it when it is the root's child
        if st is not None and self.row[3] == 0 and st.acc is not None \
                and len(st.stack) > 1:
            # ... and takes it out of the root's child it ran inside
            outer = st.spans[st.stack[1]][0]
            st.acc[outer] = st.acc.get(outer, 0.0) - self.row[2] / 1e6


def phase(name: str) -> _Phase:
    """Context manager timing one span; no-op without an open statement."""
    return _Wait(name) if name in WAITS else _Phase(name)


def lifted(name: str) -> _Phase:
    """``phase(name)`` for work that runs inside spans it is no part of:
    it hangs under the nearest open span of its own name, else under the
    root, and its time leaves the phase it ran inside."""
    return _Lifted(name)


def add(name: str, seconds: float, end_ns: Optional[int] = None) -> None:
    """Fold a pre-measured interval into the open statement; it ends at
    ``end_ns`` (``perf_counter_ns``), now when None."""
    st = getattr(_tls, "st", None)
    if st is None:
        return
    if end_ns is None:
        end_ns = time.perf_counter_ns()
    parent = st.stack[-1]
    st.spans.append([name, (end_ns - st.t0_ns) / 1e3 - seconds * 1e6,
                     seconds * 1e6, parent])
    if parent == 0 and st.acc is not None:
        st.acc[name] = st.acc.get(name, 0.0) + seconds


def stash(name: str, seconds: float) -> None:
    """Record time measured before begin(); folded into the next one."""
    st = getattr(_tls, "stash", None)
    if st is None:
        st = {}
        _tls.stash = st
    start, dt = st.get(name, (time.perf_counter_ns() - int(seconds * 1e9),
                              0.0))
    st[name] = (start, dt + seconds)


def clear_stash() -> None:
    """Drop any pending stash (statement boundary)."""
    _tls.stash = None
