"""The four host-thread readers — ``accept_ms``, ``handler_cpu_ms``,
``offcpu_ms``, ``gc_pause_ms`` — on hand-written records: a solo
statement, a shared-scan follower, a statement whose inner
``dispatch.wait`` lies under a ``subquery``, a record read before its
root closed, and records from a program without the keys."""

import pytest

from harness import registry

T0_NS = 5_000_000_000_000


def rec(rows, cpu, wait_cpu, gc):
    return {"spans": rows, "cpu_us": cpu, "wait_cpu_us": wait_cpu,
            "gc": gc, "t0_ns": T0_NS, "total_ms": 0.0}


def solo():
    """10 ms from the accept, 4 ms on the CPU: the accept 0.5 ms,
    admission 1 ms, a dispatch whose wait is 3 ms and whose fetch is
    1 ms, 0.6 ms of the waits on the CPU (the fetch's unpack). Lost
    outside the waits: (10 - 4) - (1 + 3 + 1 - 0.6) = 1.6 ms."""
    return rec([
        ["http.request", 0.0, 10000.0, -1],
        ["http.accept", 0.0, 500.0, 0],
        ["http.read", 600.0, 100.0, 0],
        ["wlm.admit", 800.0, 1000.0, 0],
        ["dispatch", 2000.0, 4500.0, 0],
        ["dispatch.launch", 2000.0, 500.0, 4],
        ["dispatch.wait", 2500.0, 3000.0, 4],
        ["dispatch.fetch", 5500.0, 1000.0, 4],
        ["http.encode", 7000.0, 2000.0, 0],
    ], 4000.0, 600.0, {"ms": 0.0, "collections": 0, "max_gen": None})


def follower():
    """A follower parked 20 ms in hold + ride (cut after the fact: no
    CPU of their own), 30 ms of wall, 6 on the CPU: lost (30 - 6) - 20
    = 4 ms; a 2.5 ms gen-2 collection overlapped it."""
    return rec([
        ["http.request", 0.0, 30000.0, -1],
        ["http.accept", 0.0, 3000.0, 0],
        ["http.read", 3100.0, 100.0, 0],
        ["coalesce.hold", 3500.0, 8000.0, 0],
        ["coalesce.ride", 11500.0, 12000.0, 0],
        ["http.encode", 24000.0, 5000.0, 0],
    ], 6000.0, 0.0, {"ms": 2.5, "collections": 3, "max_gen": 2})


def subquery():
    """An inner's dispatch under ``subquery``: its 8 ms wait counts
    once, and the ``dispatch.fetch`` nested, oddly, under the wait is
    not counted again. 20 ms wall, 5 CPU, 0.5 of it in the wait: lost
    (20 - 5) - (8 - 0.5) = 7.5 ms."""
    return rec([
        ["http.request", 0.0, 20000.0, -1],
        ["http.accept", 0.0, 200.0, 0],
        ["plan.rewrite", 1000.0, 12000.0, 0],
        ["subquery", 2000.0, 10000.0, 0],
        ["dispatch", 3000.0, 9000.0, 3],
        ["dispatch.wait", 3000.0, 8000.0, 4],
        ["dispatch.fetch", 4000.0, 1000.0, 5],
    ], 5000.0, 500.0, {"ms": 0.5, "collections": 1, "max_gen": 0})


def still_open():
    """Read before the handler's last line: root open, its CPU and
    collections null until it closes."""
    r = solo()
    r["spans"][0][2] = None
    r.update(cpu_us=None, wait_cpu_us=None, gc=None)
    return r


OLD = {"spans": [["http.request", 0.0, 8000.0, -1],
                 ["http.read", 50.0, 250.0, 0]],
       "t0_ns": T0_NS, "total_ms": 6.0}        # no new keys
OLDER = {"phases": {"dispatch": 2.5}, "total_ms": 6.0}     # no tree

NAMES = ("accept_ms", "handler_cpu_ms", "offcpu_ms", "gc_pause_ms")


def metric(name, records):
    run = {"records": list(records), "samples": [], "pairs": [],
           "trace": None, "slice_s": None}
    return registry.load_module("metrics", name).compute(run)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("records", [[], [OLD], [OLD, OLDER]],
                         ids=["none", "old", "older"])
def test_none_without_the_keys(name, records):
    assert metric(name, records) is None


@pytest.mark.parametrize("name", NAMES[1:])
def test_an_open_root_is_left_out(name):
    """The root's CPU and collections arrive when it closes (its
    closed ``http.accept`` is read: ``test_accept_ms...``)."""
    assert metric(name, [still_open()]) is None


def test_accept_ms_is_the_span_median():
    assert metric("accept_ms", [solo(), follower(), subquery()]) \
        == pytest.approx(0.5)
    assert metric("accept_ms", [solo(), still_open(), OLD]) \
        == pytest.approx(0.5)          # the open root's span is closed


def test_handler_cpu_ms_is_the_root_cpu_mean():
    # (4 + 6 + 5) / 3; a tick-quantized clock leaves a median nothing
    assert metric("handler_cpu_ms", [solo(), follower(), follower()]) \
        == pytest.approx(16.0 / 3)
    assert metric("handler_cpu_ms", [follower(), still_open(), OLD]) \
        == pytest.approx(6.0)


@pytest.mark.parametrize("record,lost", [
    (solo, 1.6), (follower, 4.0), (subquery, 7.5)],
    ids=["solo", "follower", "subquery"])
def test_offcpu_ms_subtracts_the_designed_waits_only(record, lost):
    assert metric("offcpu_ms", [record()]) == pytest.approx(lost)
    assert metric("offcpu_ms", [record(), still_open(), OLD]) \
        == pytest.approx(lost)


def test_offcpu_ms_is_a_mean():
    # (1.6 + 1.6 + 7.5) / 3, where a median would read 1.6
    assert metric("offcpu_ms", [solo(), solo(), subquery()]) \
        == pytest.approx(10.7 / 3)


def test_gc_pause_ms_is_a_mean():
    # (0 + 2.5 + 0.5) / 3, where a median would read 0.5
    assert metric("gc_pause_ms", [solo(), follower(), subquery()]) \
        == pytest.approx(1.0)
    assert metric("gc_pause_ms", [solo(), still_open(), OLD]) == 0.0
