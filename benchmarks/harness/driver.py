"""The one load driver. A traffic generator's ``schedule(params,
classes, rng)`` returns sessions; a session is an iterator of rounds
``{"due_s": None | seconds from the window's start, "sends": [class,
...]}``. A session fires a round's statements together (one thread
each, released by a barrier), waits for every answer, then takes its
next round. ``due_s`` None is a closed loop; with a due instant the
session waits for it, latency runs from the due instant and the
lateness of the send is recorded — an open loop is a generator that
gives due instants, not a change here."""

import contextlib
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from . import client

MAX_BURST = 1024     # statements one round may fire together


class Driver:
    def __init__(self, port, classes, lane=None):
        self.port = port
        self.classes = classes
        self.extra = {"lane": lane} if lane else {}
        self.annotate = None        # name -> context manager, when traced

    def _span(self, name):
        return self.annotate(name) if self.annotate \
            else contextlib.nullcontext()

    def _send(self, cls, barrier, due):
        barrier.wait()
        with self._span("client:" + cls):
            t0, t1, status, body = client.post_sql(
                self.port, self.classes[cls]["sql"], **self.extra)
        start = t0 if due is None else due
        return {"cls": cls, "sql": self.classes[cls]["sql"],
                "t0": start, "t1": t1,
                "ms": (t1 - start) * 1000.0,
                "late_ms": (t0 - start) * 1000.0,
                "status": status, "body": body, "ok": status == 200}

    def run_round(self, pool, rnd, t_start):
        due = None if rnd.get("due_s") is None else t_start + rnd["due_s"]
        if due is not None:
            time.sleep(max(0.0, due - time.perf_counter()))
        barrier = threading.Barrier(len(rnd["sends"]))
        futs = [pool.submit(self._send, cls, barrier, due)
                for cls in rnd["sends"]]
        return [f.result() for f in futs]

    def _session(self, session, t_start, t_end, out, between, failed):
        try:
            with ThreadPoolExecutor(max_workers=MAX_BURST) as pool:
                for rnd in session:
                    if time.perf_counter() >= t_end:
                        break
                    out.extend(self.run_round(pool, rnd, t_start))
                    if between is not None:
                        with self._span("between_rounds"):
                            between(len(out))
        except BaseException as e:  # noqa: BLE001 — re-raised by run()
            failed.append(e)

    def run(self, sessions, seconds, between=None):
        """Drive every session for ``seconds``; a round that has started
        is finished. ``between(n)`` is called by the FIRST session after
        each of its rounds with the number of answers it has so far (the
        traced run's slice control and history reads). Returns (samples, t_start, t_end of the last answer)."""
        t_start = time.perf_counter()
        t_end = t_start + seconds
        outs, failed = [[] for _ in sessions], []
        threads = [threading.Thread(
            target=self._session,
            args=(s, t_start, t_end, outs[i], between if i == 0 else None,
                  failed))
            for i, s in enumerate(sessions)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if failed:
            raise failed[0]
        samples = [s for out in outs for s in out]
        return samples, t_start, max([s["t1"] for s in samples],
                                     default=time.perf_counter())

    def warm(self, sessions, times=2):
        """Rounds of the first session until every class has been sent
        ``times`` times; returns the samples."""
        seen = {c: 0 for c in self.classes}
        out = []
        with ThreadPoolExecutor(max_workers=MAX_BURST) as pool:
            for rnd in sessions[0]:
                out.extend(self.run_round(
                    pool, {**rnd, "due_s": None}, 0.0))
                for cls in rnd["sends"]:
                    seen[cls] += 1
                if min(seen.values()) >= times:
                    return out
        return out
