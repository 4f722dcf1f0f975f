"""The client's side of the served path (``chip_smoke.post_sql`` with
the body kept raw: it is parsed and checked after the window)."""

import json
import time
import urllib.error
import urllib.request

import pandas as pd


def post_sql(port, sql, timeout=900, **extra):
    """POST /sql; returns (t0, t1, status, body bytes). The clock stops
    when the whole body has been read. A refusal or an error comes back
    as its status, never as an exception."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sql",
        data=json.dumps({"sql": sql, **extra}).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            body, status = resp.read(), resp.status
    except urllib.error.HTTPError as e:
        body, status = e.read(), e.code
    except OSError as e:
        body, status = repr(e).encode(), 0
    return t0, time.perf_counter(), status, body


def body_frame(body):
    doc = json.loads(body)
    return pd.DataFrame(doc["rows"], columns=doc["columns"])


def history(port):
    """GET /history: the last records (the program keeps 500), oldest
    first."""
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/history",
                                timeout=60) as resp:
        return json.loads(resp.read())["history"]
