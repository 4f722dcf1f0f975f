"""The comparison that decides ``correct`` (``chip_smoke.check_frames``,
made independent of the server's encoder: a reference's date column is
compared as dates, whatever spelling the response gives them)."""

import numpy as np
import pandas as pd


class Mismatch(AssertionError):
    pass


def _check(cond, msg):
    if not cond:
        raise Mismatch(msg)


def check_frames(name, got, want, approx=(), rtol=1e-6, approx_rtol=0.05):
    """``got``: the response as parsed from JSON; ``want``: the stored
    reference. Integers, counts, strings and dates exact; float columns
    within ``rtol``; ``approx`` columns (sketch estimates) within
    ``approx_rtol``. Row order is not compared (rows are matched on the
    exact columns). Returns the largest relative error of a float
    column."""
    _check(list(got.columns) == list(want.columns),
           f"{name}: columns {list(got.columns)} != {list(want.columns)}")
    _check(len(got) == len(want), f"{name}: {len(got)} rows != {len(want)}")
    got, want = got.copy(), want.copy()
    for c in want.columns:
        if want[c].dtype.kind == "M":
            got[c] = pd.to_datetime(got[c], format="ISO8601") \
                .astype("datetime64[ns]")
            want[c] = want[c].astype("datetime64[ns]")
    # JSON prints a whole-valued float as an integer: a column is a
    # float column if EITHER side parsed as one
    floats = {c for c in want.columns
              if "f" in (got[c].dtype.kind, want[c].dtype.kind)}
    keys = [c for c in want.columns if c not in floats and c not in approx]
    if keys:
        got = got.sort_values(keys).reset_index(drop=True)
        want = want.sort_values(keys).reset_index(drop=True)
    worst = 0.0
    for c in want.columns:
        g, w = got[c].to_numpy(), want[c].to_numpy()
        if c in approx:
            ok = np.allclose(g.astype(float), w.astype(float),
                             rtol=approx_rtol)
        elif c in floats:
            g, w = g.astype(float), w.astype(float)
            ok = np.allclose(g, w, rtol=rtol, atol=0.0, equal_nan=True)
            with np.errstate(divide="ignore", invalid="ignore"):
                rel = np.abs(g - w) / np.abs(w)
            worst = max(worst, float(np.nanmax(np.where(w == 0, 0, rel),
                                               initial=0.0)))
        else:
            ok = np.array_equal(g, w)
        _check(ok, f"{name}: column {c!r} differs (rtol {rtol})\n"
                   f" got {g[:8]!r}\nwant {w[:8]!r}")
    return worst
