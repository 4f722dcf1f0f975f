"""Plain pandas references for the eight dashboard tiles (the SQL of
``chip_smoke.STORM``): filter-and-group-by over the flat frame, float64.
Each takes ``data`` (generated frames by datasource name) and returns
the frame a client should receive, columns in the statement's order."""

import numpy as np
import pandas as pd


def _aggs(g):
    """``sum(l_extendedprice) revenue, sum(l_quantity) units, count(*) n``
    of a groupby (or of a frame, for an ungrouped tile)."""
    if isinstance(g, pd.DataFrame):
        return pd.DataFrame({"revenue": [float(g.l_extendedprice.sum())],
                             "units": [int(g.l_quantity.sum())],
                             "n": [int(len(g))]})
    return pd.DataFrame({"revenue": g.l_extendedprice.sum(),
                         "units": g.l_quantity.sum().astype(np.int64),
                         "n": g.size().astype(np.int64)})


# the columns the tiles read: a filter then copies these, not the flat
# frame's seventy-odd (comments and addresses among them)
_COLS = ["c_mktsegment", "l_linestatus", "l_returnflag", "l_shipmode",
         "l_shipdate", "l_quantity", "l_extendedprice", "l_discount",
         "sn_name", "o_orderpriority"]


def _flat(data):
    return data["tpch_flat"][_COLS]


def _building(data):
    flat = _flat(data)
    return flat[flat.c_mktsegment == "BUILDING"]


def _ts(s):
    return pd.Timestamp(s)


def tile_groupby(data):
    return _aggs(_building(data).groupby("l_linestatus")).reset_index()


def tile_groupby_filtered(data):
    d = _building(data)
    return _aggs(d[d.l_returnflag == "R"].groupby("l_shipmode")) \
        .reset_index()


def tile_timeseries_year(data):
    d = _building(data)
    d = d[d.l_quantity >= 10]
    out = _aggs(d.groupby(d.l_shipdate.dt.year.rename("y"))).reset_index()
    return out.astype({"y": np.int64})


def tile_topn(data):
    out = _aggs(_building(data).groupby("sn_name")).reset_index()
    return out.sort_values("revenue", ascending=False).head(7) \
        .reset_index(drop=True)


def tile_q6_shape(data):
    flat = _flat(data)
    disc = flat.l_discount.round(2)
    d = flat[(flat.l_shipdate >= _ts("1994-01-01"))
             & (flat.l_shipdate < _ts("1995-01-01"))
             & (disc >= 0.05) & (disc <= 0.07) & (flat.l_quantity < 24)]
    return pd.DataFrame(
        {"revenue": [float((d.l_extendedprice * d.l_discount).sum())]})


def tile_kpi_total(data):
    d = _building(data)
    out = _aggs(d)
    out["dmin"] = float(d.l_discount.min())
    out["dmax"] = float(d.l_discount.max())
    return out


def tile_q1_shape(data):
    flat = _flat(data)
    d = flat[flat.l_shipdate <= _ts("1998-09-02")]
    g = d.groupby(["l_returnflag", "l_linestatus"])
    out = _aggs(g)
    out["avg_disc"] = g.l_discount.mean()
    return out.reset_index()


def tile_groupby_year_window(data):
    flat = _flat(data)
    d = flat[(flat.l_shipdate >= _ts("1995-01-01"))
             & (flat.l_shipdate < _ts("1996-01-01"))]
    return _aggs(d.groupby("o_orderpriority")).reset_index()
