"""Plain pandas references for the qgen statement pool
(``statements/tpch_qgen16.json``): the oracles of ``references/tpch.py``
with the spec's substitution parameters as arguments, and one module
attribute per class (``q3_p1`` = ``oracle_q3`` with that class's
``params``), so ``"reference": "tpch_qgen:q3_p1"`` resolves like any
other. None of them calls the engine, the planner or ``host_exec``.

The references of every statement set are written at every first run of
a seed, in every cell, so this file is on every cell's set-up. The draws
of q3, q5 and q12 differ in the predicates on the base tables, so each
table is cut by its predicate BEFORE the merge — merging everything
first and filtering the 6 M-row result thrice cost 32 s a store build
where this costs 7 (PERF.md §6, PR 28). q1's draws differ only in the
last ship day they take: lineitem is grouped by day once (``_q1_days``)
and a draw adds up the days it keeps.
"""

import functools
import json
import os
import sys
import types
import weakref

import numpy as np
import pandas as pd

_HERE = os.path.dirname(os.path.abspath(__file__))
# The harness executes this file anew for every class it resolves
# (``registry.load_module``), so what q1's draws share is held outside
# the module object: the per-day sums (a few thousand rows) of the
# lineitem frame they were made from, which is only weakly referenced.
_SHARED = sys.modules.setdefault(
    "benchmarks_references_tpch_qgen_shared",
    types.SimpleNamespace(lineitem=lambda: None, q1_days=None))


def _cols(data, table, *cols):
    return data[table][list(cols)]


def _rev(df):
    return df.l_extendedprice * (1 - df.l_discount)


def _year_after(date):
    return pd.Timestamp(date) + pd.DateOffset(years=1)


def _q1_days(data):
    """q1's sums per (flag, status, ship day): its draws differ only in
    the last ship day they take, so lineitem is grouped once (the group
    keys as categories: grouping 6 M strings is most of q1's reference
    otherwise) and a draw adds up the days it keeps."""
    if _SHARED.lineitem() is data["lineitem"]:
        return _SHARED.q1_days
    li = _cols(data, "lineitem", "l_returnflag", "l_linestatus",
               "l_quantity", "l_extendedprice", "l_discount", "l_tax",
               "l_shipdate")
    disc = _rev(li)
    df = li.assign(disc_price=disc, charge=disc * (1 + li.l_tax),
                   l_returnflag=li.l_returnflag.astype("category"),
                   l_linestatus=li.l_linestatus.astype("category"))
    _SHARED.q1_days = df.groupby(
        ["l_returnflag", "l_linestatus", "l_shipdate"],
        as_index=False, observed=True).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        sum_disc=("l_discount", "sum"),
        count_order=("l_quantity", "size"))
    _SHARED.lineitem = weakref.ref(data["lineitem"])
    return _SHARED.q1_days


def oracle_q1(data, delta):
    days = _q1_days(data)
    days = days[days.l_shipdate <= pd.Timestamp("1998-12-01")
                - pd.Timedelta(days=delta)]
    res = days.drop(columns="l_shipdate").groupby(
        ["l_returnflag", "l_linestatus"], as_index=False,
        observed=True).sum()
    res = res.assign(avg_qty=res.sum_qty / res.count_order,
                     avg_price=res.sum_base_price / res.count_order,
                     avg_disc=res.sum_disc / res.count_order)
    keys = data["lineitem"].dtypes[["l_returnflag", "l_linestatus"]]
    res = res.astype(keys.to_dict())        # categories back to strings
    return res[["l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
                "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
                "avg_disc", "count_order"]] \
        .sort_values(["l_returnflag", "l_linestatus"]) \
        .reset_index(drop=True)


# q3, q5, q12: each table is cut by its own predicate BEFORE the merge
# (the draws differ in exactly those predicates, so what they share is
# the projection of each table)

def _q3_tables(data):
    return (_cols(data, "customer", "c_custkey", "c_mktsegment"),
            _cols(data, "orders", "o_orderkey", "o_custkey",
                  "o_orderdate", "o_shippriority"),
            _cols(data, "lineitem", "l_orderkey", "l_shipdate",
                  "l_extendedprice", "l_discount"))


def oracle_q3(data, segment, date):
    c, o, li = _q3_tables(data)
    day = pd.Timestamp(date)
    df = (c[c.c_mktsegment == segment]
          .merge(o[o.o_orderdate < day],
                 left_on="c_custkey", right_on="o_custkey")
          .merge(li[li.l_shipdate > day],
                 left_on="o_orderkey", right_on="l_orderkey"))
    df = df.assign(revenue=_rev(df))
    res = df.groupby(["o_orderkey", "o_orderdate", "o_shippriority"],
                     as_index=False).revenue.sum()
    res = res.sort_values(["revenue", "o_orderdate"],
                          ascending=[False, True]).head(10)
    return res[["o_orderkey", "revenue", "o_orderdate",
                "o_shippriority"]].reset_index(drop=True)


def _q5_tables(data):
    return (_cols(data, "customer", "c_custkey"),
            _cols(data, "orders", "o_orderkey", "o_custkey", "o_orderdate"),
            _cols(data, "lineitem", "l_orderkey", "l_suppkey",
                  "l_extendedprice", "l_discount"),
            _cols(data, "supplier", "s_suppkey", "s_nationkey"),
            _cols(data, "suppnation", "sn_nationkey", "sn_name",
                  "sn_regionkey"),
            _cols(data, "suppregion", "sr_regionkey", "sr_name"))


def oracle_q5(data, region, date):
    c, o, li, s, n, r = _q5_tables(data)
    o = o[(o.o_orderdate >= pd.Timestamp(date))
          & (o.o_orderdate < _year_after(date))]
    supp = (r[r.sr_name == region]
            .merge(n, left_on="sr_regionkey", right_on="sn_regionkey")
            .merge(s, left_on="sn_nationkey", right_on="s_nationkey"))
    df = (c.merge(o, left_on="c_custkey", right_on="o_custkey")
          .merge(li, left_on="o_orderkey", right_on="l_orderkey")
          .merge(supp, left_on="l_suppkey", right_on="s_suppkey"))
    df = df.assign(revenue=_rev(df))
    res = df.groupby("sn_name", as_index=False).revenue.sum()
    return res.sort_values("revenue", ascending=False) \
        .reset_index(drop=True)


def oracle_q6(data, date, discount, quantity):
    li = data["lineitem"]
    # the bounds as the statement spells them, two decimals; l_discount is
    # two-decimal too (0.07 as a float64 is not the stored 0.07)
    disc = li.l_discount.round(2)
    lo, hi = round(discount - 0.01, 2), round(discount + 0.01, 2)
    li = li[(li.l_shipdate >= pd.Timestamp(date))
            & (li.l_shipdate < _year_after(date))
            & (disc >= lo) & (disc <= hi) & (li.l_quantity < quantity)]
    return pd.DataFrame(
        {"revenue": [float((li.l_extendedprice * li.l_discount).sum())]})


def _q12_tables(data):
    return (_cols(data, "orders", "o_orderkey", "o_orderpriority"),
            _cols(data, "lineitem", "l_orderkey", "l_shipmode",
                  "l_receiptdate"))


def oracle_q12(data, shipmode1, shipmode2, date):
    o, li = _q12_tables(data)
    li = li[(li.l_receiptdate >= pd.Timestamp(date))
            & (li.l_receiptdate < _year_after(date))]
    li = li[li.l_shipmode.isin([shipmode1, shipmode2])]
    df = o.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    high = df.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
    df = df.assign(high_line_count=high.astype(np.int64),
                   low_line_count=(~high).astype(np.int64))
    res = df.groupby("l_shipmode", as_index=False).agg(
        high_line_count=("high_line_count", "sum"),
        low_line_count=("low_line_count", "sum"))
    return res.sort_values("l_shipmode").reset_index(drop=True)


ORACLES = {"q1": oracle_q1, "q3": oracle_q3, "q5": oracle_q5,
           "q6": oracle_q6, "q12": oracle_q12}


def _bind_classes():
    with open(os.path.join(_HERE, "..", "statements",
                           "tpch_qgen16.json")) as f:
        classes = json.load(f)["classes"]
    for cls, st in classes.items():
        if "template" in st:
            globals()[cls] = functools.partial(ORACLES[st["template"]],
                                               **st["params"])


_bind_classes()
