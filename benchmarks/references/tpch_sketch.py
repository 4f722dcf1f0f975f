"""Plain pandas references for the statement set ``tpch_sketch4``: uniques
per supplier, per customer and per part-supplier pair over the flattened
index — ``groupby(...).nunique()`` for what the statements estimate,
``.sum()`` / ``.size()`` for what they count exactly. The two top-N
statements rank by an exact measure, so their references take the 20 best
groups' revenue first and count uniques over those groups' rows alone:
the same numbers as a ``nunique`` over all 100 k / 800 k groups, in a
second instead of a minute.
None of them calls the engine, the planner or ``host_exec``. Each takes
``data``: the generated frames by datasource name and returns the frame a
client should receive, columns in the statement's order.
"""

import numpy as np
import pandas as pd


def _flat(data, *cols):
    return data["tpch_flat"][list(cols)]


def _uniques(df, keys, **columns):
    """One row a group of ``keys``: ``name=column`` -> its nunique."""
    g = df.groupby(keys, sort=True)
    return pd.DataFrame({name: g[col].nunique().astype(np.int64)
                         for name, col in columns.items()})


def ref_uq_supplier(data):
    df = _flat(data, "l_suppkey", "o_custkey", "l_partkey")
    res = _uniques(df, "l_suppkey", custs="o_custkey", parts="l_partkey")
    res["n"] = df.groupby("l_suppkey", sort=True).size().astype(np.int64)
    return res.reset_index()


def ref_uq_supplier_1995(data):
    df = _flat(data, "l_suppkey", "o_custkey", "l_shipdate")
    df = df[(df.l_shipdate >= pd.Timestamp("1995-01-01"))
            & (df.l_shipdate < pd.Timestamp("1996-01-01"))]
    res = _uniques(df, "l_suppkey", custs="o_custkey")
    res["n"] = df.groupby("l_suppkey", sort=True).size().astype(np.int64)
    return res.reset_index()


def _top_by_revenue(df, keys, n=20):
    """(the ``n`` groups of ``keys`` with the largest revenue, revenue
    descending; their rows of ``df``)."""
    rev = (df.l_extendedprice * (1 - df.l_discount)) \
        .groupby([df[k] for k in keys], sort=True).sum() \
        .rename("revenue").sort_values(ascending=False).head(n) \
        .reset_index()
    return rev, df.merge(rev[keys], on=keys)


def ref_uq_customer_top(data):
    df = _flat(data, "o_custkey", "l_extendedprice", "l_discount",
               "l_suppkey", "l_partkey")
    rev, rows = _top_by_revenue(df, ["o_custkey"])
    uq = _uniques(rows, "o_custkey", supps="l_suppkey", parts="l_partkey")
    return rev.merge(uq.reset_index(), on="o_custkey")[
        ["o_custkey", "revenue", "supps", "parts"]]


def ref_uq_partsupp_top(data):
    df = _flat(data, "l_partkey", "l_suppkey", "l_extendedprice",
               "l_discount", "o_custkey")
    keys = ["l_partkey", "l_suppkey"]
    rev, rows = _top_by_revenue(df, keys)
    uq = _uniques(rows, keys, custs="o_custkey")
    return rev.merge(uq.reset_index(), on=keys)[
        ["l_partkey", "l_suppkey", "revenue", "custs"]]
