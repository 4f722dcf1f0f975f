"""Plain pandas references for the TPC-H power classes.

``oracle_q1/q3/q5/q6/q12`` are copies of ``tests/test_tpch.py`` (pandas
merges over the generated base tables; here each table is projected to
the columns the query names before it is merged), ``ref_acd`` of
``chip_smoke.py``.
None of them calls the engine, the planner or ``host_exec``. Each takes
``data``: the generated frames by datasource name (base tables, the
nation/region views, ``tpch_flat``) and returns the frame a client
should receive, columns in the statement's order.
"""

import numpy as np
import pandas as pd


def _cols(data, table, *cols):
    """A projection before a merge: the merged frame then carries these
    columns, not the table's comments and addresses (same rows, same
    answers, a fifth of the time at SF1)."""
    return data[table][list(cols)]


def _rev(df):
    return df.l_extendedprice * (1 - df.l_discount)


def oracle_q1(data):
    li = data["lineitem"]
    li = li[li.l_shipdate <= pd.Timestamp("1998-12-01")
            - pd.Timedelta(days=90)]
    disc = _rev(li)
    charge = disc * (1 + li.l_tax)
    df = li.assign(disc_price=disc, charge=charge)
    res = df.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"))
    return res.sort_values(["l_returnflag", "l_linestatus"]) \
        .reset_index(drop=True)


def oracle_q3(data):
    df = (_cols(data, "customer", "c_custkey", "c_mktsegment")
          .merge(_cols(data, "orders", "o_orderkey", "o_custkey",
                       "o_orderdate", "o_shippriority"),
                 left_on="c_custkey", right_on="o_custkey")
          .merge(_cols(data, "lineitem", "l_orderkey", "l_shipdate",
                       "l_extendedprice", "l_discount"),
                 left_on="o_orderkey", right_on="l_orderkey"))
    df = df[(df.c_mktsegment == "BUILDING")
            & (df.o_orderdate < pd.Timestamp("1995-03-15"))
            & (df.l_shipdate > pd.Timestamp("1995-03-15"))]
    df = df.assign(revenue=_rev(df))
    res = df.groupby(["o_orderkey", "o_orderdate", "o_shippriority"],
                     as_index=False).revenue.sum()
    res = res.sort_values(["revenue", "o_orderdate"],
                          ascending=[False, True]).head(10)
    return res[["o_orderkey", "revenue", "o_orderdate",
                "o_shippriority"]].reset_index(drop=True)


def oracle_q5(data):
    df = (_cols(data, "customer", "c_custkey")
          .merge(_cols(data, "orders", "o_orderkey", "o_custkey",
                       "o_orderdate"),
                 left_on="c_custkey", right_on="o_custkey")
          .merge(_cols(data, "lineitem", "l_orderkey", "l_suppkey",
                       "l_extendedprice", "l_discount"),
                 left_on="o_orderkey", right_on="l_orderkey")
          .merge(_cols(data, "supplier", "s_suppkey", "s_nationkey"),
                 left_on="l_suppkey", right_on="s_suppkey")
          .merge(_cols(data, "suppnation", "sn_nationkey", "sn_name",
                       "sn_regionkey"),
                 left_on="s_nationkey", right_on="sn_nationkey")
          .merge(_cols(data, "suppregion", "sr_regionkey", "sr_name"),
                 left_on="sn_regionkey", right_on="sr_regionkey"))
    df = df[(df.sr_name == "ASIA")
            & (df.o_orderdate >= pd.Timestamp("1994-01-01"))
            & (df.o_orderdate < pd.Timestamp("1995-01-01"))]
    df = df.assign(revenue=_rev(df))
    res = df.groupby("sn_name", as_index=False).revenue.sum()
    return res.sort_values("revenue", ascending=False) \
        .reset_index(drop=True)


def oracle_q6(data):
    li = data["lineitem"]
    # the literals as the statement spells them; l_discount is two-decimal
    # (chip_smoke.ref_q6: 0.07 as a float64 is not the stored 0.07)
    disc = li.l_discount.round(2)
    li = li[(li.l_shipdate >= pd.Timestamp("1994-01-01"))
            & (li.l_shipdate < pd.Timestamp("1995-01-01"))
            & (disc >= 0.05) & (disc <= 0.07) & (li.l_quantity < 24)]
    return pd.DataFrame(
        {"revenue": [float((li.l_extendedprice * li.l_discount).sum())]})


def oracle_q12(data):
    df = _cols(data, "orders", "o_orderkey", "o_orderpriority").merge(
        _cols(data, "lineitem", "l_orderkey", "l_shipmode",
              "l_receiptdate"),
        left_on="o_orderkey", right_on="l_orderkey")
    df = df[df.l_shipmode.isin(["MAIL", "SHIP"])
            & (df.l_receiptdate >= pd.Timestamp("1994-01-01"))
            & (df.l_receiptdate < pd.Timestamp("1995-01-01"))]
    high = df.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
    df = df.assign(high_line_count=high.astype(np.int64),
                   low_line_count=(~high).astype(np.int64))
    res = df.groupby("l_shipmode", as_index=False).agg(
        high_line_count=("high_line_count", "sum"),
        low_line_count=("low_line_count", "sum"))
    return res.sort_values("l_shipmode").reset_index(drop=True)


def ref_acd(data):
    g = data["lineitem"].groupby("l_shipmode", sort=True)
    return pd.DataFrame({"parts": g.l_partkey.nunique().astype(np.int64),
                         "n": g.size().astype(np.int64)}).reset_index()
