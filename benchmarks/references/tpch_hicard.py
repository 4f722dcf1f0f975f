"""Plain pandas references for TPC-H's ranking reports Q10, Q15, Q18 with
the spec's validation parameters (cl. 2.4.10.3, 2.4.15.3, 2.4.18.3).

``oracle_q10`` is a copy of ``tests/test_tpch.py``, ``oracle_q15`` and
``oracle_q18`` of ``tests/test_tpch22.py`` (pandas merges over the
generated base tables; here each table is projected to the columns the
statement names before it is merged, and Q18 keeps the large orders'
rows before its merge instead of after it: the same rows, the same
answer, without a 6 M-row frame of strings).
None of them calls the engine, the planner or ``host_exec``. Each takes
``data``: the generated frames by datasource name (base tables, the
nation/region views, ``tpch_flat``) and returns the frame a client
should receive, columns in the statement's order.
"""

import pandas as pd


def _cols(data, table, *cols):
    """A projection before a merge: the merged frame then carries these
    columns, not the table's comments and addresses."""
    return data[table][list(cols)]


def _rev(df):
    return df.l_extendedprice * (1 - df.l_discount)


def oracle_q10(data):
    o = _cols(data, "orders", "o_orderkey", "o_custkey", "o_orderdate")
    o = o[(o.o_orderdate >= pd.Timestamp("1993-10-01"))
          & (o.o_orderdate < pd.Timestamp("1994-01-01"))]
    li = _cols(data, "lineitem", "l_orderkey", "l_returnflag",
               "l_extendedprice", "l_discount")
    li = li[li.l_returnflag == "R"]
    df = (_cols(data, "customer", "c_custkey", "c_name", "c_acctbal",
                "c_phone", "c_nationkey")
          .merge(o, left_on="c_custkey", right_on="o_custkey")
          .merge(li, left_on="o_orderkey", right_on="l_orderkey")
          .merge(_cols(data, "custnation", "cn_nationkey", "cn_name"),
                 left_on="c_nationkey", right_on="cn_nationkey"))
    df = df.assign(revenue=_rev(df))
    res = df.groupby(["c_custkey", "c_name", "c_acctbal", "c_phone",
                      "cn_name"], as_index=False).revenue.sum()
    res = res.sort_values("revenue", ascending=False).head(20)
    return res[["c_custkey", "c_name", "revenue", "c_acctbal", "cn_name",
                "c_phone"]].reset_index(drop=True)


def oracle_q15(data):
    li = _cols(data, "lineitem", "l_suppkey", "l_shipdate",
               "l_extendedprice", "l_discount")
    li = li[(li.l_shipdate >= pd.Timestamp("1996-01-01"))
            & (li.l_shipdate < pd.Timestamp("1996-04-01"))]
    rev = _rev(li).groupby(li.l_suppkey).sum()
    sel = rev[rev == rev.max()].reset_index()
    sel.columns = ["s_suppkey", "total_revenue"]
    res = _cols(data, "supplier", "s_suppkey", "s_name", "s_address",
                "s_phone").merge(sel, on="s_suppkey")
    return res[["s_suppkey", "s_name", "s_address", "s_phone",
                "total_revenue"]].sort_values("s_suppkey") \
        .reset_index(drop=True)


def oracle_q18(data, thresh=300):
    li = _cols(data, "lineitem", "l_orderkey", "l_quantity")
    big = li.groupby("l_orderkey").l_quantity.sum()
    big = big[big > thresh].index
    o = _cols(data, "orders", "o_orderkey", "o_custkey", "o_orderdate",
              "o_totalprice")
    df = (_cols(data, "customer", "c_custkey", "c_name")
          .merge(o[o.o_orderkey.isin(big)],
                 left_on="c_custkey", right_on="o_custkey")
          .merge(li[li.l_orderkey.isin(big)],
                 left_on="o_orderkey", right_on="l_orderkey"))
    res = df.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                      "o_totalprice"], as_index=False).l_quantity.sum()
    res = res.rename(columns={"l_quantity": "total_qty"})
    return res.sort_values(["o_totalprice", "o_orderdate"],
                           ascending=[False, True]).head(100) \
        .reset_index(drop=True)
