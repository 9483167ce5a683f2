"""TPC-H Q3, shipping priority: customer x orders x lineitem, group-by,
top 10.  Every join is foreign key -> primary key, which the reference
uses."""
import datetime as pydt

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from data.tpch_gen import days
from harness import columns as C

SOURCE_COLUMNS = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"],
}
DATE = days(pydt.date(1995, 3, 15))


def build(session, tables):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.plan import expressions as E
    from spark_rapids_tpu.plan.aggregates import Sum
    from spark_rapids_tpu.session import col
    cust = session.from_arrow(tables["customer"]).filter(
        E.EqualTo(col("c_mktsegment"), E.Literal("BUILDING")))
    orders = session.from_arrow(tables["orders"]).filter(
        E.LessThan(col("o_orderdate"), E.Literal(DATE, T.DATE)))
    li = session.from_arrow(tables["lineitem"]).filter(
        E.GreaterThan(col("l_shipdate"), E.Literal(DATE, T.DATE)))
    j = cust.join(orders, left_on=["c_custkey"], right_on=["o_custkey"]) \
        .join(li, left_on=["o_orderkey"], right_on=["l_orderkey"])
    revenue = E.Multiply(col("l_extendedprice"),
                         E.Subtract(E.Literal(1), col("l_discount")))
    return (j.group_by("o_orderkey", "o_orderdate", "o_shippriority")
            .agg((Sum(revenue), "revenue"))
            .sort(("revenue", False, False), ("o_orderdate", True, True))
            .limit(10))


def reference(tables, money=np.int64):
    cust, orders, li = tables["customer"], tables["orders"], tables["lineitem"]
    building = pc.equal(cust["c_mktsegment"], "BUILDING").to_numpy(
        zero_copy_only=False)
    o_cust = C.lookup(C.ints(cust["c_custkey"]), C.ints(orders["o_custkey"]))
    o_date = C.ints(orders["o_orderdate"])
    o_keep = (o_cust >= 0) & building[o_cust] & (o_date < DATE)
    o_key = C.ints(orders["o_orderkey"])
    l_ord = C.lookup(o_key, C.ints(li["l_orderkey"]))
    l_keep = (C.ints(li["l_shipdate"]) > DATE) & (l_ord >= 0) & o_keep[l_ord]
    revenue = (C.cents(li["l_extendedprice"], money)
               * (100 - C.cents(li["l_discount"], money)))[l_keep]
    rows, sums = C.group_sum(l_ord[l_keep], revenue)
    top = np.lexsort((o_date[rows], -sums))[:10]
    at = rows[top]
    return pa.table({
        "o_orderkey": pa.array(o_key[at], pa.int64()),
        "o_orderdate": pa.array(o_date[at].astype(np.int32),
                                pa.int32()).cast(pa.date32()),
        "o_shippriority": pa.array(
            C.ints(orders["o_shippriority"])[at], pa.int32()),
        "revenue": C.decimals([sums[i] for i in top], 4),
    })


def needed_bytes(tables, answer):
    return C.needed_bytes(tables, SOURCE_COLUMNS, answer)
