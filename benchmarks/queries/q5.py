"""TPC-H Q5, local supplier volume (ASIA, 1994): a five-way join with the
supplier's nation tied to the customer's."""
import datetime as pydt

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from data.tpch_gen import days
from harness import columns as C

SOURCE_COLUMNS = {
    "region": ["r_regionkey", "r_name"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "customer": ["c_custkey", "c_nationkey"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"],
}
D_LO = days(pydt.date(1994, 1, 1))
D_HI = days(pydt.date(1995, 1, 1))


def build(session, tables):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.plan import expressions as E
    from spark_rapids_tpu.plan.aggregates import Sum
    from spark_rapids_tpu.session import col
    region = session.from_arrow(tables["region"]).filter(
        E.EqualTo(col("r_name"), E.Literal("ASIA")))
    nation = session.from_arrow(tables["nation"])
    cust = session.from_arrow(tables["customer"])
    supp = session.from_arrow(tables["supplier"])
    orders = session.from_arrow(tables["orders"]).filter(
        E.And(E.GreaterThanOrEqual(col("o_orderdate"),
                                   E.Literal(D_LO, T.DATE)),
              E.LessThan(col("o_orderdate"), E.Literal(D_HI, T.DATE))))
    li = session.from_arrow(tables["lineitem"])
    j = (region.join(nation, left_on=["r_regionkey"],
                     right_on=["n_regionkey"])
         .join(cust, left_on=["n_nationkey"], right_on=["c_nationkey"])
         .join(orders, left_on=["c_custkey"], right_on=["o_custkey"])
         .join(li, left_on=["o_orderkey"], right_on=["l_orderkey"]))
    # l_suppkey must match a supplier in the same nation:
    j = j.join(supp, left_on=["l_suppkey"], right_on=["s_suppkey"]) \
        .filter(E.EqualTo(col("s_nationkey"), col("n_nationkey")))
    revenue = E.Multiply(col("l_extendedprice"),
                         E.Subtract(E.Literal(1), col("l_discount")))
    return (j.group_by("n_name").agg((Sum(revenue), "revenue"))
            .sort(("revenue", False, False)))


def reference(tables, money=np.int64):
    region, nation = tables["region"], tables["nation"]
    cust, supp = tables["customer"], tables["supplier"]
    orders, li = tables["orders"], tables["lineitem"]
    asia = C.ints(region["r_regionkey"])[pc.equal(
        region["r_name"], "ASIA").to_numpy(zero_copy_only=False)]
    n_key = C.ints(nation["n_nationkey"])
    n_in_asia = np.isin(C.ints(nation["n_regionkey"]), asia)
    # nation row of every customer / supplier, then of every order
    c_nat = C.lookup(n_key, C.ints(cust["c_nationkey"]))
    s_nat = C.lookup(n_key, C.ints(supp["s_nationkey"]))
    o_cust = C.lookup(C.ints(cust["c_custkey"]), C.ints(orders["o_custkey"]))
    o_date = C.ints(orders["o_orderdate"])
    o_nat = np.where(o_cust >= 0, c_nat[o_cust], -1)
    o_keep = ((o_date >= D_LO) & (o_date < D_HI) & (o_nat >= 0)
              & n_in_asia[o_nat])
    l_ord = C.lookup(C.ints(orders["o_orderkey"]), C.ints(li["l_orderkey"]))
    l_sup = C.lookup(C.ints(supp["s_suppkey"]), C.ints(li["l_suppkey"]))
    keep = (l_ord >= 0) & (l_sup >= 0)
    keep &= o_keep[l_ord] & (s_nat[l_sup] == o_nat[l_ord])
    revenue = (C.cents(li["l_extendedprice"], money)
               * (100 - C.cents(li["l_discount"], money)))[keep]
    nat, sums = C.group_sum(o_nat[l_ord][keep], revenue)
    sums = [C.whole(v) for v in sums]
    names = nation["n_name"].to_pylist()
    top = sorted(range(len(nat)), key=lambda i: -sums[i])
    return pa.table({
        "n_name": pa.array([names[nat[i]] for i in top], pa.string()),
        "revenue": C.decimals([sums[i] for i in top], 4),
    })


def needed_bytes(tables, answer):
    return C.needed_bytes(tables, SOURCE_COLUMNS, answer)
