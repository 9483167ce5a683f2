"""TPC-H Q6, forecast revenue change: one filter -> aggregate over lineitem.
`build` is the engine's query as `spark_rapids_tpu.tpch.q6` words it;
`reference` is the same question in plain numpy on exact integer cents."""
import datetime as pydt
import decimal

import numpy as np
import pyarrow as pa

from data.tpch_gen import days
from harness import columns as C

SOURCE_COLUMNS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                               "l_extendedprice"]}
D_LO = days(pydt.date(1994, 1, 1))
D_HI = days(pydt.date(1995, 1, 1))


def build(session, tables):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.plan import expressions as E
    from spark_rapids_tpu.plan.aggregates import Sum
    from spark_rapids_tpu.session import col
    li = session.from_arrow(tables["lineitem"])
    cond = E.And(
        E.And(E.GreaterThanOrEqual(col("l_shipdate"),
                                   E.Literal(D_LO, T.DATE)),
              E.LessThan(col("l_shipdate"), E.Literal(D_HI, T.DATE))),
        E.And(E.And(E.GreaterThanOrEqual(col("l_discount"),
                                         E.Literal(decimal.Decimal("0.05"))),
                    E.LessThanOrEqual(col("l_discount"),
                                      E.Literal(decimal.Decimal("0.07")))),
              E.LessThan(col("l_quantity"),
                         E.Literal(decimal.Decimal("24")))))
    revenue = E.Multiply(col("l_extendedprice"), col("l_discount"))
    return li.filter(cond).agg((Sum(revenue), "revenue"))


def reference(tables, money=np.int64):
    li = tables["lineitem"]
    ship = C.ints(li["l_shipdate"])
    disc = C.cents(li["l_discount"], money)
    qty = C.cents(li["l_quantity"], money)
    price = C.cents(li["l_extendedprice"], money)
    keep = ((ship >= D_LO) & (ship < D_HI) & (disc >= 5) & (disc <= 7)
            & (qty < 2400))
    revenue = (price[keep] * disc[keep]).sum()
    return pa.table({"revenue": C.decimals([revenue], 4)})


def needed_bytes(tables, answer):
    return C.needed_bytes(tables, SOURCE_COLUMNS, answer)
