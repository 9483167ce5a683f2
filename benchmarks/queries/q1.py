"""TPC-H Q1, pricing summary report: group-by over lineitem, eight
aggregates.  Not in a cell yet (PERF.md Open questions, row 1): adding it is
data only."""
import datetime as pydt

import numpy as np
import pyarrow as pa

from data.tpch_gen import days
from harness import columns as C

SOURCE_COLUMNS = {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                               "l_extendedprice", "l_discount", "l_tax",
                               "l_shipdate"]}
CUTOFF = days(pydt.date(1998, 12, 1)) - 90


def build(session, tables):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.plan import expressions as E
    from spark_rapids_tpu.plan.aggregates import Average, Count, Sum
    from spark_rapids_tpu.session import col
    li = session.from_arrow(tables["lineitem"])
    disc_price = E.Multiply(col("l_extendedprice"),
                            E.Subtract(E.Literal(1), col("l_discount")))
    charge = E.Multiply(disc_price, E.Add(E.Literal(1), col("l_tax")))
    return (li.filter(E.LessThanOrEqual(col("l_shipdate"),
                                        E.Literal(CUTOFF, T.DATE)))
            .group_by("l_returnflag", "l_linestatus")
            .agg((Sum(col("l_quantity")), "sum_qty"),
                 (Sum(col("l_extendedprice")), "sum_base_price"),
                 (Sum(disc_price), "sum_disc_price"),
                 (Sum(charge), "sum_charge"),
                 (Average(col("l_quantity")), "avg_qty"),
                 (Average(col("l_extendedprice")), "avg_price"),
                 (Average(col("l_discount")), "avg_disc"),
                 (Count(None), "count_order"))
            .sort("l_returnflag", "l_linestatus"))


def _average(total: int, count: int) -> int:
    """Spark's avg(decimal(12,2)) -> decimal(16,6), rounded half up; `total`
    is in cents, the result unscaled at 6 places."""
    q, r = divmod(abs(total) * 10_000, count)
    q += 2 * r >= count
    return q if total >= 0 else -q


def reference(tables, money=np.int64):
    li = tables["lineitem"]
    keep = C.ints(li["l_shipdate"]) <= CUTOFF
    flag = li["l_returnflag"].combine_chunks().dictionary_encode()
    status = li["l_linestatus"].combine_chunks().dictionary_encode()
    flags, statuses = flag.dictionary.to_pylist(), status.dictionary.to_pylist()
    group = (flag.indices.to_numpy().astype(np.int64) * len(statuses)
             + status.indices.to_numpy())[keep]
    qty = C.cents(li["l_quantity"], money)[keep]
    price = C.cents(li["l_extendedprice"], money)[keep]
    disc = C.cents(li["l_discount"], money)[keep]
    tax = C.cents(li["l_tax"], money)[keep]
    disc_price = price * (100 - disc)               # 4 decimal places
    charge = disc_price * (100 + tax)               # 6 decimal places
    ones = np.ones(len(group), np.int64)
    ids, count = C.group_sum(group, ones)
    sums = {name: [C.whole(v) for v in C.group_sum(group, vals)[1]]
            for name, vals in (("qty", qty), ("price", price), ("disc", disc),
                               ("disc_price", disc_price),
                               ("charge", charge))}
    rows = sorted(
        (flags[g // len(statuses)], statuses[g % len(statuses)], i)
        for i, g in enumerate(ids.tolist()))
    pick = [i for _f, _s, i in rows]
    n = [int(count[i]) for i in pick]
    return pa.table({
        "l_returnflag": pa.array([f for f, _s, _i in rows]),
        "l_linestatus": pa.array([s for _f, s, _i in rows]),
        "sum_qty": C.decimals([sums["qty"][i] for i in pick], 2),
        "sum_base_price": C.decimals([sums["price"][i] for i in pick], 2),
        "sum_disc_price": C.decimals([sums["disc_price"][i] for i in pick], 4),
        "sum_charge": C.decimals([sums["charge"][i] for i in pick], 6),
        "avg_qty": C.decimals(
            [_average(sums["qty"][i], c) for i, c in zip(pick, n)], 6),
        "avg_price": C.decimals(
            [_average(sums["price"][i], c) for i, c in zip(pick, n)], 6),
        "avg_disc": C.decimals(
            [_average(sums["disc"][i], c) for i, c in zip(pick, n)], 6),
        "count_order": pa.array(n, pa.int64()),
    })


def needed_bytes(tables, answer):
    return C.needed_bytes(tables, SOURCE_COLUMNS, answer)
