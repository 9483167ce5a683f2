"""TPC-H Q18, large volume customer (clause 2.4.18, validation QUANTITY =
300): the orders whose lines add up to more than 300 units, with their
customer, top 100 by total price.

    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem group by l_orderkey
                         having sum(l_quantity) > 300)
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate  limit 100

`build` writes it as a Spark physical plan has it: the IN-subquery is a
left-semi join whose build side is the aggregate, the smaller side of every
join builds (this engine's build side is the right one), the HAVING compares
the decimal(22,2) sum with the integer literal exactly.  It ends the run at
once when the plan is not wholly on the device: a tree that cannot plan Q18
there must fail in seconds and not time another engine over 60M rows.

Ties.  The specification leaves the order of rows that tie on both sort
keys open, and the comparison is in order.  The reference breaks such a tie
by o_orderkey ascending; the engine's order among tied rows is whatever its
stable sort finds in its aggregate's output.  Two of the some 600 orders
that qualify at SF10 tie on an exact total price (a range of some 3e7 cents)
AND on the date (2,406 days) in fewer than one data set in 100,000; such a
run would read `wrong_values` of a row or two.
"""
import numpy as np
import pyarrow as pa

from harness import columns as C

SOURCE_COLUMNS = {
    "customer": ["c_custkey", "c_name"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
    "lineitem": ["l_orderkey", "l_quantity"],
}
QUANTITY = 300
LIMIT = 100


def build(session, tables):
    from spark_rapids_tpu.plan import expressions as E
    from spark_rapids_tpu.plan.aggregates import Sum
    from spark_rapids_tpu.session import col
    li = session.from_arrow(tables["lineitem"])
    big = (li.group_by("l_orderkey")
           .agg((Sum(col("l_quantity")), "total_qty"))
           .filter(E.GreaterThan(col("total_qty"), E.Literal(QUANTITY)))
           .select(col("l_orderkey"), names=["big_orderkey"]))
    orders = session.from_arrow(tables["orders"]).join(
        big, left_on=["o_orderkey"], right_on=["big_orderkey"],
        how="left_semi")
    # the right side builds: the few large orders, then they with their
    # customers, each smaller than what probes it
    named = session.from_arrow(tables["customer"]).join(
        orders, left_on=["c_custkey"], right_on=["o_custkey"])
    df = (li.join(named, left_on=["l_orderkey"], right_on=["o_orderkey"])
          .group_by("c_name", "c_custkey", "o_orderkey", "o_orderdate",
                    "o_totalprice")
          .agg((Sum(col("l_quantity")), "sum_qty"))
          .sort(("o_totalprice", False, False), ("o_orderdate", True, True))
          .limit(LIMIT))
    physical = df.physical()
    reasons = physical.fallback_reasons()
    if physical.kind != "device" or reasons:
        raise SystemExit(
            "benchmark FAILED: this tree cannot plan TPC-H Q18 onto the "
            f"device (plan kind {physical.kind!r}, fallback reasons "
            f"{reasons}); the cell is not run on another engine")
    return df


def reference(tables, money=np.int64):
    """numpy on exact integer cents.  Departures from the clause: none in
    the result; the joins are foreign key -> primary key, which the lookups
    use, and the outer group-by has one group an order because o_orderkey
    is the orders' key (the other four group columns depend on it)."""
    cust, orders, li = tables["customer"], tables["orders"], tables["lineitem"]
    l_key = C.ints(li["l_orderkey"])
    qty = C.cents(li["l_quantity"], money)
    keys, sums = C.group_sum(l_key, qty)
    big = keys[sums > 100 * QUANTITY]            # having sum(l_quantity) > 300
    o_key = C.ints(orders["o_orderkey"])
    o_row = C.lookup(o_key, big)                 # o_orderkey in (...)
    o_row = o_row[o_row >= 0]
    c_row = C.lookup(C.ints(cust["c_custkey"]),
                     C.ints(orders["o_custkey"])[o_row])
    o_row, c_row = o_row[c_row >= 0], c_row[c_row >= 0]   # c_custkey = o_custkey
    # o_orderkey = l_orderkey: the lines of the orders that are left, summed
    # again (the clause joins lineitem a second time)
    kept = np.sort(o_key[o_row])
    at = np.searchsorted(kept, l_key)
    at[at == len(kept)] = 0
    lines = kept[at] == l_key if len(kept) else np.zeros(len(l_key), bool)
    g_keys, g_sums = C.group_sum(l_key[lines], qty[lines])
    sum_qty = g_sums[np.searchsorted(g_keys, o_key[o_row])]
    price = C.cents(orders["o_totalprice"], money)[o_row]
    date = C.ints(orders["o_orderdate"])[o_row]
    # o_totalprice desc, o_orderdate; ties on both by o_orderkey (docstring)
    top = np.lexsort((o_key[o_row], date, -price))[:LIMIT]
    return pa.table({
        "c_name": cust["c_name"].take(pa.array(c_row[top])).combine_chunks(),
        "c_custkey": pa.array(C.ints(cust["c_custkey"])[c_row[top]],
                              pa.int64()),
        "o_orderkey": pa.array(o_key[o_row][top], pa.int64()),
        "o_orderdate": pa.array(date[top].astype(np.int32),
                                pa.int32()).cast(pa.date32()),
        "o_totalprice": C.decimals(price[top], 2),
        "sum_qty": C.decimals(sum_qty[top], 2),
    })


def needed_bytes(tables, answer):
    return C.needed_bytes(tables, SOURCE_COLUMNS, answer)
