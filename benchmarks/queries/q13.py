"""TPC-H Q13, customer distribution: a left outer join under two levels of
group-by, sorted.  Counts only, no money."""
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from harness import columns as C

SOURCE_COLUMNS = {
    "customer": ["c_custkey"],
    "orders": ["o_orderkey", "o_custkey", "o_comment"],
}


def build(session, tables):
    from spark_rapids_tpu.plan import expressions as E
    from spark_rapids_tpu.plan.aggregates import Count
    from spark_rapids_tpu.plan.strings import Contains
    from spark_rapids_tpu.session import col
    orders = session.from_arrow(tables["orders"]).filter(
        E.Not(E.And(Contains(col("o_comment"), "special"),
                    Contains(col("o_comment"), "requests"))))
    cust = session.from_arrow(tables["customer"])
    j = cust.join(orders, how="left_outer",
                  left_on=["c_custkey"], right_on=["o_custkey"])
    per_cust = (j.group_by("c_custkey")
                .agg((Count(col("o_orderkey")), "c_count")))
    return (per_cust.group_by("c_count")
            .agg((Count(None), "custdist"))
            .sort(("custdist", False, False), ("c_count", False, False)))


def reference(tables, money=np.int64):
    cust, orders = tables["customer"], tables["orders"]
    comment = orders["o_comment"]
    drop = pc.and_(pc.match_substring(comment, "special"),
                   pc.match_substring(comment, "requests")).to_numpy(
                       zero_copy_only=False)
    o_cust = C.lookup(C.ints(cust["c_custkey"]), C.ints(orders["o_custkey"]))
    o_cust = o_cust[~drop & (o_cust >= 0)]
    per_cust = np.bincount(o_cust, minlength=cust.num_rows)
    c_count, custdist = np.unique(per_cust, return_counts=True)
    top = sorted(range(len(c_count)),
                 key=lambda i: (-custdist[i], -c_count[i]))
    return pa.table({"c_count": pa.array(c_count[top], pa.int64()),
                     "custdist": pa.array(custdist[top], pa.int64())})


def needed_bytes(tables, answer):
    return C.needed_bytes(tables, SOURCE_COLUMNS, answer)
