"""TPC-H data generation + query builders over the DataFrame API.

Role of the reference's integration_tests TPC-H/TPC-DS suites + datagen
(SURVEY §2.13): deterministic scaled tables with the spec's column types
(money = decimal(12,2), dates = date32) and the query shapes used by the
test suite (tests/test_tpch.py asserts device results against a pyarrow/
python oracle) and bench.py.

Row counts scale linearly with `scale` (scale=1.0 -> SF1-ish counts); the
value distributions follow the TPC-H spec shapes (uniform ranges, date
windows) without the full dbgen text grammar.
"""
from __future__ import annotations

import datetime as pydt
from typing import Dict, Optional

import numpy as np
import pyarrow as pa

from .plan import datetime as DT
from .plan import expressions as E
from .plan.aggregates import Average, Count, Sum
from .session import DataFrame, TpuSession, col, lit


def money_from_cents(cents: np.ndarray, precision=12, scale=2) -> pa.Array:
    """Exact decimal(p,s) from integer unscaled values (no float trip).

    Vectorized: the unscaled int64 cents ARE the decimal128 low lane;
    build the array straight from buffers (a Python-Decimal loop takes
    minutes at SF1's 6M rows)."""
    unscaled = cents.astype(np.int64)
    lanes = np.empty((len(unscaled), 2), dtype=np.uint64)
    lanes[:, 0] = unscaled.view(np.uint64)
    lanes[:, 1] = np.where(unscaled < 0,
                           np.uint64(0xFFFFFFFFFFFFFFFF), np.uint64(0))
    return pa.Array.from_buffers(
        pa.decimal128(precision, scale), len(unscaled),
        [None, pa.py_buffer(lanes.tobytes())])


_DATE0 = pydt.date(1970, 1, 1)


def _days(d: pydt.date) -> int:
    return (d - _DATE0).days


def gen_tables(scale: float = 0.01, seed: int = 20240706
               ) -> Dict[str, pa.Table]:
    rng = np.random.default_rng(seed)
    n_li = max(int(6_001_215 * scale), 100)
    n_ord = max(int(1_500_000 * scale), 40)
    n_cust = max(int(150_000 * scale), 20)
    n_supp = max(int(10_000 * scale), 5)
    n_part = max(int(200_000 * scale), 20)

    nations = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT",
               "ETHIOPIA", "FRANCE", "GERMANY", "INDIA", "INDONESIA",
               "IRAN", "IRAQ", "JAPAN", "JORDAN", "KENYA", "MOROCCO",
               "MOZAMBIQUE", "PERU", "CHINA", "ROMANIA", "SAUDI ARABIA",
               "VIETNAM", "RUSSIA", "UNITED KINGDOM", "UNITED STATES"]
    region_of = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2,
                 3, 4, 2, 3, 3, 1]
    regions = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]

    region = pa.table({
        "r_regionkey": pa.array(range(5), pa.int64()),
        "r_name": pa.array(regions),
    })
    nation = pa.table({
        "n_nationkey": pa.array(range(25), pa.int64()),
        "n_name": pa.array(nations),
        "n_regionkey": pa.array(region_of, pa.int64()),
    })
    c_nation = rng.integers(0, 25, n_cust)
    customer = pa.table({
        "c_custkey": pa.array(range(n_cust), pa.int64()),
        "c_name": pa.array([f"Customer#{i:09d}" for i in range(n_cust)]),
        "c_nationkey": pa.array(c_nation, pa.int64()),
        # spec: phone country code = nationkey + 10 (TPC-H 4.2.2.9)
        "c_phone": pa.array([
            f"{k + 10}-{a}-{b}-{c}" for k, a, b, c in zip(
                c_nation, rng.integers(100, 1000, n_cust),
                rng.integers(100, 1000, n_cust),
                rng.integers(1000, 10000, n_cust))]),
        "c_mktsegment": pa.array(rng.choice(
            ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
             "HOUSEHOLD"], n_cust)),
        "c_acctbal": money_from_cents(
            rng.integers(-99999, 999999, n_cust), 12, 2),
    })
    supplier = pa.table({
        "s_suppkey": pa.array(range(n_supp), pa.int64()),
        "s_name": pa.array([f"Supplier#{i:09d}" for i in range(n_supp)]),
        "s_address": pa.array([f"addr {i} lane" for i in range(n_supp)]),
        "s_phone": pa.array([f"{11 + i % 25}-{i % 900 + 100}-55"
                             for i in range(n_supp)]),
        "s_nationkey": pa.array(rng.integers(0, 25, n_supp), pa.int64()),
        "s_acctbal": money_from_cents(
            rng.integers(-99999, 999999, n_supp), 12, 2),
        "s_comment": pa.array(rng.choice(
            ["reliable and fast", "slow Customer Complaints recorded",
             "usually on time", "pending Customer Complaints review",
             "excellent record"], n_supp)),
    })
    colors = ["green", "blue", "red", "ivory", "khaki"]
    part = pa.table({
        "p_partkey": pa.array(range(n_part), pa.int64()),
        "p_mfgr": pa.array([f"Manufacturer#{m}" for m in
                            rng.integers(1, 6, n_part)]),
        "p_name": pa.array([f"{c} polished item{i}" for i, c in
                            enumerate(rng.choice(colors, n_part))]),
        "p_type": pa.array(rng.choice(
            ["ECONOMY ANODIZED STEEL", "LARGE BRUSHED BRASS",
             "STANDARD POLISHED TIN", "SMALL PLATED COPPER",
             "PROMO BURNISHED NICKEL"], n_part)),
        "p_brand": pa.array([f"Brand#{b}" for b in
                             rng.integers(11, 56, n_part)]),
        "p_container": pa.array(rng.choice(
            ["SM CASE", "SM BOX", "MED BAG", "MED BOX", "LG CASE",
             "LG BOX", "JUMBO PKG"], n_part)),
        "p_size": pa.array(rng.integers(1, 51, n_part), pa.int32()),
    })

    n_ps = n_part * 2
    # spec: (ps_partkey, ps_suppkey) is the table's primary key — each
    # part's supplier copies use distinct stride offsets (TPC-H 4.2.3's
    # supplier-of-part formula shape)
    ps_pk = np.concatenate([np.arange(n_part), np.arange(n_part)])
    ps_sk = np.concatenate(
        [np.arange(n_part) % n_supp,
         (np.arange(n_part) + max(1, n_supp // 4 + 1)) % n_supp])
    partsupp = pa.table({
        "ps_partkey": pa.array(ps_pk, pa.int64()),
        "ps_suppkey": pa.array(ps_sk, pa.int64()),
        "ps_availqty": pa.array(rng.integers(1, 10000, n_ps), pa.int32()),
        "ps_supplycost": money_from_cents(
            rng.integers(1_00, 1000_00, n_ps), 12, 2),
    })

    o_date_lo = _days(pydt.date(1992, 1, 1))
    o_date_hi = _days(pydt.date(1998, 8, 2))
    orders = pa.table({
        "o_orderkey": pa.array(range(n_ord), pa.int64()),
        # spec 4.2.3: orders reference only custkeys that are not a
        # multiple of 3 (a third of customers have no orders -> q13/q22
        # anti-join paths see real misses)
        "o_custkey": pa.array(
            np.array([k for k in range(n_cust) if k % 3 != 0], np.int64)[
                rng.integers(0, n_cust - (n_cust + 2) // 3, n_ord)],
            pa.int64()),
        "o_orderdate": pa.array(
            rng.integers(o_date_lo, o_date_hi, n_ord).astype(np.int32),
            pa.int32()).cast(pa.date32()),
        "o_shippriority": pa.array(np.zeros(n_ord, np.int32), pa.int32()),
        "o_orderstatus": pa.array(rng.choice(["F", "O", "P"], n_ord)),
        "o_orderpriority": pa.array(rng.choice(
            ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
             "5-LOW"], n_ord)),
        "o_comment": pa.array(rng.choice(
            ["fast delivery", "special requests pending",
             "nothing unusual", "pending special requests now",
             "routine order"], n_ord)),
        "o_totalprice": money_from_cents(
            rng.integers(100_00, 500_000_00, n_ord), 12, 2),
    })

    l_ship = rng.integers(o_date_lo, o_date_hi + 122, n_li).astype(np.int32)
    l_commit = l_ship + rng.integers(-30, 61, n_li).astype(np.int32)
    l_receipt = l_ship + rng.integers(1, 31, n_li).astype(np.int32)
    rf = rng.choice(["A", "N", "R"], n_li)
    lineitem = pa.table({
        "l_orderkey": pa.array(rng.integers(0, n_ord, n_li), pa.int64()),
        "l_partkey": pa.array(rng.integers(0, n_part, n_li), pa.int64()),
        "l_suppkey": pa.array(rng.integers(0, n_supp, n_li), pa.int64()),
        "l_quantity": money_from_cents(
            rng.integers(1, 51, n_li) * 100, 12, 2),
        "l_extendedprice": money_from_cents(
            rng.integers(900_00, 10_500_000, n_li), 12, 2),
        "l_discount": money_from_cents(rng.integers(0, 11, n_li), 12, 2),
        "l_tax": money_from_cents(rng.integers(0, 9, n_li), 12, 2),
        "l_returnflag": pa.array(rf),
        "l_linestatus": pa.array(np.where(
            l_ship > _days(pydt.date(1995, 6, 17)), "O", "F")),
        "l_shipdate": pa.array(l_ship, pa.int32()).cast(pa.date32()),
        "l_commitdate": pa.array(l_commit, pa.int32()).cast(pa.date32()),
        "l_receiptdate": pa.array(l_receipt, pa.int32()).cast(pa.date32()),
        "l_shipmode": pa.array(rng.choice(
            ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP",
             "TRUCK"], n_li)),
    })
    return {"lineitem": lineitem, "orders": orders, "customer": customer,
            "supplier": supplier, "part": part, "partsupp": partsupp,
            "nation": nation, "region": region}


# ---------------------------------------------------------------------------
# Queries
# ---------------------------------------------------------------------------

def q1(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Pricing summary report."""
    cutoff = _days(pydt.date(1998, 12, 1)) - 90
    li = s.from_arrow(t["lineitem"])
    disc_price = E.Multiply(col("l_extendedprice"),
                            E.Subtract(E.Literal(1), col("l_discount")))
    charge = E.Multiply(disc_price,
                        E.Add(E.Literal(1), col("l_tax")))
    return (li.filter(E.LessThanOrEqual(col("l_shipdate"),
                                        E.Literal(cutoff, DTYPE_DATE)))
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


def q3(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Shipping priority."""
    date = _days(pydt.date(1995, 3, 15))
    cust = s.from_arrow(t["customer"]).filter(
        E.EqualTo(col("c_mktsegment"), E.Literal("BUILDING")))
    orders = s.from_arrow(t["orders"]).filter(
        E.LessThan(col("o_orderdate"), E.Literal(date, DTYPE_DATE)))
    li = s.from_arrow(t["lineitem"]).filter(
        E.GreaterThan(col("l_shipdate"), E.Literal(date, DTYPE_DATE)))
    j = cust.join(orders, left_on=["c_custkey"], right_on=["o_custkey"]) \
        .join(li, left_on=["o_orderkey"], right_on=["l_orderkey"])
    revenue = E.Multiply(col("l_extendedprice"),
                         E.Subtract(E.Literal(1), col("l_discount")))
    return (j.group_by("o_orderkey", "o_orderdate", "o_shippriority")
            .agg((Sum(revenue), "revenue"))
            .sort(("revenue", False, False), ("o_orderdate", True, True))
            .limit(10))


def q5(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Local supplier volume: ASIA, 1994."""
    d_lo = _days(pydt.date(1994, 1, 1))
    d_hi = _days(pydt.date(1995, 1, 1))
    region = s.from_arrow(t["region"]).filter(
        E.EqualTo(col("r_name"), E.Literal("ASIA")))
    nation = s.from_arrow(t["nation"])
    cust = s.from_arrow(t["customer"])
    supp = s.from_arrow(t["supplier"])
    orders = s.from_arrow(t["orders"]).filter(
        E.And(E.GreaterThanOrEqual(col("o_orderdate"),
                                   E.Literal(d_lo, DTYPE_DATE)),
              E.LessThan(col("o_orderdate"), E.Literal(d_hi, DTYPE_DATE))))
    li = s.from_arrow(t["lineitem"])
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


def q6(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Forecast revenue change."""
    d_lo = _days(pydt.date(1994, 1, 1))
    d_hi = _days(pydt.date(1995, 1, 1))
    li = s.from_arrow(t["lineitem"])
    import decimal as pydec
    cond = E.And(
        E.And(E.GreaterThanOrEqual(col("l_shipdate"),
                                   E.Literal(d_lo, DTYPE_DATE)),
              E.LessThan(col("l_shipdate"), E.Literal(d_hi, DTYPE_DATE))),
        E.And(E.And(E.GreaterThanOrEqual(col("l_discount"),
                                         E.Literal(pydec.Decimal("0.05"))),
                    E.LessThanOrEqual(col("l_discount"),
                                      E.Literal(pydec.Decimal("0.07")))),
              E.LessThan(col("l_quantity"),
                         E.Literal(pydec.Decimal("24")))))
    revenue = E.Multiply(col("l_extendedprice"), col("l_discount"))
    return li.filter(cond).agg((Sum(revenue), "revenue"))


def q4(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Order priority checking: EXISTS ≡ left-semi join."""
    d_lo = _days(pydt.date(1993, 7, 1))
    d_hi = _days(pydt.date(1993, 10, 1))
    orders = s.from_arrow(t["orders"]).filter(
        E.And(E.GreaterThanOrEqual(col("o_orderdate"),
                                   E.Literal(d_lo, DTYPE_DATE)),
              E.LessThan(col("o_orderdate"), E.Literal(d_hi, DTYPE_DATE))))
    late = s.from_arrow(t["lineitem"]).filter(
        E.LessThan(col("l_commitdate"), col("l_receiptdate")))
    j = orders.join(late, how="left_semi",
                    left_on=["o_orderkey"], right_on=["l_orderkey"])
    return (j.group_by("o_orderpriority")
            .agg((Count(None), "order_count"))
            .sort("o_orderpriority"))


def q10(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Returned item reporting (top 20 customers by lost revenue)."""
    d_lo = _days(pydt.date(1993, 10, 1))
    d_hi = _days(pydt.date(1994, 1, 1))
    cust = s.from_arrow(t["customer"])
    orders = s.from_arrow(t["orders"]).filter(
        E.And(E.GreaterThanOrEqual(col("o_orderdate"),
                                   E.Literal(d_lo, DTYPE_DATE)),
              E.LessThan(col("o_orderdate"), E.Literal(d_hi, DTYPE_DATE))))
    li = s.from_arrow(t["lineitem"]).filter(
        E.EqualTo(col("l_returnflag"), E.Literal("R")))
    nation = s.from_arrow(t["nation"])
    j = (cust.join(orders, left_on=["c_custkey"], right_on=["o_custkey"])
         .join(li, left_on=["o_orderkey"], right_on=["l_orderkey"])
         .join(nation, left_on=["c_nationkey"], right_on=["n_nationkey"]))
    revenue = E.Multiply(col("l_extendedprice"),
                         E.Subtract(E.Literal(1), col("l_discount")))
    return (j.group_by("c_custkey", "n_name")
            .agg((Sum(revenue), "revenue"))
            .sort(("revenue", False, False), ("c_custkey", True, True))
            .limit(20))


def q12(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Shipping modes and order priority (CASE WHEN sums + IN)."""
    d_lo = _days(pydt.date(1994, 1, 1))
    d_hi = _days(pydt.date(1995, 1, 1))
    li = s.from_arrow(t["lineitem"]).filter(E.And(
        E.And(E.In(col("l_shipmode"), ["MAIL", "SHIP"]),
              E.And(E.LessThan(col("l_commitdate"), col("l_receiptdate")),
                    E.LessThan(col("l_shipdate"), col("l_commitdate")))),
        E.And(E.GreaterThanOrEqual(col("l_receiptdate"),
                                   E.Literal(d_lo, DTYPE_DATE)),
              E.LessThan(col("l_receiptdate"),
                         E.Literal(d_hi, DTYPE_DATE)))))
    orders = s.from_arrow(t["orders"])
    j = orders.join(li, left_on=["o_orderkey"], right_on=["l_orderkey"])
    high = E.CaseWhen(
        [(E.In(col("o_orderpriority"), ["1-URGENT", "2-HIGH"]),
          E.Literal(1))], E.Literal(0))
    low = E.CaseWhen(
        [(E.In(col("o_orderpriority"), ["1-URGENT", "2-HIGH"]),
          E.Literal(0))], E.Literal(1))
    return (j.group_by("l_shipmode")
            .agg((Sum(high), "high_line_count"),
                 (Sum(low), "low_line_count"))
            .sort("l_shipmode"))


def q14(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Promotion effect: 100 * promo revenue / total revenue."""
    from .plan.strings import StartsWith
    d_lo = _days(pydt.date(1995, 9, 1))
    d_hi = _days(pydt.date(1995, 10, 1))
    li = s.from_arrow(t["lineitem"]).filter(
        E.And(E.GreaterThanOrEqual(col("l_shipdate"),
                                   E.Literal(d_lo, DTYPE_DATE)),
              E.LessThan(col("l_shipdate"), E.Literal(d_hi, DTYPE_DATE))))
    part = s.from_arrow(t["part"])
    j = li.join(part, left_on=["l_partkey"], right_on=["p_partkey"])
    revenue = E.Multiply(col("l_extendedprice"),
                         E.Subtract(E.Literal(1), col("l_discount")))
    promo = E.CaseWhen([(StartsWith(col("p_type"), "PROMO"), revenue)],
                       E.Literal(pydec_zero()))
    agg = j.agg((Sum(promo), "promo"), (Sum(revenue), "total"))
    ratio = E.Divide(E.Multiply(E.Literal(100.0),
                                E.Cast(col("promo"), _t.DOUBLE)),
                     E.Cast(col("total"), _t.DOUBLE))
    return agg.select(ratio, names=["promo_revenue"])


def pydec_zero():
    import decimal as pydec
    return pydec.Decimal("0.00")


def q17(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Small-quantity-order revenue: correlated avg subquery as a join."""
    part = s.from_arrow(t["part"]).filter(
        E.EqualTo(col("p_type"), E.Literal("PROMO BURNISHED NICKEL")))
    li = s.from_arrow(t["lineitem"])
    per_part = (li.group_by("l_partkey")
                .agg((Average(col("l_quantity")), "avg_qty")))
    per_part = per_part.select(
        col("l_partkey"), E.Multiply(E.Literal(0.2),
                                     E.Cast(col("avg_qty"), _t.DOUBLE)),
        names=["ap_partkey", "qty_limit"])
    j = (li.join(part, left_on=["l_partkey"], right_on=["p_partkey"])
         .join(per_part, left_on=["l_partkey"], right_on=["ap_partkey"])
         .filter(E.LessThan(E.Cast(col("l_quantity"), _t.DOUBLE),
                            col("qty_limit"))))
    total = j.agg((Sum(col("l_extendedprice")), "s"))
    return total.select(
        E.Divide(E.Cast(col("s"), _t.DOUBLE), E.Literal(7.0)),
        names=["avg_yearly"])


def q18(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Large-volume customers (HAVING sum(qty) > threshold via join).

    A reduced shape, kept as the tests and bench.py run it; not clause
    2.4.18.  Left out: the decimal HAVING (`sum(l_quantity) > 300`: a cast
    to double against 7200.0 stands here), `c_name`, the join back to
    `lineitem` and the outer group-by.  The query as the clause writes it,
    with its plain reference, is benchmarks/queries/q18.py (the cell
    `tpch-sf10.q18`)."""
    li = s.from_arrow(t["lineitem"])
    big = (li.group_by("l_orderkey")
           .agg((Sum(col("l_quantity")), "total_qty"))
           .filter(E.GreaterThan(E.Cast(col("total_qty"), _t.DOUBLE),
                                 E.Literal(7200.0))))
    big = big.select(col("l_orderkey"), col("total_qty"),
                     names=["big_orderkey", "total_qty"])
    orders = s.from_arrow(t["orders"])
    cust = s.from_arrow(t["customer"])
    j = (orders.join(big, left_on=["o_orderkey"], right_on=["big_orderkey"])
         .join(cust, left_on=["o_custkey"], right_on=["c_custkey"]))
    return (j.select(col("c_custkey"), col("o_orderkey"), col("o_orderdate"),
                     col("o_totalprice"), col("total_qty"))
            .sort(("o_totalprice", False, False), ("o_orderdate", True, True))
            .limit(100))


def q7(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Volume shipping between FRANCE and GERMANY (nation joined twice
    under renames)."""
    d_lo = _days(pydt.date(1995, 1, 1))
    d_hi = _days(pydt.date(1996, 12, 31))
    supp_nation = s.from_arrow(t["nation"]).select(
        col("n_nationkey"), col("n_name"),
        names=["sn_key", "supp_nation"]).filter(
        E.In(col("supp_nation"), ["FRANCE", "GERMANY"]))
    cust_nation = s.from_arrow(t["nation"]).select(
        col("n_nationkey"), col("n_name"),
        names=["cn_key", "cust_nation"]).filter(
        E.In(col("cust_nation"), ["FRANCE", "GERMANY"]))
    li = s.from_arrow(t["lineitem"]).filter(
        E.And(E.GreaterThanOrEqual(col("l_shipdate"),
                                   E.Literal(d_lo, DTYPE_DATE)),
              E.LessThanOrEqual(col("l_shipdate"),
                                E.Literal(d_hi, DTYPE_DATE))))
    j = (li.join(s.from_arrow(t["supplier"]),
                 left_on=["l_suppkey"], right_on=["s_suppkey"])
         .join(supp_nation, left_on=["s_nationkey"], right_on=["sn_key"])
         .join(s.from_arrow(t["orders"]),
               left_on=["l_orderkey"], right_on=["o_orderkey"])
         .join(s.from_arrow(t["customer"]),
               left_on=["o_custkey"], right_on=["c_custkey"])
         .join(cust_nation, left_on=["c_nationkey"], right_on=["cn_key"])
         .filter(E.Not(E.EqualTo(col("supp_nation"),
                                 col("cust_nation")))))
    volume = E.Multiply(col("l_extendedprice"),
                        E.Subtract(E.Literal(1), col("l_discount")))
    year = DT.Year(col("l_shipdate"))
    return (j.group_by(col("supp_nation"), col("cust_nation"),
                       E.Alias(year, "l_year"))
            .agg((Sum(volume), "revenue"))
            .sort("supp_nation", "cust_nation", "l_year"))


def q9(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Product type profit measure: the spec's 6-table join with
    ps_supplycost (profit = price*(1-disc) - supplycost*qty)."""
    from .plan.strings import Contains
    part = s.from_arrow(t["part"]).filter(
        Contains(col("p_name"), "green"))
    li = s.from_arrow(t["lineitem"])
    ps = s.from_arrow(t["partsupp"])
    j = (li.join(part, left_on=["l_partkey"], right_on=["p_partkey"])
         .join(s.from_arrow(t["supplier"]),
               left_on=["l_suppkey"], right_on=["s_suppkey"])
         .join(ps, left_on=["l_partkey", "l_suppkey"],
               right_on=["ps_partkey", "ps_suppkey"])
         .join(s.from_arrow(t["orders"]),
               left_on=["l_orderkey"], right_on=["o_orderkey"])
         .join(s.from_arrow(t["nation"]),
               left_on=["s_nationkey"], right_on=["n_nationkey"]))
    amount = E.Subtract(
        E.Multiply(col("l_extendedprice"),
                   E.Subtract(E.Literal(1), col("l_discount"))),
        E.Multiply(col("ps_supplycost"), col("l_quantity")))
    year = DT.Year(col("o_orderdate"))
    return (j.group_by(col("n_name"), E.Alias(year, "o_year"))
            .agg((Sum(amount), "sum_profit"))
            .sort(("n_name", True, True), ("o_year", False, False)))


def q13(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Customer distribution: two-level aggregation over a left outer
    join with a NOT-LIKE filtered order side."""
    from .plan.strings import Contains
    orders = s.from_arrow(t["orders"]).filter(
        E.Not(E.And(Contains(col("o_comment"), "special"),
                    Contains(col("o_comment"), "requests"))))
    cust = s.from_arrow(t["customer"])
    j = cust.join(orders, how="left_outer",
                  left_on=["c_custkey"], right_on=["o_custkey"])
    per_cust = (j.group_by("c_custkey")
                .agg((Count(col("o_orderkey")), "c_count")))
    return (per_cust.group_by("c_count")
            .agg((Count(None), "custdist"))
            .sort(("custdist", False, False), ("c_count", False, False)))


def q19(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Discounted revenue: disjunction of brand/container/quantity
    conjuncts (the OR-of-ANDs predicate shape)."""
    import decimal as pydec
    li = s.from_arrow(t["lineitem"]).filter(
        E.And(E.In(col("l_shipmode"), ["AIR", "REG AIR"]),
              E.EqualTo(col("l_returnflag"), E.Literal("N"))))
    part = s.from_arrow(t["part"])
    j = li.join(part, left_on=["l_partkey"], right_on=["p_partkey"])

    def qty_between(lo, hi):
        return E.And(
            E.GreaterThanOrEqual(col("l_quantity"),
                                 E.Literal(pydec.Decimal(lo))),
            E.LessThanOrEqual(col("l_quantity"),
                              E.Literal(pydec.Decimal(hi))))
    branch1 = E.And(E.And(E.EqualTo(col("p_brand"), E.Literal("Brand#12")),
                          E.In(col("p_container"),
                               ["SM CASE", "SM BOX"])),
                    E.And(qty_between("1", "11"),
                          E.LessThanOrEqual(col("p_size"), E.Literal(5))))
    branch2 = E.And(E.And(E.EqualTo(col("p_brand"), E.Literal("Brand#23")),
                          E.In(col("p_container"),
                               ["MED BAG", "MED BOX"])),
                    E.And(qty_between("10", "20"),
                          E.LessThanOrEqual(col("p_size"), E.Literal(10))))
    branch3 = E.And(E.And(E.EqualTo(col("p_brand"), E.Literal("Brand#34")),
                          E.In(col("p_container"),
                               ["LG CASE", "LG BOX", "JUMBO PKG"])),
                    E.And(qty_between("20", "30"),
                          E.LessThanOrEqual(col("p_size"), E.Literal(15))))
    revenue = E.Multiply(col("l_extendedprice"),
                         E.Subtract(E.Literal(1), col("l_discount")))
    return (j.filter(E.Or(E.Or(branch1, branch2), branch3))
            .agg((Sum(revenue), "revenue")))


def q2(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Minimum-cost supplier: correlated MIN subquery as a self-join on
    (partkey, min cost)."""
    from .plan.strings import EndsWith
    part = s.from_arrow(t["part"]).filter(
        E.And(E.EqualTo(col("p_size"), E.Literal(15)),
              EndsWith(col("p_type"), "BRASS")))
    europe = (s.from_arrow(t["region"])
              .filter(E.EqualTo(col("r_name"), E.Literal("EUROPE")))
              .join(s.from_arrow(t["nation"]),
                    left_on=["r_regionkey"], right_on=["n_regionkey"]))
    esupp = europe.join(s.from_arrow(t["supplier"]),
                        left_on=["n_nationkey"], right_on=["s_nationkey"])
    ps = s.from_arrow(t["partsupp"])
    eps = ps.join(esupp, left_on=["ps_suppkey"], right_on=["s_suppkey"]) \
        .join(part, left_on=["ps_partkey"], right_on=["p_partkey"])
    from .plan.aggregates import Min
    mins = (eps.group_by("ps_partkey")
            .agg((Min(col("ps_supplycost")), "min_cost"))
            .select(col("ps_partkey"), col("min_cost"),
                    names=["mc_partkey", "min_cost"]))
    j = eps.join(mins, left_on=["ps_partkey", "ps_supplycost"],
                 right_on=["mc_partkey", "min_cost"])
    return (j.select(col("s_acctbal"), col("s_name"), col("n_name"),
                     col("p_partkey"), col("p_mfgr"), col("s_address"),
                     col("s_phone"))
            .sort(("s_acctbal", False, False), ("n_name", True, True),
                  ("s_name", True, True), ("p_partkey", True, True))
            .limit(100))


def q8(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """National market share: BRAZIL's share of AMERICA's ECONOMY
    ANODIZED STEEL volume per year."""
    d_lo = _days(pydt.date(1995, 1, 1))
    d_hi = _days(pydt.date(1996, 12, 31))
    part = s.from_arrow(t["part"]).filter(
        E.EqualTo(col("p_type"), E.Literal("ECONOMY ANODIZED STEEL")))
    orders = s.from_arrow(t["orders"]).filter(
        E.And(E.GreaterThanOrEqual(col("o_orderdate"),
                                   E.Literal(d_lo, DTYPE_DATE)),
              E.LessThanOrEqual(col("o_orderdate"),
                                E.Literal(d_hi, DTYPE_DATE))))
    n1 = (s.from_arrow(t["region"])
          .filter(E.EqualTo(col("r_name"), E.Literal("AMERICA")))
          .join(s.from_arrow(t["nation"]),
                left_on=["r_regionkey"], right_on=["n_regionkey"])
          .select(col("n_nationkey"), names=["cn_key"]))
    n2 = s.from_arrow(t["nation"]).select(
        col("n_nationkey"), col("n_name"), names=["sn_key", "supp_nation"])
    j = (s.from_arrow(t["lineitem"])
         .join(part, left_on=["l_partkey"], right_on=["p_partkey"])
         .join(s.from_arrow(t["supplier"]),
               left_on=["l_suppkey"], right_on=["s_suppkey"])
         .join(orders, left_on=["l_orderkey"], right_on=["o_orderkey"])
         .join(s.from_arrow(t["customer"]),
               left_on=["o_custkey"], right_on=["c_custkey"])
         .join(n1, left_on=["c_nationkey"], right_on=["cn_key"])
         .join(n2, left_on=["s_nationkey"], right_on=["sn_key"]))
    volume = E.Multiply(
        E.Cast(col("l_extendedprice"), _t.DOUBLE),
        E.Subtract(E.Literal(1.0), E.Cast(col("l_discount"), _t.DOUBLE)))
    brazil = E.CaseWhen(
        [(E.EqualTo(col("supp_nation"), E.Literal("BRAZIL")), volume)],
        E.Literal(0.0))
    year = DT.Year(col("o_orderdate"))
    g = (j.group_by(E.Alias(year, "o_year"))
         .agg((Sum(brazil), "brazil_vol"), (Sum(volume), "total_vol")))
    share = E.Divide(col("brazil_vol"), col("total_vol"))
    return (g.select(col("o_year"), share, names=["o_year", "mkt_share"])
            .sort("o_year"))


def q11(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Important stock identification: HAVING against a scalar subquery
    (total value fraction) via a 1-row cross join."""
    germany = (s.from_arrow(t["partsupp"])
               .join(s.from_arrow(t["supplier"]),
                     left_on=["ps_suppkey"], right_on=["s_suppkey"])
               .join(s.from_arrow(t["nation"]).filter(
                   E.EqualTo(col("n_name"), E.Literal("GERMANY"))),
                   left_on=["s_nationkey"], right_on=["n_nationkey"]))
    value = E.Multiply(E.Cast(col("ps_supplycost"), _t.DOUBLE),
                       E.Cast(col("ps_availqty"), _t.DOUBLE))
    per_part = (germany.group_by("ps_partkey")
                .agg((Sum(value), "value")))
    total = (germany.agg((Sum(value), "tv"))
             .select(E.Multiply(col("tv"), E.Literal(0.0001)),
                     names=["threshold"]))
    j = per_part.join(total, how="cross")
    return (j.filter(E.GreaterThan(col("value"), col("threshold")))
            .select(col("ps_partkey"), col("value"))
            .sort(("value", False, False), ("ps_partkey", True, True)))


def q15(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Top supplier: revenue view + MAX scalar subquery."""
    from .plan.aggregates import Max
    d_lo = _days(pydt.date(1996, 1, 1))
    d_hi = _days(pydt.date(1996, 4, 1))
    li = s.from_arrow(t["lineitem"]).filter(
        E.And(E.GreaterThanOrEqual(col("l_shipdate"),
                                   E.Literal(d_lo, DTYPE_DATE)),
              E.LessThan(col("l_shipdate"), E.Literal(d_hi, DTYPE_DATE))))
    revenue = E.Multiply(
        E.Cast(col("l_extendedprice"), _t.DOUBLE),
        E.Subtract(E.Literal(1.0), E.Cast(col("l_discount"), _t.DOUBLE)))
    rev = (li.group_by("l_suppkey")
           .agg((Sum(revenue), "total_revenue")))
    top = rev.agg((Max(col("total_revenue")), "max_revenue"))
    j = (rev.join(top, how="cross")
         .filter(E.EqualTo(col("total_revenue"), col("max_revenue")))
         .join(s.from_arrow(t["supplier"]),
               left_on=["l_suppkey"], right_on=["s_suppkey"]))
    return (j.select(col("s_suppkey"), col("s_name"), col("s_address"),
                     col("s_phone"), col("total_revenue"))
            .sort("s_suppkey"))


def q16(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Parts/supplier relationship: NOT IN subquery as anti join +
    count(distinct)."""
    from .plan.aggregates import CountDistinct
    from .plan.strings import Contains, StartsWith
    bad_supp = s.from_arrow(t["supplier"]).filter(
        E.And(Contains(col("s_comment"), "Customer"),
              Contains(col("s_comment"), "Complaints")))
    part = s.from_arrow(t["part"]).filter(
        E.And(E.Not(E.EqualTo(col("p_brand"), E.Literal("Brand#45"))),
              E.And(E.Not(StartsWith(col("p_type"), "MEDIUM POLISHED")),
                    E.In(E.Cast(col("p_size"), _t.INT),
                         [49, 14, 23, 45, 19, 3, 36, 9]))))
    ps = (s.from_arrow(t["partsupp"])
          .join(bad_supp, how="left_anti",
                left_on=["ps_suppkey"], right_on=["s_suppkey"])
          .join(part, left_on=["ps_partkey"], right_on=["p_partkey"]))
    return (ps.group_by("p_brand", "p_type", "p_size")
            .agg((CountDistinct(col("ps_suppkey")), "supplier_cnt"))
            .sort(("supplier_cnt", False, False), ("p_brand", True, True),
                  ("p_type", True, True), ("p_size", True, True)))


def q20(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Potential part promotion: nested IN subqueries as semi joins over
    a half-of-shipped-quantity threshold."""
    from .plan.strings import StartsWith
    d_lo = _days(pydt.date(1994, 1, 1))
    d_hi = _days(pydt.date(1995, 1, 1))
    green = s.from_arrow(t["part"]).filter(
        StartsWith(col("p_name"), "green"))
    shipped = (s.from_arrow(t["lineitem"])
               .filter(E.And(
                   E.GreaterThanOrEqual(col("l_shipdate"),
                                        E.Literal(d_lo, DTYPE_DATE)),
                   E.LessThan(col("l_shipdate"),
                              E.Literal(d_hi, DTYPE_DATE))))
               .group_by("l_partkey", "l_suppkey")
               .agg((Sum(col("l_quantity")), "sum_qty")))
    shipped = shipped.select(
        col("l_partkey"), col("l_suppkey"),
        E.Multiply(E.Literal(0.5), E.Cast(col("sum_qty"), _t.DOUBLE)),
        names=["sh_partkey", "sh_suppkey", "half_qty"])
    ps = (s.from_arrow(t["partsupp"])
          .join(green, how="left_semi",
                left_on=["ps_partkey"], right_on=["p_partkey"])
          .join(shipped, left_on=["ps_partkey", "ps_suppkey"],
                right_on=["sh_partkey", "sh_suppkey"])
          .filter(E.GreaterThan(E.Cast(col("ps_availqty"), _t.DOUBLE),
                                col("half_qty"))))
    supp = (s.from_arrow(t["supplier"])
            .join(s.from_arrow(t["nation"]).filter(
                E.EqualTo(col("n_name"), E.Literal("CANADA"))),
                left_on=["s_nationkey"], right_on=["n_nationkey"])
            .join(ps, how="left_semi",
                  left_on=["s_suppkey"], right_on=["ps_suppkey"]))
    return supp.select(col("s_name"), col("s_address")).sort("s_name")


def q21(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Suppliers who kept orders waiting: EXISTS/NOT-EXISTS pair rewritten
    as per-order distinct-supplier counts (total > 1, late == 1)."""
    from .plan.aggregates import CountDistinct
    li = s.from_arrow(t["lineitem"])
    late = li.filter(E.GreaterThan(col("l_receiptdate"),
                                   col("l_commitdate")))
    total_supp = (li.group_by("l_orderkey")
                  .agg((CountDistinct(col("l_suppkey")), "n_supp"))
                  .select(col("l_orderkey"), col("n_supp"),
                          names=["ts_orderkey", "n_supp"]))
    late_supp = (late.group_by("l_orderkey")
                 .agg((CountDistinct(col("l_suppkey")), "n_late"))
                 .select(col("l_orderkey"), col("n_late"),
                         names=["ls_orderkey", "n_late"]))
    fails = s.from_arrow(t["orders"]).filter(
        E.EqualTo(col("o_orderstatus"), E.Literal("F")))
    saudi = (s.from_arrow(t["supplier"])
             .join(s.from_arrow(t["nation"]).filter(
                 E.EqualTo(col("n_name"), E.Literal("SAUDI ARABIA"))),
                 left_on=["s_nationkey"], right_on=["n_nationkey"]))
    j = (late.join(saudi, left_on=["l_suppkey"], right_on=["s_suppkey"])
         .join(fails, left_on=["l_orderkey"], right_on=["o_orderkey"])
         .join(total_supp, left_on=["l_orderkey"], right_on=["ts_orderkey"])
         .join(late_supp, left_on=["l_orderkey"], right_on=["ls_orderkey"])
         .filter(E.And(E.GreaterThan(col("n_supp"), E.Literal(1)),
                       E.EqualTo(col("n_late"), E.Literal(1)))))
    return (j.group_by("s_name")
            .agg((Count(None), "numwait"))
            .sort(("numwait", False, False), ("s_name", True, True))
            .limit(100))


def q22(s: TpuSession, t: Dict[str, pa.Table]) -> DataFrame:
    """Global sales opportunity: phone-prefix IN + scalar AVG subquery +
    NOT EXISTS anti join."""
    from .plan.strings import Substring
    codes = ["13", "31", "23", "29", "30", "18", "17"]
    cust = s.from_arrow(t["customer"]).select(
        col("c_custkey"), col("c_acctbal"),
        Substring(col("c_phone"), 1, 2),
        names=["c_custkey", "c_acctbal", "cntrycode"])
    cust = cust.filter(E.In(col("cntrycode"), codes))
    pos = cust.filter(E.GreaterThan(
        E.Cast(col("c_acctbal"), _t.DOUBLE), E.Literal(0.0)))
    avg_bal = pos.agg(
        (Average(E.Cast(col("c_acctbal"), _t.DOUBLE)), "avg_bal"))
    cand = (cust.join(avg_bal, how="cross")
            .filter(E.GreaterThan(E.Cast(col("c_acctbal"), _t.DOUBLE),
                                  col("avg_bal")))
            .join(s.from_arrow(t["orders"]), how="left_anti",
                  left_on=["c_custkey"], right_on=["o_custkey"]))
    return (cand.group_by("cntrycode")
            .agg((Count(None), "numcust"),
                 (Sum(E.Cast(col("c_acctbal"), _t.DOUBLE)), "totacctbal"))
            .sort("cntrycode"))


from . import types as _t           # noqa: E402
DTYPE_DATE = _t.DATE

QUERIES = {"q1": q1, "q2": q2, "q3": q3, "q4": q4, "q5": q5, "q6": q6,
           "q7": q7, "q8": q8, "q9": q9, "q10": q10, "q11": q11,
           "q12": q12, "q13": q13, "q14": q14, "q15": q15, "q16": q16,
           "q17": q17, "q18": q18, "q19": q19, "q20": q20, "q21": q21,
           "q22": q22}
