"""TPC-DS Q36, gross margin by item hierarchy: a ROLLUP over (i_category,
i_class) of store sales in 2001 at stores of eight states, each row ranked
within its level and parent category by its margin, top 100.

    select sum(ss_net_profit) / sum(ss_ext_sales_price) as gross_margin,
           i_category, i_class,
           grouping(i_category) + grouping(i_class) as lochierarchy,
           rank() over (
               partition by grouping(i_category) + grouping(i_class),
                 case when grouping(i_class) = 0 then i_category end
               order by sum(ss_net_profit) / sum(ss_ext_sales_price) asc)
             as rank_within_parent
    from store_sales, date_dim d1, item, store
    where d1.d_year = 2001 and d1.d_date_sk = ss_sold_date_sk
      and i_item_sk = ss_item_sk and s_store_sk = ss_store_sk
      and s_state in ('TN','SC','AL','GA','SD','MI','OH','TX')
    group by rollup(i_category, i_class)
    order by lochierarchy desc,
      case when lochierarchy = 0 then i_category end, rank_within_parent
    limit 100

`build` writes the plan as `spark_rapids_tpu.tpcds.q36` has it: the margin
is a double division of the two sums cast to double (the specification's
text divides the decimals), the CASE of the ORDER BY is an output column
`sort_cat`, and the order goes on by i_category and i_class, so that no two
rows tie.  It ends the run at once when the plan is not wholly on the
device, or when the tree cannot say that the plan traces as whole-plan
programs: such a tree must fail in seconds and not time another engine
over 28.8M rows.
"""
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from harness import columns as C

SOURCE_COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                    "ss_ext_sales_price", "ss_net_profit"],
    "date_dim": ["d_date_sk", "d_year"],
    "item": ["i_item_sk", "i_category", "i_class"],
    "store": ["s_store_sk", "s_state"],
}
YEAR = 2001
STATES = ["TN", "SC", "AL", "GA", "SD", "MI", "OH", "TX"]
LIMIT = 100
NAMES = ["gross_margin", "i_category", "i_class", "lochierarchy",
         "rank_within_parent", "sort_cat"]


def build(session, tables):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.plan import expressions as E
    from spark_rapids_tpu.plan.aggregates import Sum
    from spark_rapids_tpu.plan.window import Rank
    from spark_rapids_tpu.session import col
    dates = session.from_arrow(tables["date_dim"]).filter(
        E.EqualTo(col("d_year"), E.Literal(YEAR)))
    j = (session.from_arrow(tables["store_sales"])
         .join(dates, left_on=["ss_sold_date_sk"], right_on=["d_date_sk"])
         .join(session.from_arrow(tables["item"]),
               left_on=["ss_item_sk"], right_on=["i_item_sk"])
         .join(session.from_arrow(tables["store"]).filter(
             E.In(col("s_state"), STATES)),
             left_on=["ss_store_sk"], right_on=["s_store_sk"]))
    r = j.rollup("i_category", "i_class")
    g = r.agg((Sum(col("ss_net_profit")), "profit"),
              (Sum(col("ss_ext_sales_price")), "sales"))
    margin = E.Divide(E.Cast(col("profit"), T.DOUBLE),
                      E.Cast(col("sales"), T.DOUBLE))
    lochier = E.Add(r.grouping("i_category"), r.grouping("i_class"))
    parent = E.CaseWhen(
        [(E.EqualTo(r.grouping("i_class"), E.Literal(0)),
          col("i_category"))], E.Literal(None, T.STRING))
    g = g.select(margin, col("i_category"), col("i_class"), lochier,
                 parent, names=["gross_margin", "i_category", "i_class",
                                "lochierarchy", "parent_cat"])
    w = g.window([(Rank(), "rank_within_parent")],
                 partition_by=["lochierarchy", "parent_cat"],
                 order_by=[("gross_margin", True, True)])
    w = w.with_column("sort_cat", E.CaseWhen(
        [(E.EqualTo(col("lochierarchy"), E.Literal(0)),
          col("i_category"))], E.Literal(None, T.STRING)))
    df = (w.select(*(col(n) for n in NAMES), names=NAMES)
          .sort(("lochierarchy", False, False), ("sort_cat", True, True),
                ("rank_within_parent", True, True),
                ("i_category", True, True), ("i_class", True, True))
          .limit(LIMIT))
    physical = df.physical()
    reasons = physical.fallback_reasons()
    check = getattr(physical, "whole_plan_trace_errors", None)
    errors = ["the tree has no whole-plan trace check"] if check is None \
        else check()
    if physical.kind != "device" or reasons or errors:
        raise SystemExit(
            "benchmark FAILED: this tree cannot run TPC-DS Q36 as "
            f"whole-plan programs (plan kind {physical.kind!r}, fallback "
            f"reasons {reasons}, trace {errors}); the cell is not run on "
            "another engine")
    return df


def _keys(col) -> np.ndarray:
    """Surrogate keys as int64, a null as -1 (no key is negative)."""
    return C.ints(pc.fill_null(col, -1)).astype(np.int64)


def _codes(col):
    """A string column as (codes, names): code -1 for a null."""
    enc = C._one_chunk(col).dictionary_encode()
    codes = enc.indices.to_numpy(zero_copy_only=False)
    codes = np.where(enc.indices.is_valid().to_numpy(zero_copy_only=False),
                     codes, -1).astype(np.int64)
    return codes, enc.dictionary.to_pylist()


def _rank_key(margin):
    return (margin is not None, margin if margin is not None else 0.0)


def reference(tables, money=np.int64):
    """numpy on exact integer cents.  Each dimension row is found by its
    surrogate key; the three grouping sets are summed directly, not by
    expanding rows; each sum becomes a double as Spark casts a decimal (the
    exact value rounded once: Python's int / int), the margin is one IEEE
    division, and a rank is 1 + the rows of its partition ordered before
    it, so that ties share the lowest."""
    ss, dd, item, store = (tables[n] for n in
                           ("store_sales", "date_dim", "item", "store"))
    date_row = C.lookup(C.ints(dd["d_date_sk"]), _keys(ss["ss_sold_date_sk"]))
    years = C.ints(dd["d_year"])
    keep = date_row >= 0
    keep[keep] = years[date_row[keep]] == YEAR
    item_row = C.lookup(C.ints(item["i_item_sk"]), _keys(ss["ss_item_sk"]))
    store_row = C.lookup(C.ints(store["s_store_sk"]),
                         _keys(ss["ss_store_sk"]))
    state_ok = np.array([v in STATES for v in store["s_state"].to_pylist()],
                        bool)
    keep &= item_row >= 0
    keep &= store_row >= 0
    keep[keep] = state_ok[store_row[keep]]

    cat, cat_names = _codes(item["i_category"])
    cls, cls_names = _codes(item["i_class"])
    rows = item_row[keep]
    pair = (cat[rows] + 1) * (len(cls_names) + 1) + (cls[rows] + 1)
    keys, profit = C.group_sum(pair, C.cents(ss["ss_net_profit"],
                                             money)[keep])
    _, sales = C.group_sum(pair, C.cents(ss["ss_ext_sales_price"],
                                         money)[keep])
    sums = {}                     # (category, class, level) -> [profit, sales]
    for k, p, s in zip(keys.tolist(), profit.tolist(), sales.tolist()):
        c, k2 = divmod(k, len(cls_names) + 1)
        name_c = cat_names[c - 1] if c else None
        name_k = cls_names[k2 - 1] if k2 else None
        for group in ((name_c, name_k, 0), (name_c, None, 1),
                      (None, None, 2)):
            acc = sums.setdefault(group, [0, 0])
            acc[0] += p
            acc[1] += s

    rows, within = [], {}
    for (name_c, name_k, level), (p, s) in sums.items():
        p_d, s_d = C.whole(p) / 100, C.whole(s) / 100
        margin = p_d / s_d if s_d != 0 else None
        part = (level, name_c if level == 0 else None)
        within.setdefault(part, []).append(_rank_key(margin))
        rows.append((margin, name_c, name_k, level, part))
    out = [(margin, name_c, name_k, level,
            1 + sum(m < _rank_key(margin) for m in within[part]),
            name_c if level == 0 else None)
           for margin, name_c, name_k, level, part in rows]

    def order(row):
        margin, name_c, name_k, level, rank, sort_cat = row
        return (-level, sort_cat is not None, sort_cat or "", rank,
                name_c is not None, name_c or "", name_k is not None,
                name_k or "")
    top = sorted(out, key=order)[:LIMIT]
    columns = list(zip(*top)) if top else [()] * len(NAMES)
    types = [pa.float64(), pa.string(), pa.string(), pa.int32(), pa.int32(),
             pa.string()]
    return pa.table({n: pa.array(list(v), ty)
                     for n, v, ty in zip(NAMES, columns, types)})


def needed_bytes(tables, answer):
    """harness/columns.py's count, with the null store keys counted as a
    key value: the width that holds them and the live keys together."""
    ss = tables["store_sales"]
    at = ss.schema.get_field_index("ss_store_sk")
    filled = dict(tables, store_sales=ss.set_column(
        at, "ss_store_sk", pc.fill_null(ss["ss_store_sk"], 0)))
    return C.needed_bytes(filled, SOURCE_COLUMNS, answer)
