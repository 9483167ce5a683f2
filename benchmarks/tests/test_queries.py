import pytest

from data import tpch_gen
from harness import columns as C
from harness import compare

QUERIES = ["q1", "q3", "q5", "q6", "q13"]


@pytest.fixture(scope="module")
def modules():
    import importlib
    return {q: importlib.import_module(f"queries.{q}") for q in QUERIES}


@pytest.fixture(scope="module")
def tables(modules):
    return tpch_gen.gen_tables(0.002, 2**31 + 11, C.merge_columns(
        m.SOURCE_COLUMNS for m in modules.values()))


@pytest.mark.parametrize("q", QUERIES)
def test_reference_agrees_with_the_engines_cpu_path(q, modules, tables):
    """Two witnesses that share no code: the plain numpy reference and the
    engine's pyarrow path (`sql.enabled=false`), at scale 0.002."""
    from spark_rapids_tpu.session import TpuSession
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
    answer = modules[q].build(cpu, tables).collect()
    reference = modules[q].reference(tables)
    assert reference.num_rows > 0
    assert compare.table_gaps(answer, reference) == (0, 0.0)


def test_needed_bytes_of_q6_by_hand(modules, tables):
    """By value range: l_shipdate in days since 1970 (8036..10561) 2 B,
    l_discount 0..10 cents 1 B, l_quantity 100..5000 cents 2 B,
    l_extendedprice 90,100..10,495,000 cents 4 B = 9 B a row, plus the
    answer's one cell (1.5 x 10^9 unscaled at this scale: 4 B)."""
    answer = modules["q6"].reference(tables)
    rows = tables["lineitem"].num_rows
    assert modules["q6"].needed_bytes(tables, answer) == 9 * rows + 4
    # strings: the dictionary code where few values, else length + 4
    assert C.column_width(tables["lineitem"]["l_returnflag"]) == 1.0
    import pyarrow as pa
    names = pa.array([f"Customer#{i:09d}" for i in range(70_000)])
    assert C.column_width(names) == 18.0 + 4.0
    assert C._fixed_width(-1, 127) == 1.0 and C._fixed_width(0, 255) == 1.0
    assert C._fixed_width(-129, 0) == 2.0 and C._fixed_width(0, 2**32) == 8.0


def test_same_seed_same_tables_and_columns_do_not_disturb_each_other():
    a = tpch_gen.gen_tables(0.002, 7, {"lineitem": ["l_discount", "l_tax"]})
    b = tpch_gen.gen_tables(0.002, 7, {"lineitem": ["l_tax"]})
    c = tpch_gen.gen_tables(0.002, 8, {"lineitem": ["l_tax"]})
    assert a["lineitem"]["l_tax"].equals(b["lineitem"]["l_tax"])
    assert not b["lineitem"]["l_tax"].equals(c["lineitem"]["l_tax"])
    assert tpch_gen.row_counts(10)["lineitem"] == 59_986_052


def test_the_generator_populates_as_clause_4_2_3():
    import numpy as np
    import pyarrow.compute as pc
    t = tpch_gen.gen_tables(0.05, 2**32 + 3, {
        "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_comment"],
        "lineitem": ["l_orderkey", "l_shipdate", "l_quantity",
                     "l_extendedprice"]})
    o_key = C.ints(t["orders"]["o_orderkey"])
    l_key = C.ints(t["lineitem"]["l_orderkey"])
    assert t["orders"].num_rows == 75_000
    assert t["lineitem"].num_rows == int(6_001_215 * 0.05)
    assert tpch_gen.row_counts(1)["lineitem"] == 6_001_215
    # sparse order keys, 1 to 7 lines an order, clustered by order key
    assert o_key[:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 33]
    assert (np.diff(l_key) >= 0).all()
    keys, lines = np.unique(l_key, return_counts=True)
    assert np.array_equal(keys, o_key)
    assert lines.min() == 1 and lines.max() == 7
    assert abs(np.bincount(lines)[1:] / len(lines) - 1 / 7).max() < 0.01
    # a line ships 1 to 121 days after its order
    wait = C.ints(t["lineitem"]["l_shipdate"]) - np.repeat(
        C.ints(t["orders"]["o_orderdate"]), lines)
    assert wait.min() == 1 and wait.max() == 121
    assert (C.ints(t["orders"]["o_custkey"]) % 3 != 0).all()
    # price = quantity x the part's retail price of 900.00 .. 2098.99
    unit = C.cents(t["lineitem"]["l_extendedprice"]) * 100 \
        // C.cents(t["lineitem"]["l_quantity"])
    assert 90_000 <= unit.min() and unit.max() <= 209_900
    # comments: text strings [19, 78], nearly all distinct, about 1% of
    # them LIKE '%special%requests%' (1.07% in TPC-H's own Q13 answer)
    comment = t["orders"]["o_comment"]
    sizes = pc.binary_length(comment)
    assert pc.min(sizes).as_py() == 19 and pc.max(sizes).as_py() == 78
    assert pc.count_distinct(comment).as_py() > 0.98 * len(comment)
    share = pc.sum(pc.match_like(comment, "%special%requests%")).as_py() \
        / len(comment)
    assert 0.007 < share < 0.014
