"""TPC-DS Q36 over cached store_sales, date_dim, item and store as a
deployment: the benchmark's own Q36 against its plain reference on both
engines (a rank tie and a category with no store planted), the window
queries of the TPC-DS tranche as one whole-plan program, and a window
plan's canonical program key.  CPU backend, small sizes."""
import importlib
import os
import sys
import types

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from spark_rapids_tpu import tpcds
from spark_rapids_tpu.plan import expressions as E
from spark_rapids_tpu.plan.window import Rank
from spark_rapids_tpu.session import DataFrame, TpuSession, col

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmarks")
WHOLE = {"spark.rapids.tpu.sql.compile.wholePlan": "ON"}
ENGINES = {"eager": {"spark.rapids.tpu.sql.compile.wholePlan": "OFF"},
           "whole_plan": WHOLE}
SCALE = 0.01


@pytest.fixture
def bench():
    """The benchmark's modules, imported as benchmarks/run.py imports
    them (`benchmarks/` on the path), and forgotten again afterwards."""
    before = set(sys.modules)
    sys.path[:0] = [_BENCH]
    try:
        yield types.SimpleNamespace(
            gen=importlib.import_module("data.tpcds_gen"),
            base=importlib.import_module("data.tpch_gen"),
            q36=importlib.import_module("queries.ds_q36"),
            compare=importlib.import_module("harness.compare"),
            checks=importlib.import_module("harness.checks"),
            columns=importlib.import_module("harness.columns"))
    finally:
        sys.path.remove(_BENCH)
        for name in set(sys.modules) - before:
            if name.split(".")[0] in ("data", "queries", "harness"):
                del sys.modules[name]


def _items_of(bench, tables, pairs):
    """Row mask of store_sales whose item is one of `pairs`."""
    item = tables["item"]
    hit = {sk for sk, c, k in zip(item["i_item_sk"].to_pylist(),
                                  item["i_category"].to_pylist(),
                                  item["i_class"].to_pylist())
           if (c, k) in pairs or (c, None) in pairs}
    sold = bench.columns.ints(tables["store_sales"]["ss_item_sk"])
    return np.isin(sold, np.array(sorted(hit), dtype=np.int64))


def _plant_tie(bench, tables):
    """Every sale of two classes of one category earns 1.00 on 4.00: the
    two margins are both exactly 0.25, so they tie in rank."""
    item = tables["item"]
    classes = sorted({k for c, k in zip(item["i_category"].to_pylist(),
                                        item["i_class"].to_pylist())
                      if c == "Jewelry"})
    pairs = {("Jewelry", k) for k in classes[3:5]}
    rows = _items_of(bench, tables, pairs)
    ss = tables["store_sales"]
    money = {"ss_net_profit": 100, "ss_ext_sales_price": 400}
    for name, cents in money.items():
        values = bench.columns.cents(ss[name]).copy()
        values[rows] = cents
        ss = ss.set_column(ss.schema.get_field_index(name), name,
                           bench.base.money(values, 7, 2))
    return dict(tables, store_sales=ss), pairs


def _plant_no_store(bench, tables):
    """Every sale of one category has a null store: the category's rows
    find no store, and the category drops out of the answer."""
    rows = _items_of(bench, tables, {("Music", None)})
    ss = tables["store_sales"]
    keys = ss["ss_store_sk"]
    null = pc.or_(pc.is_null(keys), pa.array(rows))
    ss = ss.set_column(ss.schema.get_field_index("ss_store_sk"),
                       "ss_store_sk",
                       pc.if_else(null, pa.scalar(None, pa.int64()), keys))
    return dict(tables, store_sales=ss), "Music"


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("seed,planted", [
    (2147490011, None), (3000005519, None), (4294970999, None),
    (2147490023, "tie"), (3000005531, "no_store")])
def test_q36_equals_its_reference_value_by_value_and_in_order(
        bench, engine, seed, planted):
    tables = bench.gen.gen_tables(SCALE, seed, bench.q36.SOURCE_COLUMNS)
    if planted == "tie":
        tables, tied = _plant_tie(bench, tables)
    elif planted == "no_store":
        tables, gone = _plant_no_store(bench, tables)
    df = bench.q36.build(TpuSession(ENGINES[engine]), tables)
    assert df.physical().fallback_reasons() == []
    answer = df.collect()
    reference = bench.q36.reference(tables)
    verdict = bench.compare.judge([("ds_q36", answer)],
                                  {"ds_q36": reference}, 0)
    assert verdict["correct"], verdict["numbers"]
    m = df.metrics()
    assert not m.get("whole_plan_fallbacks")
    if engine == "whole_plan":
        assert bench.checks.collect_faults(m) == []
        assert m["whole_plan_compiled_queries"] == 1
        assert m["window.in_program"] == 1
        assert m["expand.capacity_rows"] > 0
    rows = answer.to_pylist()
    if planted == "tie":
        ranks = {(r["i_category"], r["i_class"]): r for r in rows
                 if r["lochierarchy"] == 0}
        a, b = (ranks[p] for p in sorted(tied))
        assert a["gross_margin"] == b["gross_margin"] == 0.25
        assert a["rank_within_parent"] == b["rank_within_parent"]
        after = [r["rank_within_parent"] for r in rows
                 if r["lochierarchy"] == 0 and r["i_category"] == "Jewelry"
                 and r["rank_within_parent"] > a["rank_within_parent"]]
        assert not after or min(after) == a["rank_within_parent"] + 2
    if planted == "no_store":
        assert gone not in {r["i_category"] for r in rows}
        assert len([r for r in rows if r["lochierarchy"] == 1]) == 9


WINDOW_QUERIES = ["q12", "q20", "q36", "q70", "q86", "q98"]


@pytest.fixture(scope="module")
def ds_tables():
    return tpcds.gen_tables(scale=0.0005)


@pytest.mark.parametrize("qname", WINDOW_QUERIES)
def test_window_queries_run_as_one_whole_plan_program(qname, ds_tables):
    """Every query of the tranche with a window runs whole-plan, the
    window inside the program, and equals the CPU oracle (doubles to the
    reduction-order tolerance of tests/test_tpcds.py).  q93 is left out:
    it falls back for its build side on two keys that is not unique
    (ROADMAP C10), not for a window."""
    df = tpcds.QUERIES[qname](TpuSession(WHOLE), ds_tables)
    assert df.physical().whole_plan_trace_errors() == []
    got = df.collect()
    m = df.metrics()
    assert m["whole_plan_compiled_queries"] == 1
    assert not m.get("whole_plan_fallbacks")
    assert m["window.in_program"] >= 1
    oracle = DataFrame(df._plan, TpuSession(
        {"spark.rapids.tpu.sql.enabled": "false"})).collect()
    assert got.schema.names == oracle.schema.names
    assert got.num_rows == oracle.num_rows > 0
    for name in got.schema.names:
        for g, o in zip(got[name].to_pylist(), oracle[name].to_pylist()):
            if isinstance(g, float) and isinstance(o, float):
                assert g == pytest.approx(o, rel=1e-9, abs=1e-12)
            else:
                assert g == o, (qname, name)


def _ranked(session, table, least):
    return (session.from_arrow(table)
            .filter(E.GreaterThan(col("x"), E.Literal(least)))
            .window([(Rank(), "r")], partition_by=["g"],
                    order_by=[("x", True, True)])
            .sort("g", "x"))


def test_a_window_plan_has_a_canonical_key_and_literals_share_it():
    from spark_rapids_tpu.exec.compiled import _node_exprs, plan_structure_key
    from spark_rapids_tpu.exec.window import WindowExec
    table = pa.table({"g": np.arange(40) % 3, "x": np.arange(40) * 1.5})
    session = TpuSession(WHOLE)
    first = _ranked(session, table, 3.0)
    root = first.physical().root
    windows = [n for n in _walk(root) if isinstance(n, WindowExec)]
    assert len(windows) == 1 and _node_exprs(windows[0]) is not None
    assert plan_structure_key(root, session.conf) is not None
    assert first.collect()["r"].to_pylist()[:3] == [1, 2, 3]
    second = _ranked(session, table, 10.0)
    assert plan_structure_key(second.physical().root, session.conf) == \
        plan_structure_key(root, session.conf)
    out = second.collect()
    assert second.metrics().get("whole_plan_structure_hits") == 1
    assert min(out["x"].to_pylist()) > 10.0


def test_rank_ties_only_equal_doubles():
    """Peers by the value's bits: -0.0 and 0.0 tie, as in Spark; two
    doubles one ulp apart do not."""
    x = [0.1, np.nextafter(0.1, 1.0), -0.0, 0.0, 0.1, 2.0]
    table = pa.table({"g": [1] * len(x), "x": x})
    for engine in ENGINES.values():
        out = (TpuSession(engine).from_arrow(table)
               .window([(Rank(), "r")], partition_by=["g"],
                       order_by=[("x", True, True)])
               .collect().to_pydict())
        ranks = sorted(zip(out["x"], out["r"]), key=lambda p: p[1])
        assert [r for _x, r in ranks] == [1, 1, 3, 3, 5, 6]


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)
