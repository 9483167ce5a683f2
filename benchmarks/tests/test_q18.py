"""The Q18 cell's own pieces (ISSUE 35): the generator that adds c_name and
o_totalprice to `tpch_gen`'s tables, the reference, the controls on the
cell at a small size, and what the manifest finds.  CPU only."""
import argparse
import time

import numpy as np
import pytest

from data import tpch_gen, tpch_gen_q18
from harness import cell, columns as C, compare, controls, manifest
from queries import q18

SEED = 2**31 + 2663


def test_every_column_both_generators_make_is_tpch_gens_own():
    """Same streams, same values for a seed, column by column, whatever
    else is asked for beside it."""
    for table, makers in tpch_gen.COLUMNS.items():
        names = [n for n in makers if n != "o_comment"]
        theirs = tpch_gen.gen_tables(0.01, SEED, {table: names})[table]
        mine = tpch_gen_q18.gen_tables(
            0.01, SEED, {table: names + sorted(
                tpch_gen_q18.EXTRA.get(table, {}))})[table]
        for name in names:
            assert mine[name].equals(theirs[name]), (table, name)
    with pytest.raises(KeyError, match="o_clerk"):
        tpch_gen_q18.gen_tables(0.01, SEED, {"orders": ["o_clerk"]})


def test_customer_names_are_the_key_in_nine_digits():
    t = tpch_gen_q18.gen_tables(0.01, SEED, {
        "customer": ["c_custkey", "c_name"]})["customer"]
    keys = t["c_custkey"].to_pylist()
    assert t["c_name"].to_pylist() == [f"Customer#{k:09d}" for k in keys]
    assert tpch_gen_q18.customer_names(
        np.array([1, 149_999_999])).to_pylist() == [
            "Customer#000000001", "Customer#149999999"]
    with pytest.raises(ValueError, match="nine digits"):
        tpch_gen_q18.customer_names(np.array([10**9]))


def test_o_totalprice_is_the_clauses_sum_in_python_integers():
    """Clause 4.2.3: the sum over the order's lines of l_extendedprice *
    (1 + l_tax) * (1 - l_discount), recomputed line by line in Python
    integers at six decimal places and rounded once, half up, to cents;
    and asking for o_totalprice alone gives the same column."""
    t = tpch_gen_q18.gen_tables(0.002, SEED, {
        "orders": ["o_orderkey", "o_totalprice"],
        "lineitem": ["l_orderkey", "l_extendedprice", "l_tax",
                     "l_discount"]})
    exact = {}
    for key, price, tax, disc in zip(
            t["lineitem"]["l_orderkey"].to_pylist(),
            C.cents(t["lineitem"]["l_extendedprice"]).tolist(),
            C.cents(t["lineitem"]["l_tax"]).tolist(),
            C.cents(t["lineitem"]["l_discount"]).tolist()):
        exact[key] = exact.get(key, 0) + price * (100 + tax) * (100 - disc)
    want = [(exact[k] + 5_000) // 10_000
            for k in t["orders"]["o_orderkey"].to_pylist()]
    assert C.cents(t["orders"]["o_totalprice"]).tolist() == want
    assert str(t["orders"]["o_totalprice"].type) == "decimal128(12, 2)"
    alone = tpch_gen_q18.gen_tables(0.002, SEED,
                                    {"orders": ["o_totalprice"]})
    assert alone["orders"]["o_totalprice"].equals(
        t["orders"]["o_totalprice"])
    assert max(want) > 2**24          # what float32 cannot hold


def test_the_reference_follows_the_clause_row_by_row():
    """Against a plain Python walk of clause 2.4.18 at a small scale."""
    tables = tpch_gen_q18.gen_tables(0.05, SEED, q18.SOURCE_COLUMNS)
    li, orders, cust = (tables[n].to_pydict()
                        for n in ("lineitem", "orders", "customer"))
    total = {}
    for k, q in zip(li["l_orderkey"], li["l_quantity"]):
        total[k] = total.get(k, 0) + q
    names = dict(zip(cust["c_custkey"], cust["c_name"]))
    rows = [(names[c], c, k, d, p, total[k]) for k, c, d, p in zip(
        orders["o_orderkey"], orders["o_custkey"], orders["o_orderdate"],
        orders["o_totalprice"]) if total[k] > q18.QUANTITY and c in names]
    rows.sort(key=lambda r: (-r[4], r[3]))
    reference = q18.reference(tables)
    assert 0 < reference.num_rows == len(rows) <= q18.LIMIT
    assert [tuple(r.values()) for r in reference.to_pylist()] == rows


def _run(tamper, scale=0.2):
    args = argparse.Namespace(
        workload="tpch-sf10.q18", seed=SEED, seconds=0.3, trace=0,
        scale=scale, rehearse_cpu=True, keep_trace=None, trace_queries=None)
    return cell.run_cell(args, time.perf_counter(), tamper,
                         say=lambda msg: None)


def test_sound_run_of_the_q18_cell_is_correct_and_complete():
    line = _run(None)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == line["checks"]["compared"] > 0
    assert set(line["metrics"]) >= {"query_ms", "setup_s"}
    assert line["checks"]["wrong_values"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("tamper", sorted(controls.BY_NAME))
def test_control_and_planted_faults_come_out_not_correct_on_q18(tamper):
    """SF0.2 has a dozen qualifying orders: one alone could have a total
    price that float32 happens to hold (one value in four past 2^24)."""
    line = _run(controls.BY_NAME[tamper]())
    assert line["correct"] is False
    assert line["checks"]["wrong_values"]["value"] > 0


def test_a_tree_that_cannot_plan_q18_onto_the_device_ends_the_run():
    """The engine's CPU path stands in for a tree that lacks the decimal
    rule: `build` names the reasons and raises SystemExit."""
    from spark_rapids_tpu.session import TpuSession
    tables = tpch_gen_q18.gen_tables(0.002, SEED, q18.SOURCE_COLUMNS)
    off = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
    with pytest.raises(SystemExit, match="cannot plan TPC-H Q18"):
        q18.build(off, tables)


def test_the_manifest_finds_the_q18_cells_files():
    bench = manifest.benchmark()
    entry = [w for w in bench["workloads"] if w["name"] == "tpch-sf10.q18"]
    assert entry == [dict(entry[0], config="tpch-sf10-q18", traffic="q18",
                          chips=1)]
    c = manifest.Cell("tpch-sf10.q18")
    assert c.generator is tpch_gen_q18 and c.queries == {"q18": q18}
    assert c.spec["loop"] == {"kind": "closed_round_robin",
                              "trace_queries": 2}
    assert c.end_to_end == ["query_ms", "setup_s"]
    mine = {"sort_pct_of_busy", "join_build_bound_rows_per_query",
            "wide_decimal_device_per_query"}
    assert mine <= {n for n, _s, _r in c.per_layer}
    for m in bench["per_layer"]:
        if m["name"] in mine:
            assert "tpch-sf10.q18" in m["workloads"]
            assert m["moves"] == "query_ms"
    assert compare.LIMITS == {"wrong_values": 0, "max_rel_gap": 0.0,
                              "answers_missing": 0}
