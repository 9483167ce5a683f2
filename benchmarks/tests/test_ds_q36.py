"""The TPC-DS Q36 cell's own pieces: the generator's row counts and item
hierarchy, the reference against a plain Python walk of the query, the
controls and planted faults on the cell at a small size, and what the
manifest finds.  CPU only."""
import argparse
import datetime
import time

import pytest

from data import tpcds_gen
from harness import cell, columns as C, compare, controls, manifest
from queries import ds_q36

SEED = 2**31 + 3967


def test_row_counts_are_the_specifications_and_scale_elsewhere():
    assert tpcds_gen.row_counts(10) == {
        "store_sales": 28_800_991, "item": 102_000, "store": 102,
        "date_dim": 73_049}
    assert tpcds_gen.row_counts(1)["store_sales"] == 2_880_404
    small = tpcds_gen.row_counts(0.05)
    assert small == {"store_sales": 144_020, "item": 900, "store": 8,
                     "date_dim": 73_049}
    t = tpcds_gen.gen_tables(0.05, SEED, ds_q36.SOURCE_COLUMNS)
    assert {k: v.num_rows for k, v in t.items()} == {
        k: small[k] for k in ("store_sales", "date_dim", "item", "store")}


def test_date_dim_is_every_day_keyed_by_its_julian_day():
    dd = tpcds_gen.gen_tables(0.01, SEED, {"date_dim": ["d_date_sk",
                                                        "d_year"]})["date_dim"]
    sk, year = dd["d_date_sk"].to_pylist(), dd["d_year"].to_pylist()
    assert (sk[0], sk[-1]) == (2_415_022, 2_488_070)
    for i in (0, 1, 365, 36_524, 36_525, 73_048):
        day = datetime.date(1900, 1, 2) + datetime.timedelta(days=i)
        assert year[i] == day.year and sk[i] == day.toordinal() + 1_721_425


def test_the_item_hierarchy_nests_classes_under_their_category():
    assert len(tpcds_gen.CLASSES) == 10 and len(tpcds_gen.PAIRS) == 100
    assert sorted({len(v) for v in tpcds_gen.CLASSES.values()}) == [4, 16]
    item = tpcds_gen.gen_tables(0.5, SEED, {
        "item": ["i_item_sk", "i_category", "i_class"]})["item"]
    pairs = set(zip(item["i_category"].to_pylist(),
                    item["i_class"].to_pylist()))
    assert pairs == set(tpcds_gen.PAIRS)        # 9,000 items: every pair
    assert len({k for _c, k in pairs}) == 99    # "kids": Shoes and Home


def test_store_sales_keys_dates_and_money_follow_the_rules():
    t = tpcds_gen.gen_tables(0.05, SEED, ds_q36.SOURCE_COLUMNS)
    ss = t["store_sales"]
    store = ss["ss_store_sk"]
    assert 0.03 < store.null_count / len(store) < 0.05
    assert set(t["store"]["s_state"].to_pylist()) == set(ds_q36.STATES)
    sold = C.ints(ss["ss_sold_date_sk"])
    assert sold.min() >= tpcds_gen.julian(datetime.date(1998, 1, 1))
    assert sold.max() <= tpcds_gen.julian(datetime.date(2002, 12, 31))
    in_2001 = (sold >= tpcds_gen.julian(datetime.date(2001, 1, 1))) & \
        (sold <= tpcds_gen.julian(datetime.date(2001, 12, 31)))
    assert 0.18 < in_2001.mean() < 0.22
    profit = C.cents(ss["ss_net_profit"])
    sales = C.cents(ss["ss_ext_sales_price"])
    assert str(ss["ss_net_profit"].type) == "decimal128(7, 2)"
    assert (profit < 0).any() and (profit <= sales).all()
    assert sales.min() >= 30 and sales.max() < 10_000_000
    # a column asked for alone is the same column
    alone = tpcds_gen.gen_tables(0.05, SEED, {
        "store_sales": ["ss_net_profit"]})["store_sales"]
    assert alone["ss_net_profit"].equals(ss["ss_net_profit"])


def test_the_reference_follows_the_query_row_by_row():
    """Against a plain Python walk of the query at a small scale."""
    t = tpcds_gen.gen_tables(0.02, SEED, ds_q36.SOURCE_COLUMNS)
    ss, dd, item, store = (t[n].to_pydict() for n in
                           ("store_sales", "date_dim", "item", "store"))
    year = dict(zip(dd["d_date_sk"], dd["d_year"]))
    kind = dict(zip(item["i_item_sk"], zip(item["i_category"],
                                           item["i_class"])))
    state = dict(zip(store["s_store_sk"], store["s_state"]))
    sums = {}
    for d, i, s, sales, profit in zip(
            ss["ss_sold_date_sk"], ss["ss_item_sk"], ss["ss_store_sk"],
            ss["ss_ext_sales_price"], ss["ss_net_profit"]):
        if year.get(d) != ds_q36.YEAR or state.get(s) not in ds_q36.STATES:
            continue
        cat, cls = kind[i]
        for key in ((cat, cls, 0), (cat, None, 1), (None, None, 2)):
            acc = sums.setdefault(key, [0, 0])
            acc[0] += int(profit.scaleb(2))
            acc[1] += int(sales.scaleb(2))
    rows = []
    for (cat, cls, level), (p, s) in sums.items():
        margin = (p / 100) / (s / 100)
        part = (level, cat if level == 0 else None)
        rank = 1 + sum(1 for (c2, k2, l2), (p2, s2) in sums.items()
                       if (l2, c2 if l2 == 0 else None) == part
                       and (p2 / 100) / (s2 / 100) < margin)
        rows.append((margin, cat, cls, level, rank,
                     cat if level == 0 else None))
    rows.sort(key=lambda r: (-r[3], r[5] or "", r[4], r[1] or "",
                             r[2] or ""))
    reference = ds_q36.reference(t)
    assert reference.schema.names == ds_q36.NAMES
    assert reference.num_rows == ds_q36.LIMIT < len(rows)
    assert [tuple(r.values()) for r in reference.to_pylist()] == \
        rows[:ds_q36.LIMIT]


def _run(tamper, scale=0.01):
    args = argparse.Namespace(
        workload="tpcds-sf10.q36", seed=SEED, seconds=0.3, trace=0,
        scale=scale, rehearse_cpu=True, keep_trace=None, trace_queries=None)
    return cell.run_cell(args, time.perf_counter(), tamper,
                         say=lambda msg: None)


def test_sound_run_of_the_q36_cell_is_correct_and_complete():
    line = _run(None)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == line["checks"]["compared"] > 0
    assert set(line["metrics"]) >= {"query_ms", "setup_s"}
    assert line["checks"]["wrong_values"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("tamper", sorted(controls.BY_NAME))
def test_control_and_planted_faults_come_out_not_correct_on_q36(tamper):
    line = _run(controls.BY_NAME[tamper]())
    assert line["correct"] is False
    assert line["checks"]["wrong_values"]["value"] > 0


def test_a_tree_that_cannot_run_q36_whole_plan_ends_the_run():
    """The engine's CPU path stands in for a tree whose plan is not on the
    device: `build` names it and raises SystemExit."""
    from spark_rapids_tpu.session import TpuSession
    t = tpcds_gen.gen_tables(0.002, SEED, ds_q36.SOURCE_COLUMNS)
    off = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
    with pytest.raises(SystemExit, match="cannot run TPC-DS Q36"):
        ds_q36.build(off, t)


def test_the_manifest_finds_the_q36_cells_files():
    bench = manifest.benchmark()
    entry = [w for w in bench["workloads"] if w["name"] == "tpcds-sf10.q36"]
    assert entry == [dict(entry[0], config="tpcds-sf10-q36", traffic="q36",
                          chips=1)]
    c = manifest.Cell("tpcds-sf10.q36")
    assert c.generator is tpcds_gen and c.queries == {"ds_q36": ds_q36}
    assert c.spec["loop"] == {"kind": "closed_round_robin",
                              "trace_queries": 4}
    assert c.end_to_end == ["query_ms", "setup_s"]
    assert c.config["reduced"] == ["scale_factor", "tables", "columns"]
    mine = {"window_in_program_per_query": "window.in_program",
            "expand_rows_per_query": "expand.capacity_rows"}
    found = {n: spec for n, spec, _r in c.per_layer}
    for name, key in mine.items():
        assert found[name]["key"] == key
        m = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert m["workloads"] == ["tpcds-sf10.q36"]
        assert m["moves"] == "query_ms"
    # and only this cell reads them
    assert not set(mine) & {n for n, _s, _r in
                            manifest.Cell("tpch-sf10.q18").per_layer}
    assert compare.LIMITS == {"wrong_values": 0, "max_rel_gap": 0.0,
                              "answers_missing": 0}
