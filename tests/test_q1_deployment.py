"""TPC-H Q1 over a cached lineitem as a deployment (ISSUE 27): the
benchmark's own Q1 against its plain reference through the whole-plan
path with lineitem in many batches, the aggregates' strategy counters
(`agg.strategy.*`, `agg.capacity_rows`, `agg.partial_batches`), and what
the manifest applies to the two Q1 cells.  CPU backend, small sizes."""
import os
import sys

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.ops.groupby import MASKED_DOMAIN_MAX
from spark_rapids_tpu.plan.aggregates import Count, Sum
from spark_rapids_tpu.session import TpuSession, col

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmarks")
WHOLE = {"spark.rapids.tpu.sql.compile.wholePlan": "ON"}
STRATEGIES = ("dense", "packed_sort", "lexsort", "reduce")


@pytest.fixture
def bench():
    """The benchmark's modules, imported as benchmarks/run.py imports
    them (`benchmarks/` on the path), and forgotten again afterwards."""
    import importlib
    import types
    before = set(sys.modules)
    sys.path[:0] = [_BENCH]
    try:
        yield types.SimpleNamespace(
            gen=importlib.import_module("data.tpch_gen"),
            q1=importlib.import_module("queries.q1"),
            compare=importlib.import_module("harness.compare"),
            checks=importlib.import_module("harness.checks"),
            manifest=importlib.import_module("harness.manifest"))
    finally:
        sys.path.remove(_BENCH)
        for name in set(sys.modules) - before:
            if name.split(".")[0] in ("data", "queries", "harness"):
                del sys.modules[name]


def _agg_counts(metrics):
    return {k: v for k, v in metrics.items() if k.startswith("agg.")}


def test_q1_in_many_batches_is_exact_and_counts_what_the_plan_says(bench):
    """The SF10 cell's shape at a small size: lineitem in 8 batches, one
    seam (the aggregate, whose dense output is sliced), each batch's
    partial aggregate and their merge all `dense`."""
    tables = bench.gen.gen_tables(0.01, 2147486111, bench.q1.SOURCE_COLUMNS)
    rows = tables["lineitem"].num_rows
    conf = dict(WHOLE, **{
        "spark.rapids.tpu.sql.batchSizeRows": "8192",
        # the chip's plan: 4M-row buckets are over the seam gate
        "spark.rapids.tpu.sql.compile.seamSplitMinRows": "1"})
    df = bench.q1.build(TpuSession(conf), tables)
    batches = -(-rows // 8192)
    assert batches >= 5
    reference = bench.q1.reference(tables)
    for _ in range(2):               # compiled, then adopted from the cache
        answer = df.collect()
        verdict = bench.compare.judge([("q1", answer)], {"q1": reference}, 0)
        assert verdict["correct"], verdict["numbers"]
        m = df.metrics()
        assert bench.checks.plan_faults(df) == []
        assert bench.checks.collect_faults(m) == []
        assert m["overhead.seam_count"] == 1
        assert "overhead.seam_lazy_count" not in m
        assert m["exec_dispatches"] == 2
        counts = _agg_counts(m)
        assert counts.pop("agg.capacity_rows") >= rows
        # 12 buckets: every dense program is the one without scatters
        assert counts == {"agg.strategy.dense": batches + 1,
                          "agg.strategy.dense_masked": batches + 1,
                          "agg.partial_batches": batches}


def _keyed(s, table, keys):
    df = s.from_arrow(table)
    return (df.group_by(*keys) if keys else df).agg(
        (Sum(col("v")), "sv"), (Count(None), "c"))


@pytest.mark.parametrize("engine", ["eager", "whole_plan"])
@pytest.mark.parametrize("strategy,keys", [
    ("dense_masked", ["s"]),         # a small dictionary: 4 buckets
    ("dense", ["w"]),                # a domain to scatter into
    ("packed_sort", ["i", "j"]),     # ranged integers: one packed lane
    ("lexsort", ["i", "d"]),         # a double key packs into nothing
    ("reduce", [])])
def test_each_strategy_bumps_its_own_key_and_no_other(engine, strategy,
                                                      keys):
    rng = np.random.default_rng(3)
    n = 3000
    table = pa.table({
        "s": pa.array(rng.choice(["a", "b", "c"], n)),
        # one word more than ops/groupby.py reduces under bucket masks
        "w": pa.array([f"w{i % (MASKED_DOMAIN_MAX + 1)}" for i in range(n)]),
        "i": pa.array(rng.integers(0, 50, n), pa.int64()),
        "j": pa.array(rng.integers(-5, 5, n), pa.int64()),
        "d": pa.array(rng.integers(0, 9, n).astype(np.float64)),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64())})
    df = _keyed(TpuSession(WHOLE if engine == "whole_plan" else {}),
                table, keys)
    df.collect()
    want = {f"agg.strategy.{strategy}": 1, "agg.capacity_rows": 4096,
            "agg.partial_batches": 1}
    if strategy in ("packed_sort", "lexsort"):
        want["agg.strategy.sorted"] = 1
    if strategy == "dense_masked":   # counted as a dense program too
        want["agg.strategy.dense"] = 1
    assert _agg_counts(df.metrics()) == want
    df.collect()                     # counted per run, not per compile
    assert _agg_counts(df.metrics()) == want


def test_two_aggregates_of_a_split_plan_both_count():
    """Counters add up over the programs of a collect: an aggregate in
    each of two segments (the keys are per program run, where the other
    host numbers of a trace overwrite one another)."""
    rng = np.random.default_rng(5)
    n = 3000
    table = pa.table({"i": pa.array(rng.integers(0, 50, n), pa.int64()),
                      "v": pa.array(rng.integers(0, 1000, n), pa.int64())})
    s = TpuSession(dict(WHOLE, **{
        "spark.rapids.tpu.sql.compile.seamSplitMinRows": "1"}))
    inner = s.from_arrow(table).group_by("i").agg((Sum(col("v")), "v"))
    df = inner.group_by("v").agg((Count(None), "c")).sort("v")
    df.collect()
    m = df.metrics()
    assert m["overhead.seam_count"] >= 1
    assert sum(m.get(f"agg.strategy.{x}", 0) for x in STRATEGIES) == 2
    assert m["agg.partial_batches"] == 2


@pytest.mark.parametrize("cell,config,trace_queries", [
    ("tpch-sf1.groupby", "tpch-sf1", 8), ("tpch-sf10.q1", "tpch-sf10-q1", 2)])
def test_manifest_of_the_q1_cells(bench, cell, config, trace_queries):
    found = bench.manifest.Cell(cell)
    assert (found.config_name, found.chips) == (config, 1)
    assert found.config["session_conf"] == {}
    assert found.query_names == ["q1"]
    assert found.queries["q1"] is bench.q1
    assert found.spec["loop"] == {"kind": "closed_round_robin",
                                  "trace_queries": trace_queries}
    assert found.end_to_end == ["query_ms", "setup_s"]   # no query_p95_ms
    applied = {name: spec for name, spec, _reader in found.per_layer}
    assert not any(name.startswith("wall_ms.") for name in applied)
    assert {"xla_programs_roofline", "device_idle_pct",
            "peak_hbm_GB"} <= set(applied)
    for name, key in (("agg_masked_dense_per_query",
                       "agg.strategy.dense_masked"),
                      ("agg_sort_strategies_per_query",
                       "agg.strategy.sorted"),
                      ("agg_partial_batches_per_query",
                       "agg.partial_batches"),
                      ("agg_capacity_rows_per_query", "agg.capacity_rows")):
        assert (applied[name]["reader"], applied[name]["key"],
                applied[name]["per"]) == ("ctx_metric", key, "query")


def test_the_masked_dense_metric_and_its_manifest_entry_agree(bench):
    """ISSUE 28's one per-layer metric: data only, applied in every cell
    (no `workloads` list), reading the key that `HashAggregate._note`
    bumps."""
    import json
    entry = [m for m in bench.manifest.benchmark()["per_layer"]
             if m["name"] == "agg_masked_dense_per_query"]
    assert entry == [{"name": "agg_masked_dense_per_query",
                      "unit": "count", "better": "higher",
                      "source": "program_counter",
                      "layer": "XLA programs (kernels)",
                      "moves": "query_ms"}]
    with open(os.path.join(_BENCH, "layer_metrics",
                           "agg_masked_dense_per_query.json")) as f:
        spec = json.load(f)
    assert {k: spec[k] for k in ("reader", "key", "per", "unit", "layer")} \
        == {"reader": "ctx_metric", "key": "agg.strategy.dense_masked",
            "per": "query", "unit": entry[0]["unit"],
            "layer": entry[0]["layer"]}
    for cell in ("tpch-sf1.joins", "tpch-sf10.q6", "tpch-sf1.groupby",
                 "tpch-sf10.q1"):
        assert "agg_masked_dense_per_query" in [
            name for name, _spec, _reader in
            bench.manifest.Cell(cell).per_layer]


def test_the_q1_configuration_is_its_own_deployment(bench):
    """`tpch-sf10-q1` is SF10's lineitem as Q1 holds it (7 columns), under a
    source of its own; what a run reads of it is what `tpch-sf10` gives."""
    entries = {c["name"]: c for c in bench.manifest.benchmark()["configs"]}
    new, old = entries["tpch-sf10-q1"], entries["tpch-sf10"]
    assert new["source"] != old["source"] and "2.4.1 Q1" in new["source"]
    assert new["file"] == "benchmarks/configs/tpch-sf10-q1.json"
    found = bench.manifest.Cell("tpch-sf10.q1").config
    assert (found["name"], found["source"]) == (new["name"], new["source"])
    assert found["reduced"] == new["reduced"] == ["tables", "columns"]
    q6 = bench.manifest.Cell("tpch-sf10.q6").config
    for key in ("scale_factor", "generator", "session_conf", "rows", "chips",
                "tables"):
        assert found[key] == q6[key], key
    assert set(found["guarantees"]) == set(q6["guarantees"])
    for column in bench.q1.SOURCE_COLUMNS["lineitem"]:
        assert column in found["columns"], column
