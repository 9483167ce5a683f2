"""TPC-H Q1 and Q6 over a lineitem sharded on a four-device mesh as a
deployment (ISSUE 32): the benchmark's own queries against their plain
references through `DataFrame.collect()` on the CPU mesh, the warm path
of a re-collected DataFrame (program from the process-wide cache under a
key that names the mesh, sharded lanes from the per-table cache), its
span and counters (`tpu.shard`, `mesh.*`), the separation of mesh and
one-chip programs, and what the manifest applies to `tpch-sf10.mesh4`.
CPU backend (8 virtual devices, tests/conftest.py), small sizes."""
import decimal
import gc
import importlib
import json
import os
import sys
import types

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec import compiled as C
from spark_rapids_tpu.obs.profile import QueryProfile
from spark_rapids_tpu.plan.aggregates import Sum
from spark_rapids_tpu.session import TpuSession, col

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmarks")
CELL = "tpch-sf10.mesh4"
WHOLE = {"spark.rapids.tpu.sql.compile.wholePlan": "ON",
         # lineitem in several upload batches, as the cell holds it
         "spark.rapids.tpu.sql.batchSizeRows": "8192"}
NEW_METRICS = {
    "wall_ms.q1": ("wall_by_query", "q1"),
    "wall_ms.q6": ("wall_by_query", "q6"),
    "shard_ms_per_query": ("ctx_metric", "overhead.shard_ms"),
    "mesh_reshard_bytes_per_query": ("ctx_metric", "mesh.reshard_bytes"),
    "mesh_replicated_lanes_per_query": ("ctx_metric",
                                        "mesh.replicated_lanes"),
    "collectives_pct_of_busy": ("trace_ops", "collectives")}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, imported as benchmarks/run.py imports
    them (`benchmarks/` on the path), and forgotten again afterwards."""
    before = set(sys.modules)
    sys.path[:0] = [_BENCH]
    try:
        yield types.SimpleNamespace(
            gen=importlib.import_module("data.tpch_gen"),
            q1=importlib.import_module("queries.q1"),
            q6=importlib.import_module("queries.q6"),
            columns=importlib.import_module("harness.columns"),
            compare=importlib.import_module("harness.compare"),
            checks=importlib.import_module("harness.checks"),
            manifest=importlib.import_module("harness.manifest"),
            ctx_metric=importlib.import_module("harness.readers.ctx_metric"),
            trace_ops=importlib.import_module("harness.readers.trace_ops"))
    finally:
        sys.path.remove(_BENCH)
        for name in set(sys.modules) - before:
            if name.split(".")[0] in ("data", "queries", "harness"):
                del sys.modules[name]


@pytest.fixture(scope="module")
def mesh_conf(bench):
    """The configuration's own session conf (the two mesh keys), with the
    engine the chip runs and small batches."""
    return dict(bench.manifest.Cell(CELL).config["session_conf"], **WHOLE)


@pytest.fixture(scope="module")
def tables(bench):
    """Seeded `tpch_gen` data, the columns the cell's two queries read."""
    wanted = bench.columns.merge_columns(
        [bench.q1.SOURCE_COLUMNS, bench.q6.SOURCE_COLUMNS])
    return bench.gen.gen_tables(0.01, 2147486111, wanted)


def _uploads_since(before):
    """Keys the upload cache gained (a scan that prunes columns uploads a
    pruned table of its own, so not keyed by the DataFrame's table); a
    sharded copy's key has a fifth part, the mesh."""
    return [k for k in C._SCAN_UPLOAD_CACHE if k not in before]


@pytest.mark.parametrize("query", ["q1", "q6"])
def test_mesh_answers_equal_the_reference_and_the_one_chip_engine(
        bench, tables, mesh_conf, query, eight_devices):
    """Limits 0: exact decimals, every row, in the query's order."""
    module = getattr(bench, query)
    df = module.build(TpuSession(mesh_conf), tables)
    assert bench.checks.plan_faults(df) == []
    answer = df.collect()
    m = df.metrics()
    assert bench.checks.collect_faults(m) == []
    assert m["mesh.devices"] == 4 and m["exec_dispatches"] == 1
    verdict = bench.compare.judge([(query, answer)],
                                  {query: module.reference(tables)}, 0)
    assert verdict["correct"], verdict["numbers"]
    assert all(n["value"] == 0 for n in verdict["numbers"].values())
    one_chip = module.build(TpuSession(WHOLE), tables)
    assert answer.equals(one_chip.collect())
    assert "mesh.devices" not in one_chip.metrics()


@pytest.mark.parametrize("frame", ["same", "rebuilt"])
@pytest.mark.parametrize("query", ["q1", "q6"])
def test_a_warm_mesh_collect_places_nothing_and_compiles_nothing(
        bench, tables, mesh_conf, query, frame, eight_devices):
    """The second collect finds its lanes in the per-table cache, and its
    program in the plan its DataFrame kept (`same`) or, where the
    DataFrame was built again and plans anew, under the mesh's key in the
    process-wide cache (`rebuilt`)."""
    module = getattr(bench, query)
    # a table of this test's own, so that the first collect is cold
    mine = {"lineitem": tables["lineitem"].slice(0)}
    session = TpuSession(mesh_conf)
    df = module.build(session, mine)
    before = set(C._SCAN_UPLOAD_CACHE)
    first = df.collect()
    cold = df.metrics()
    assert cold["mesh.reshard_bytes"] > 0 and cold["overhead.shard_ms"] > 0
    assert cold.get("compile_cache_misses", 0) == 1
    assert cold.get("whole_plan_structure_hits", 0) == 0
    if frame == "rebuilt":
        df = module.build(session, mine)
    assert df.collect().equals(first)
    warm = df.metrics()
    assert warm["mesh.reshard_bytes"] == 0
    assert "overhead.shard_ms" not in warm
    assert warm.get("plan.reused", 0) == (frame == "same")
    assert warm.get("whole_plan_structure_hits", 0) == (frame == "rebuilt")
    assert warm.get("compile_cache_misses", 0) == 0
    assert warm["mesh.replicated_lanes"] == 0 and warm["mesh.devices"] == 4
    assert warm["exec_dispatches"] == 1 and warm["host_syncs"] == 1
    # the spans' own times still add up to the collect's
    for m in (cold, warm):
        parts = sum(v for k, v in m.items()
                    if k.startswith("overhead.") and k.endswith("_ms")
                    and k not in ("overhead.collect_ms",
                                  "overhead.seam_wait_ms",
                                  "overhead.fetch_wait_ms"))
        assert parts == pytest.approx(m["overhead.collect_ms"], rel=1e-6)
    # without spans the wall's categories still add up: a placement
    # counts as an upload
    for collected in (cold, warm):
        bd = QueryProfile([], [], {}, collected, {}).wall_breakdown()
        named = sum(v for k, v in bd.items() if k.endswith("_ms")
                    and k not in ("wall_ms", "pad_waste_ms",
                                  "semaphore_wait_ms"))
        assert named == pytest.approx(bd["wall_ms"], abs=0.02), bd
    # the bytes placed are the sharded copy's, counted once
    placed = _uploads_since(before)
    assert [len(k) for k in placed] == [5]
    assert C._SCAN_UPLOAD_CACHE[placed[0]][2] == cold["mesh.reshard_bytes"]


def _wide_money(tables):
    """A filter that hands on a decimal(30,2): two int64 lanes a value."""
    n = 20000
    cents = pa.array([decimal.Decimal(i * 10 ** 20 + i) / 100
                      for i in range(n)], pa.decimal128(30, 2))
    table = pa.table({"k": np.arange(n, dtype=np.int64), "w": cents})
    return lambda session: session.from_arrow(table) \
        .filter(col("k") >= n - 3).select(col("w")), 3


@pytest.mark.parametrize("case", ["q1", "wide_decimal"])
def test_every_lane_is_sharded_four_ways_and_none_is_kept_whole(
        bench, tables, mesh_conf, case, eight_devices):
    if case == "q1":
        mine = {"lineitem": tables["lineitem"].slice(0)}
        build, rows = (lambda s: bench.q1.build(s, mine)), 4
    else:
        build, rows = _wide_money(tables)
    before = set(C._SCAN_UPLOAD_CACHE)
    df = build(TpuSession(mesh_conf))
    assert df.collect().num_rows == rows
    assert df.metrics()["mesh.replicated_lanes"] == 0
    keys = _uploads_since(before)
    assert [len(k) for k in keys] == [5]             # no unsharded copy
    _ref, batches, nbytes = C._SCAN_UPLOAD_CACHE[keys[0]]
    assert len(batches) > 2
    if case == "wide_decimal":                       # the hi lane is there
        assert all(db.columns[-1].data_hi is not None for db in batches)
    lanes = [a for db in batches for a in C._flatten_batch(db)[0]]
    assert sum(a.nbytes for a in lanes) == nbytes
    for lane in lanes:
        assert len(lane.sharding.device_set) == 4
        assert {s.data.shape[0] for s in lane.addressable_shards} \
            == {lane.shape[0] // 4}


@pytest.mark.parametrize("frame", ["same", "rebuilt"])
def test_a_one_chip_and_a_mesh_session_over_one_table_get_two_programs(
        bench, tables, mesh_conf, frame, eight_devices):
    """`rebuilt`: a new DataFrame every round, which plans anew and asks
    the process-wide cache; `same`: from the second round on each
    DataFrame runs the program its kept plan holds."""
    mine = {"lineitem": tables["lineitem"].slice(0)}
    want = bench.q6.reference(mine)
    sessions = ((TpuSession(mesh_conf), 4), (TpuSession(WHOLE), None))
    frames = [bench.q6.build(s, mine) for s, _devices in sessions]
    # a key names no table (the anchors do), so an earlier test's q6
    # programs would sit under the very keys this one files
    C._PLAN_EXEC_CACHE.clear()
    uploads = set(C._SCAN_UPLOAD_CACHE)
    for round_ in range(3):                  # interleaved: both stay warm
        for i, (session, devices) in enumerate(sessions):
            df = frames[i] if frame == "same" \
                else bench.q6.build(session, mine)
            answer = df.collect()
            assert bench.compare.table_gaps(answer, want) == (0, 0.0)
            m = df.metrics()
            assert m.get("mesh.devices") == devices
            warm = round_ > 0
            assert m.get("plan.reused", 0) == (warm and frame == "same")
            assert m.get("whole_plan_structure_hits", 0) == \
                (warm and frame == "rebuilt")
            assert m.get("compile_cache_misses", 0) == (round_ == 0)
    new = list(C._PLAN_EXEC_CACHE)
    # a one-chip key is the four parts it has always been; the mesh's
    # ends in the mesh and one partition spec an input
    assert sorted(len(k) for k in new) == [4, 5]
    mesh_key = max(new, key=len)
    (axes, devices), specs = mesh_key[4]
    assert axes == ("shards",) and len(devices) == 4
    assert len(specs) == len(mesh_key[2]) and ("shards",) in specs
    assert sorted(len(k) for k in _uploads_since(uploads)) == [4, 5]


def test_dropping_the_table_releases_its_sharded_copies(mesh_conf,
                                                        eight_devices):
    table = pa.table({"v": np.arange(4096, dtype=np.int64)})
    df = TpuSession(mesh_conf).from_arrow(table).agg((Sum(col("v")), "s"))
    before = set(C._SCAN_UPLOAD_CACHE)
    assert df.collect()["s"].to_pylist() == [4096 * 4095 // 2]
    placed = _uploads_since(before)
    assert [(k[0], len(k)) for k in placed] == [(id(table), 5)]
    del df, table
    gc.collect()
    assert placed[0] not in C._SCAN_UPLOAD_CACHE


def test_a_lane_the_mesh_cannot_divide_is_replicated_and_counted(
        eight_devices):
    """Capacities are powers of two: three devices divide none of them."""
    table = pa.table({"v": np.arange(3000, dtype=np.int64)})
    conf = dict(WHOLE, **{"spark.rapids.tpu.sql.mesh.enabled": "true",
                          "spark.rapids.tpu.sql.mesh.devices": "3"})
    df = TpuSession(conf).from_arrow(table).agg((Sum(col("v")), "s"))
    for _ in range(2):                       # placed anew, then cached
        assert df.collect()["s"].to_pylist() == [3000 * 2999 // 2]
        m = df.metrics()
        assert m["mesh.devices"] == 3 and m["mesh.replicated_lanes"] == 2


def test_a_ragged_column_stays_whole_beside_sharded_flat_lanes(
        eight_devices):
    """Offsets (rows + 1) and value lanes do not fit a split of the rows:
    one placement decides it, lane by lane, and the launch counts them."""
    from spark_rapids_tpu.columnar.host import HostBatch
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.parallel.mesh import make_mesh
    n = 1000
    rb = pa.record_batch({
        "k": pa.array(np.arange(n, dtype=np.int64)),
        "l": pa.array([[i] * (i % 3) for i in range(n)],
                      pa.list_(pa.int64()))})
    mesh = make_mesh(4)
    db = C._upload_sharded(HostBatch(rb), TpuConf(WHOLE), None, mesh)
    flat, ragged = db.columns
    for lane in (flat.data, flat.validity):
        assert tuple(lane.sharding.spec) == ("shards",)
    whole = [ragged.data, ragged.validity, ragged.offsets, ragged.elem_valid]
    for lane in whole:
        assert lane.sharding.is_fully_replicated
        assert len(lane.sharding.device_set) == 4
    lanes = C._flatten_batch(db)[0]
    assert C._unsplit_lanes(lanes) == len(whole)
    assert C._mesh_sig(mesh, lanes)[1].count(("shards",)) == 2


def test_the_program_hash_script_prints_the_same_lines_in_any_process():
    """`scripts/program_hashes.py` is how the one-chip cells' programs and
    keys are compared with the parent's: its lines may not depend on the
    process (a key holds sets of strings, printed in hash-seed order)."""
    import subprocess
    runs = []
    for hash_seed in ("1", "2"):
        out = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "scripts",
                                          "program_hashes.py"),
             "--workload", "tpch-sf1.joins", "--seed", "7", "--seconds",
             "0.2", "--trace", "0", "--rehearse-cpu", "--scale", "0.002"],
            cwd=_ROOT, capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONHASHSEED=hash_seed))
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append([l for l in out.stdout.splitlines()
                     if l.startswith("PROGRAM ")])
    assert len(runs[0]) == 2 and runs[0] == runs[1]       # q3's and q5's


# -- the benchmark's side ---------------------------------------------------

def test_manifest_of_the_mesh_cell(bench):
    entry = [w for w in bench.manifest.benchmark()["workloads"]
             if w["name"] == CELL]
    assert [(w["config"], w["traffic"], w["chips"]) for w in entry] \
        == [("tpch-sf10-mesh4", "mesh4", 4)]
    found = bench.manifest.Cell(CELL)
    assert (found.config_name, found.chips, found.config["chips"]) \
        == ("tpch-sf10-mesh4", 4, 4)
    assert found.config["session_conf"] == {
        "spark.rapids.tpu.sql.mesh.enabled": "true",
        "spark.rapids.tpu.sql.mesh.devices": "4",
        # ISSUE 32's first lever for the run's time limit, in `assumed`
        "spark.rapids.tpu.sql.batchSizeRows": "16777216"}
    assert any("batchSizeRows 16777216" in a
               for a in found.config["assumed"])
    assert found.query_names == ["q1", "q6"]
    assert found.spec["loop"] == {"kind": "closed_round_robin",
                                  "trace_queries": 8}
    assert found.end_to_end == ["query_ms", "setup_s"]   # no query_p95_ms
    q1_config = bench.manifest.Cell("tpch-sf10.q1").config
    for key in ("scale_factor", "generator", "rows", "tables", "reduced",
                "types"):
        assert found.config[key] == q1_config[key], key
    assert set(found.config["guarantees"]) == set(q1_config["guarantees"])
    # one of the cells asks for four chips
    assert [w["chips"] for w in bench.manifest.benchmark()["workloads"]
            if w["chips"] != 1] == [4]


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_file_loads_and_applies_to_the_mesh_cell_alone(
        bench, name):
    entry = [m for m in bench.manifest.benchmark()["per_layer"]
             if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == [CELL]
    assert entry[0]["moves"] == "query_ms"
    with open(os.path.join(_BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert (spec["reader"], spec["key"]) == NEW_METRICS[name]
    assert (spec["unit"], spec["layer"]) == (entry[0]["unit"],
                                             entry[0]["layer"])
    applied = {n: reader for n, _spec, reader in
               bench.manifest.Cell(CELL).per_layer}
    assert applied[name].__name__ == "harness.readers." + spec["reader"]
    for cell in ("tpch-sf1.joins", "tpch-sf10.q6", "tpch-sf1.groupby",
                 "tpch-sf10.q1"):
        assert name not in [n for n, _s, _r in
                            bench.manifest.Cell(cell).per_layer]


def test_the_accepted_metrics_without_a_list_apply_to_the_mesh_cell(bench):
    applied = [n for n, _s, _r in bench.manifest.Cell(CELL).per_layer]
    for name in ("device_idle_pct", "xla_programs_roofline", "peak_hbm_GB",
                 "dispatches_per_query", "host_syncs_per_query",
                 "compiles_in_window", "launch_ms_per_query",
                 "agg_masked_dense_per_query"):
        assert name in applied, name
    assert not {"wall_ms.q3", "wall_ms.q5"} & set(applied)


def _run(records=(), trace=None):
    window = types.SimpleNamespace(records=[
        types.SimpleNamespace(error=None, metrics=m) for m in records])
    return {"window": window, "trace": trace}


def test_the_collectives_reader_sums_the_collectives_over_busy(bench):
    with open(os.path.join(_BENCH, "layer_metrics",
                           "collectives_pct_of_busy.json")) as f:
        spec = json.load(f)
    read = bench.trace_ops.read
    trace = {"busy_s": 0.5, "device_ops": [
        ["select_reduce_fusion", 0.30], ["all-reduce", 0.02],
        ["all-reduce-start", 0.01], ["all-gather.kLoop", 0.01],
        ["reduce", 0.05], ["reduce-scatter", 0.005],
        ["collective-permute-done", 0.005]]}
    assert read(spec, _run(trace=trace)) == pytest.approx(10.0)
    # no collective among the ten longest ops: 0, not nothing
    assert read(spec, _run(trace={"busy_s": 0.5, "device_ops": [
        ["select_reduce_fusion", 0.5]]})) == 0.0
    # an untraced run, and a trace without a device plane (the rehearsal)
    assert read(spec, _run()) is None
    assert read(spec, {"window": None}) is None


@pytest.mark.parametrize("name", ["shard_ms_per_query",
                                  "mesh_reshard_bytes_per_query",
                                  "mesh_replicated_lanes_per_query"])
def test_the_mesh_counters_read_per_query_and_zero_where_absent(bench, name):
    """A program without the span or the counter (the parent's) reads 0
    and does not raise; bytes are per query, not scaled."""
    with open(os.path.join(_BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["per"] == "query" and "scale" not in spec
    read = bench.ctx_metric.read
    assert read(spec, _run([{}, {"exec_dispatches": 1}])) == 0.0
    assert read(spec, _run([{spec["key"]: 6}, {}, {spec["key"]: 3}])) == 3.0
    assert read(spec, _run()) is None
