"""Always-on metrics plane tests (obs/registry + recorder + export,
ISSUE 5): log2-bucket histogram math, bounded label cardinality,
flight-recorder ring semantics, the crash-dump black box, subsystem
telemetry (HBM gauges, spill timings, semaphore-wait and shuffle-skew
histograms, per-device ICI bytes), the tracer thread-safety satellite,
truncated-event-log tolerance, export surfaces (heartbeat JSONL,
Prometheus endpoint), the overhead bound, the docs lint and the bench
regression gate."""
import importlib.util
import json
import os
import threading
import time
import urllib.request
from collections import Counter

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.exec.plan import ExecContext
from spark_rapids_tpu.obs.recorder import FLIGHT_RECORDER, FlightRecorder
from spark_rapids_tpu.obs.registry import (MetricsRegistry, OVERFLOW,
                                           REGISTRY, bucket_index,
                                           bucket_le)
from spark_rapids_tpu.obs.tracer import QueryTracer, read_event_log
from spark_rapids_tpu.session import TpuSession, col, lit

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _plane_on():
    """The plane is process-global; tests that flip the enabled flag or
    start exporters must not leak that state into their neighbors."""
    yield
    from spark_rapids_tpu.obs.export import shutdown_exporters
    shutdown_exporters()
    REGISTRY.enabled = True
    FLIGHT_RECORDER.enabled = True


def _hist(metric, **labels):
    """Histogram state (count/sum/buckets) or a zero state."""
    return metric.value(**labels) or {"count": 0, "sum": 0.0,
                                      "buckets": {}}


# ---------------------------------------------------------------------------
# registry: bucket math, kinds, cardinality bound, export formats
# ---------------------------------------------------------------------------

def test_bucket_index_log2_edges():
    # bucket 0 is (-inf, 1]; bucket i is (2^(i-1), 2^i]
    assert bucket_index(0) == 0 and bucket_index(1) == 0
    assert bucket_index(-5) == 0
    assert bucket_index(2) == 1
    assert bucket_index(3) == 2 and bucket_index(4) == 2
    assert bucket_index(5) == 3 and bucket_index(8) == 3
    assert bucket_index(1024) == 10 and bucket_index(1025) == 11
    assert bucket_index(1.5) == 1          # non-integers round up
    for v in (1, 2, 3, 7, 8, 9, 100, 4096, 1 << 40):
        i = bucket_index(v)
        lo = 0 if i == 0 else bucket_le(i - 1)
        assert lo < v <= bucket_le(i) or (i == 0 and v <= 1)
    # petabyte-scale values clamp into the last bucket, never KeyError
    assert bucket_index(1 << 60) == 50


def test_counter_gauge_histogram_kinds():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter", ("site",))
    c.inc(site="a")
    c.inc(2, site="a")
    c.inc(site="b")
    assert c.value(site="a") == 3 and c.value(site="b") == 1
    assert c.value(site="never") == 0      # counters default to 0

    g = reg.gauge("g_bytes", "a gauge")
    g.set(10)
    g.max(7)                               # high-water keeps the larger
    assert g.value() == 10
    g.max(25)
    assert g.value() == 25
    g.add(-5)
    assert g.value() == 20

    h = reg.histogram("h_ms", "a histogram")
    for v in (1, 2, 3, 1000):
        h.observe(v)
    st = h.value()
    assert st["count"] == 4 and st["sum"] == 1006.0
    assert st["buckets"] == {0: 1, 1: 1, 2: 1, 10: 1}

    # same-shape re-registration returns the SAME family object
    assert reg.counter("c_total", "a counter", ("site",)) is c
    with pytest.raises(ValueError):
        reg.counter("c_total", "different labels", ("other",))
    with pytest.raises(ValueError):
        reg.gauge("c_total", "different kind")


def test_label_cardinality_is_bounded():
    reg = MetricsRegistry(max_series=4)
    c = reg.counter("many_total", "cardinality bomb", ("q",))
    for i in range(100):
        c.inc(q=f"query-{i}")
    series = c.series()
    assert len(series) == 5                # 4 real + 1 overflow
    overflow = [s for s in series if s["labels"]["q"] == OVERFLOW]
    assert overflow and overflow[0]["value"] == 96
    assert sum(s["value"] for s in series) == 100   # nothing lost


def test_snapshot_flat_and_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", ("code",)).inc(3, code=200)
    reg.gauge("live_bytes", "live").set(42)
    h = reg.histogram("wait_ms", "wait")
    h.observe(1)
    h.observe(3)
    h.observe(3)

    snap = reg.snapshot()
    assert {f["name"] for f in snap["families"]} == \
        {"req_total", "live_bytes", "wait_ms"}

    flat = reg.flat()
    assert flat["req_total{code=200}"] == 3
    assert flat["live_bytes"] == 42
    assert flat["wait_ms.count"] == 3 and flat["wait_ms.sum"] == 7.0

    text = reg.prometheus_text()
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert 'req_total{code="200"} 3' in text
    assert "live_bytes 42" in text
    # histogram: CUMULATIVE buckets + +Inf + sum/count
    assert 'wait_ms_bucket{le="1"} 1' in text
    assert 'wait_ms_bucket{le="4"} 3' in text
    assert 'wait_ms_bucket{le="+Inf"} 3' in text
    assert "wait_ms_sum 7.0" in text
    assert "wait_ms_count 3" in text


def test_disabled_registry_publishes_nothing():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "x")
    h = reg.histogram("y_ms", "y")
    reg.enabled = False
    c.inc(5)
    h.observe(10)
    assert c.value() == 0 and h.value() is None
    reg.enabled = True
    c.inc(5)
    assert c.value() == 5


def test_registry_reset_keeps_families():
    reg = MetricsRegistry()
    c = reg.counter("z_total", "z")
    c.inc(9)
    reg.reset()
    assert reg.family_names() == ["z_total"]
    assert c.value() == 0


# ---------------------------------------------------------------------------
# flight recorder: bounded ring, newest-kept semantics
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_is_bounded():
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("instant", f"e{i}", "test", {"i": i})
    assert len(fr) == 8
    tail = fr.tail()
    assert [r["name"] for r in tail] == [f"e{i}" for i in range(12, 20)]
    assert [r["name"] for r in fr.tail(3)] == ["e17", "e18", "e19"]
    # attrs stay JSON-serializable (numpy scalars coerce)
    fr.record("instant", "np", "test", {"n": np.int64(7), "o": object()})
    rec = fr.tail(1)[0]
    json.dumps(rec)
    assert rec["attrs"]["n"] == 7


def test_flight_recorder_resize_keeps_newest():
    fr = FlightRecorder(capacity=16)
    for i in range(10):
        fr.record("instant", f"e{i}", "test")
    fr.resize(4)
    assert [r["name"] for r in fr.tail()] == ["e6", "e7", "e8", "e9"]
    fr.enabled = False
    fr.record("instant", "dropped", "test")
    assert len(fr) == 4


# ---------------------------------------------------------------------------
# tracer satellites: thread-safety hammer + truncated event logs
# ---------------------------------------------------------------------------

def test_tracer_byte_and_instant_thread_safety_hammer():
    """add_bytes/instant are hit from operator-stream, spill and shuffle
    threads concurrently; totals must be exact (the satellite fix takes
    the tracer lock) — and so must the always-on registry counters the
    same calls feed."""
    from spark_rapids_tpu.obs.registry import DATA_BYTES, RUNTIME_EVENTS
    tr = QueryTracer(query_id=99)
    nthreads, iters = 8, 400
    before_bytes = DATA_BYTES.value(channel="h2d")
    before_ev = RUNTIME_EVENTS.value(event="hammer", cat="test")

    def pound():
        for _ in range(iters):
            tr.add_bytes("h2d_bytes", 3)
            tr.instant("hammer", "test", who=threading.get_ident())

    threads = [threading.Thread(target=pound) for _ in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert tr.counters["h2d_bytes"] == 3 * nthreads * iters
    assert len(tr.events) == nthreads * iters
    assert DATA_BYTES.value(channel="h2d") - before_bytes == \
        3 * nthreads * iters
    assert RUNTIME_EVENTS.value(event="hammer", cat="test") - before_ev \
        == nthreads * iters


def test_read_event_log_tolerates_truncated_tail(tmp_path):
    """Crash-time logs end mid-write: the parsed prefix comes back with
    truncated=True instead of a raw JSONDecodeError (satellite)."""
    p = tmp_path / "query_7.jsonl"
    p.write_text("\n".join([
        json.dumps({"type": "query_start", "query_id": 7,
                    "wall_start_unix": 100.0}),
        json.dumps({"type": "span", "id": 1, "parent": None,
                    "name": "root", "cat": "query", "t0_ms": 0.0,
                    "dur_ms": 5.0}),
        json.dumps({"type": "instant", "name": "spill",
                    "cat": "runtime", "t_ms": 1.0}),
        '{"type": "query_end", "metrics": {"scanned_ro',   # mid-write
    ]))
    log = read_event_log(str(p))
    assert log.truncated
    assert log.query_id == 7
    assert [sp.name for sp in log.spans] == ["root"]
    assert [e.name for e in log.events] == ["spill"]
    assert log.metrics == {}               # the torn record contributes nothing

    from spark_rapids_tpu.obs.profile import QueryProfile
    prof = QueryProfile.from_event_log(str(p))
    assert prof.truncated
    assert "TRUNCATED" in prof.render().splitlines()[0]


def test_read_event_log_midfile_corruption_still_raises(tmp_path):
    p = tmp_path / "query_8.jsonl"
    p.write_text("\n".join([
        json.dumps({"type": "query_start", "query_id": 8}),
        "{this is not json",
        json.dumps({"type": "query_end"}),
    ]))
    with pytest.raises(json.JSONDecodeError):
        read_event_log(str(p))


# ---------------------------------------------------------------------------
# subsystem telemetry through real machinery
# ---------------------------------------------------------------------------

def _tbl(n=4000, seed=7):
    rng = np.random.default_rng(seed)
    return pa.table({"k": pa.array(rng.integers(0, 8, n), pa.int64()),
                     "v": pa.array(rng.standard_normal(n))})


def test_query_lifecycle_publishes_always_on(tmp_path):
    """Default conf (tracing OFF): one collect still lands in the
    registry and the flight recorder — the between-queries visibility
    the plane exists for."""
    from spark_rapids_tpu.obs.registry import (DATA_BYTES, QUERIES_TOTAL,
                                               QUERY_WALL_MS)
    before_q = QUERIES_TOTAL.value(status="ok", kind="device")
    before_wall = _hist(QUERY_WALL_MS)["count"]
    before_h2d = DATA_BYTES.value(channel="h2d")

    s = TpuSession()
    df = s.from_arrow(_tbl()).filter(col("v") > lit(0.0)).select(col("k"))
    df.collect()

    assert QUERIES_TOTAL.value(status="ok", kind="device") == before_q + 1
    assert _hist(QUERY_WALL_MS)["count"] == before_wall + 1
    assert DATA_BYTES.value(channel="h2d") - before_h2d > 0
    # lifecycle markers ride the flight recorder with a shared query seq
    names = [(r["name"], r.get("query")) for r in s.flight_record(10)]
    starts = [q for n, q in names if n == "query_start"]
    ends = [q for n, q in names if n == "query_end"]
    assert starts and ends and starts[-1] == ends[-1]
    # session surfaces
    snap = s.metrics_snapshot()
    assert {"tpu_queries_total", "tpu_query_wall_ms"} <= \
        {f["name"] for f in snap["families"]}
    flat = s.metrics_snapshot(compact=True)
    assert flat["tpu_queries_total{status=ok,kind=device}"] >= 1


def test_metrics_disabled_is_a_noop_plane():
    from spark_rapids_tpu.obs.registry import QUERIES_TOTAL
    before = QUERIES_TOTAL.value(status="ok", kind="device")
    before_flight = list(FLIGHT_RECORDER.tail())
    s = TpuSession({"spark.rapids.tpu.metrics.enabled": "false"})
    s.from_arrow(_tbl(500)).select(col("k")).collect()
    assert QUERIES_TOTAL.value(status="ok", kind="device") == before
    assert s.flight_record() == before_flight   # recorder off too


def test_hbm_gauges_follow_budget():
    """The HBM gauges report the process CENSUS — the SUM across all
    live budgets (obs/memattr.py), so serving tenants' budgets no
    longer stomp each other's gauge writes — and the high-water
    sticks."""
    from spark_rapids_tpu.obs.memattr import CENSUS
    from spark_rapids_tpu.obs.registry import (HBM_LIVE_BYTES,
                                               HBM_PEAK_BYTES)
    from spark_rapids_tpu.runtime.memory import MemoryBudget, _device_label
    conf = TpuConf({"spark.rapids.tpu.memory.tpu.budgetBytes": 1 << 20})
    budget = MemoryBudget(conf)
    dev = _device_label()
    live0 = CENSUS.totals()["live_bytes"]
    budget.reserve(1000)
    assert HBM_LIVE_BYTES.value(device=dev) == live0 + 1000
    assert HBM_PEAK_BYTES.value(device=dev) >= live0 + 1000
    peak = HBM_PEAK_BYTES.value(device=dev)
    budget.release(1000)
    assert HBM_LIVE_BYTES.value(device=dev) == live0
    assert HBM_PEAK_BYTES.value(device=dev) == peak   # high-water sticks


def test_spill_tiers_publish_counters_and_timings():
    from spark_rapids_tpu.obs.registry import (SPILL_BATCHES, SPILL_BYTES,
                                               SPILL_MS)
    from spark_rapids_tpu.columnar.device import to_device
    from spark_rapids_tpu.columnar.host import HostBatch
    from spark_rapids_tpu.runtime.memory import MemoryBudget, Spillable
    conf = TpuConf({"spark.rapids.tpu.memory.tpu.budgetBytes": 1 << 22,
                    "spark.rapids.tpu.memory.host.spillStorageSize":
                        1 << 22})
    budget = MemoryBudget(conf)
    before = {t: SPILL_BATCHES.value(tier=t) for t in ("host", "disk")}
    before_ms = {op: _hist(SPILL_MS, op=op)["count"]
                 for op in ("spill", "to_disk", "read")}

    rng = np.random.default_rng(3)
    hb = HostBatch(pa.record_batch(
        {"v": pa.array(rng.standard_normal(4000))}))
    sp = Spillable(to_device(hb, conf), budget)
    sp.spill()                             # device -> host
    sp.to_disk()                           # host -> disk
    assert int(sp.get().num_rows) == 4000  # disk -> device (read)
    sp.close()

    assert SPILL_BATCHES.value(tier="host") == before["host"] + 1
    assert SPILL_BATCHES.value(tier="disk") == before["disk"] + 1
    assert SPILL_BYTES.value(tier="host") > 0
    for op in ("spill", "to_disk", "read"):
        assert _hist(SPILL_MS, op=op)["count"] == before_ms[op] + 1


def test_semaphore_wait_histogram_under_contention():
    """Chaos-harness style thread hammer (tests/test_memory.py pattern):
    with ONE permit and N contenders holding it, every acquisition logs
    one observation and the blocked ones land in non-zero buckets."""
    from spark_rapids_tpu.obs.registry import SEMAPHORE_WAIT_MS
    from spark_rapids_tpu.runtime.semaphore import device_permit
    conf = TpuConf({"spark.rapids.tpu.sql.concurrentTpuTasks": 1})
    before = _hist(SEMAPHORE_WAIT_MS)["count"]
    nthreads, hold_s = 4, 0.02
    errors = []

    def contend():
        try:
            with device_permit(conf, metrics={}):
                time.sleep(hold_s)
        except Exception as e:             # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=contend) for _ in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    st = _hist(SEMAPHORE_WAIT_MS)
    assert st["count"] == before + nthreads   # one observation per acquire
    # serialized holders: the last waiter blocked >= (n-1) * hold time,
    # so the tail of the distribution must reach past hold_s in ms
    assert max(bucket_le(i) for i in st["buckets"]) >= hold_s * 1e3


def test_shuffle_partition_skew_histogram_matches_independent():
    """The byte-skew satellite: write a skewed TPC-H q4-shaped shuffle
    (lineitem hash-partitioned on l_orderkey, most keys collapsed into
    one hot partition) and check the registry histogram against a
    distribution computed independently by re-serializing each slice."""
    from spark_rapids_tpu import tpch
    from spark_rapids_tpu.columnar.host import HostBatch
    from spark_rapids_tpu.obs.registry import (SHUFFLE_BYTES,
                                               SHUFFLE_PARTITION_BYTES)
    from spark_rapids_tpu.shuffle.manager import (ShuffleManager,
                                                  serialize_batch)
    tables = tpch.gen_tables(scale=0.001)
    rb = tables["lineitem"].combine_chunks().to_batches()[0]
    okey = np.asarray(rb.column(rb.schema.get_field_index("l_orderkey")))
    nparts = 8
    # q4's join shuffle keys on orderkey; skew it: ~2/3 of rows hash to
    # partition 0, the rest spread — a hot partition plus a light tail
    ids = np.where(okey % 3 == 0, okey % nparts, 0).astype(np.int64)
    assert (ids == 0).mean() > 0.5

    # the independent distribution: slice exactly as the writer does
    # (stable sort by partition id keeps original row order per slice)
    expected = Counter()
    expected_total = 0
    for p in range(nparts):
        mask = ids == p
        if not mask.any():
            continue
        size = len(serialize_batch(rb.filter(pa.array(mask))))
        expected[bucket_index(size)] += 1
        expected_total += size

    before = _hist(SHUFFLE_PARTITION_BYTES)
    before_w = SHUFFLE_BYTES.value(direction="written")
    mgr = ShuffleManager(num_threads=4)
    total = mgr.write_batch(mgr.new_shuffle(), HostBatch(rb), ids, nparts)
    assert total == expected_total
    assert SHUFFLE_BYTES.value(direction="written") - before_w == total

    after = _hist(SHUFFLE_PARTITION_BYTES)
    delta = Counter(after["buckets"])
    delta.subtract(before["buckets"])
    assert +delta == expected
    assert after["count"] - before["count"] == sum(expected.values())
    assert after["sum"] - before["sum"] == expected_total
    # the skew is visible: the hot partition sits in a strictly higher
    # bucket than every tail partition
    assert len(expected) > 1


def test_ici_exchange_publishes_wire_bytes_once(eight_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from spark_rapids_tpu.obs.registry import (EXCHANGE_WIRE_POST,
                                               EXCHANGE_WIRE_PRE,
                                               ICI_EXCHANGE_BYTES)
    from spark_rapids_tpu.parallel.exchange import RaggedExchange
    from spark_rapids_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(8)
    before = ICI_EXCHANGE_BYTES.value()
    pre0, post0 = EXCHANGE_WIRE_PRE.value(), EXCHANGE_WIRE_POST.value()

    cap, n = 64, 8 * 64
    shard = NamedSharding(mesh, P(mesh.axis_names[0]))
    ex = RaggedExchange(mesh, nlanes=1, cap=cap)
    dk = jax.device_put(jnp.zeros(n, jnp.int64), shard)
    dl = jax.device_put(jnp.ones(n, bool), shard)
    dest = jax.device_put(jnp.zeros(n, jnp.int32), shard)
    ex([dk], dl, dest)

    # ONE emit per exchange, totalled across the mesh (no per-device
    # python loop on the hot path): the counter advances by exactly the
    # post-compress wire volume the exchange reports
    delta = ICI_EXCHANGE_BYTES.value() - before
    assert delta == ex.last_stats["wire_post"] > 0
    assert EXCHANGE_WIRE_POST.value() - post0 == delta
    pre_delta = EXCHANGE_WIRE_PRE.value() - pre0
    assert pre_delta == ex.last_stats["wire_pre"] >= delta


# ---------------------------------------------------------------------------
# crash dumps: the flight recorder is the black box (acceptance)
# ---------------------------------------------------------------------------

def test_fatal_fault_dump_embeds_flight_tail_ending_on_the_fault(tmp_path):
    from spark_rapids_tpu.runtime.failure import FatalDeviceError
    s = TpuSession({"spark.rapids.tpu.test.faults": "execute:fatal:nth=1",
                    "spark.rapids.tpu.coredump.path": str(tmp_path)})
    df = s.from_arrow(_tbl(2000)).sort(("v", True, True))
    with pytest.raises(FatalDeviceError) as ei:
        df.collect()
    dump = json.load(open(ei.value.dump_path))
    tail = dump["flight_recorder"]
    assert tail, "crash dump carries no flight-recorder events"
    last = tail[-1]
    # the LAST event is the injected fault itself: the dump shows what
    # the runtime was doing in the instants before death
    assert last["name"] == "fault_injected"
    assert last["attrs"]["site"] == "execute"
    assert last["attrs"]["kind"] == "fatal"
    assert any(r["name"] == "query_start" for r in tail)
    # the registry snapshot rides along, with the fault counted
    reg = dump["metrics_registry"]
    assert reg["tpu_faults_injected_total{site=execute,kind=fatal}"] >= 1
    json.dumps(dump)                       # the whole dump serializes


# ---------------------------------------------------------------------------
# export: heartbeat JSONL + Prometheus endpoint
# ---------------------------------------------------------------------------

def test_heartbeat_appends_parseable_snapshot_lines(tmp_path):
    from spark_rapids_tpu.obs.export import Heartbeat
    path = tmp_path / "hb.jsonl"
    hb = Heartbeat(str(path), interval_s=3600)
    hb.beat()
    hb.beat()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 2
    for rec in lines:
        assert rec["type"] == "heartbeat"
        assert isinstance(rec["registry"], dict)
        assert isinstance(rec["flight_len"], int)
    hb.stop()


def test_prometheus_endpoint_serves_registry(tmp_path):
    from spark_rapids_tpu.obs.export import MetricsHttpServer
    from spark_rapids_tpu.obs.registry import QUERIES_TOTAL
    QUERIES_TOTAL.inc(status="ok", kind="device")   # ensure a series
    srv = MetricsHttpServer(0)             # ephemeral port
    port = srv.start()
    try:
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "# TYPE tpu_queries_total counter" in text
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=5).read())
        assert any(f["name"] == "tpu_queries_total"
                   for f in snap["families"])
        flight = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/flight", timeout=5).read())
        assert isinstance(flight, list)
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5)
    finally:
        srv.stop()


def test_heartbeat_conf_starts_exporter(tmp_path):
    """The conf path end-to-end: a session with heartbeatPath writes
    lines on its own (short interval, then wait for one)."""
    path = tmp_path / "live.jsonl"
    TpuSession({"spark.rapids.tpu.metrics.heartbeatPath": str(path),
                "spark.rapids.tpu.metrics.reportIntervalS": "0.05"})
    deadline = time.time() + 10
    while time.time() < deadline:
        if path.exists() and path.read_text().strip():
            break
        time.sleep(0.02)
    lines = path.read_text().splitlines()
    assert lines, "heartbeat thread never wrote a snapshot"
    assert json.loads(lines[0])["type"] == "heartbeat"


# ---------------------------------------------------------------------------
# event-log + profile integration
# ---------------------------------------------------------------------------

def test_event_log_query_end_embeds_registry_snapshot(tmp_path):
    import glob as _glob
    s = TpuSession({"spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    s.from_arrow(_tbl()).filter(col("v") > lit(0.0)).select(col("k")) \
        .collect()
    log = read_event_log(_glob.glob(str(tmp_path / "*.jsonl"))[0])
    assert not log.truncated
    assert log.registry, "query_end record carries no registry snapshot"
    assert any(k.startswith("tpu_queries_total") for k in log.registry)
    from spark_rapids_tpu.obs.profile import QueryProfile
    prof = QueryProfile.from_event_log(log)
    assert prof.to_dict()["registry"] == log.registry
    assert "-- metrics registry" in prof.render()


def test_profile_report_tolerates_mixed_log_dirs(tmp_path, capsys):
    """scripts/profile_report.py over a dir holding a real event log, a
    heartbeat JSONL and a truncated crash-time log must render all three
    without a KeyError/JSONDecodeError (satellite)."""
    import glob as _glob
    s = TpuSession({"spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    s.from_arrow(_tbl()).select(col("k")).collect()
    real = _glob.glob(str(tmp_path / "*.jsonl"))[0]
    # a heartbeat file: valid JSONL, not a query event log
    (tmp_path / "metrics_hb.jsonl").write_text(
        json.dumps({"ts": 1.0, "type": "heartbeat", "registry": {}}) + "\n")
    # a crash-truncated copy of the real log
    torn = tmp_path / "query_torn.jsonl"
    torn.write_text(open(real).read()[:-40])
    mod = _load_script("profile_report")
    assert mod.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "== query profile ==" in out
    assert mod.main([str(tmp_path), "--json"]) == 0


# ---------------------------------------------------------------------------
# overhead bound (acceptance): always-on plane vs metrics.enabled=false
# ---------------------------------------------------------------------------

def test_always_on_overhead_within_bound():
    """bench.py proves the ~2% bound on real device_ms; here the same
    A/B on a warm TPC-H q6 with a GENEROUS margin (the plane's per-query
    cost is a fixed few hundred microseconds — it must never scale with
    the data, so 2x + 10ms headroom catches only real regressions)."""
    from spark_rapids_tpu import tpch
    tables = tpch.gen_tables(scale=0.001)

    def median_warm(conf):
        s = TpuSession(conf)
        q = tpch.QUERIES["q6"](s, tables).physical()
        q.collect(ExecContext(q.conf))     # warm (compile + uploads)
        times = []
        for _ in range(7):
            t0 = time.perf_counter()
            q.collect(ExecContext(q.conf))
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    on_s = median_warm({})
    off_s = median_warm({"spark.rapids.tpu.metrics.enabled": "false"})
    assert on_s <= off_s * 2.0 + 0.010, \
        f"always-on plane overhead too high: on={on_s*1e3:.2f}ms " \
        f"off={off_s*1e3:.2f}ms"


# ---------------------------------------------------------------------------
# CI: docs lint + bench regression gate
# ---------------------------------------------------------------------------

def test_metrics_docs_cover_every_registered_family():
    mod = _load_script("check_docs")
    assert mod.missing_metric_docs() == [], \
        "docs/METRICS.md stale — document every registry family"
    assert mod.missing_keys() == [], \
        "docs/configs.md stale — run `python -m spark_rapids_tpu.config`"


def test_check_regression_gate(tmp_path, capsys):
    """Exit 0 on the committed MULTICHIP_r*/ENCODINGS_r*/... trajectory; a
    synthetic 2x slowdown of a per-query round exits non-zero
    (acceptance)."""
    mod = _load_script("check_regression")
    assert mod.main([]) == 0
    capsys.readouterr()

    # a per-query round in bench.py's emitted shape (device_ms +
    # sync_rtt_ms: the gate compares floor-subtracted values), and the
    # same round 2x slower
    base = tmp_path / "base.json"
    base.write_text(json.dumps({
        "backend": "cpu", "sync_rtt_ms": 1.0,
        "tpch_suite_queries": {f"q{i}": {"device_ms": 100.0 * i + 1.0}
                               for i in range(1, 6)}}))
    qs, backend, _cms = mod.load_file(str(base))
    assert backend == "cpu" and qs["q2"] == pytest.approx(200.0)
    fixture = tmp_path / "slow.json"
    fixture.write_text(json.dumps({
        "tpch_suite_queries": {q: {"device_ms_net": ms * 2.0}
                               for q, ms in qs.items()},
        "backend": backend}))
    assert mod.main(["--current", str(base), str(base)]) == 0
    capsys.readouterr()
    rc = mod.main(["--current", str(fixture), str(base)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "REGRESSION" in out

    # an unreadable --current is usage error 2, not a crash
    missing = tmp_path / "nope.json"
    assert mod.main(["--current", str(missing)]) == 2


def test_check_regression_gates_multichip_timings(tmp_path, capsys):
    """MULTICHIP rounds gate like per-query device_ms: timings mine out
    of the legacy dryrun tail (a python-repr dict), land under the mc:
    prefix, and a slowed fused-groupby fails the gate — on the same
    backend only."""
    mod = _load_script("check_regression")
    base = tmp_path / "MULTICHIP_a.json"
    base.write_text(json.dumps({"n_devices": 8, "tail":
        "{'multichip_timings_s': {'groupby_8_rows_per_device': 10.0, "
        "'mesh_query_q1': 1.0}, 'peak_rss_mb': 1}\n"}))
    qs, backend, _ = mod.load_file(str(base))
    assert qs == {"mc:groupby_8_rows_per_device": 10000.0,
                  "mc:mesh_query_q1": 1000.0}
    assert backend == "cpu"              # dryrun rounds force cpu

    cur = tmp_path / "MULTICHIP_b.json"  # the suite runner's shape
    cur.write_text(json.dumps(
        {"multichip_timings_s": {"groupby_8_rows_per_device": 30.0,
                                 "mesh_query_q1": 0.9},
         "backend": "cpu"}))
    rc = mod.main(["--current", str(cur), str(base)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "REGRESSION mc:groupby_8_rows_per_device" in out
    assert "improved   mc:mesh_query_q1" in out

    # a different backend never gates against this baseline
    cur2 = tmp_path / "MULTICHIP_c.json"
    cur2.write_text(json.dumps(
        {"multichip_timings_s": {"groupby_8_rows_per_device": 30.0},
         "backend": "tpu"}))
    assert mod.main(["--current", str(cur2), str(base)]) == 0
    capsys.readouterr()


def test_metrics_port_zero_binds_ephemeral_and_reports(tmp_path):
    """metrics.port=0 binds an EPHEMERAL port (concurrent worker
    processes on one host never race a fixed port): the bound port is
    discoverable via bound_metrics_port(), scrapeable, and stamped
    into every heartbeat line; -1 (the default) starts no server."""
    from spark_rapids_tpu.obs.export import (Heartbeat,
                                             bound_metrics_port,
                                             configure_plane,
                                             shutdown_exporters)
    from spark_rapids_tpu.config import TpuConf
    assert bound_metrics_port() is None            # nothing running
    configure_plane(TpuConf({}))                   # default -1: still none
    assert bound_metrics_port() is None
    configure_plane(TpuConf({"spark.rapids.tpu.metrics.port": "0"}))
    port = bound_metrics_port()
    assert isinstance(port, int) and port > 0
    from spark_rapids_tpu.obs.registry import QUERIES_TOTAL
    QUERIES_TOTAL.inc(status="ok", kind="device")
    snap = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics.json", timeout=5).read())
    assert any(f["name"] == "tpu_queries_total" for f in snap["families"])
    # heartbeat lines carry the bound port + pid (the serving pool's
    # supervisor reads them off worker heartbeats the same way)
    path = tmp_path / "hb.jsonl"
    hb = Heartbeat(str(path), interval_s=3600)
    hb.beat()
    rec = json.loads(path.read_text().splitlines()[0])
    assert rec["metrics_port"] == port
    assert rec["pid"] == os.getpid()
    hb.stop()
    shutdown_exporters()
    assert bound_metrics_port() is None            # released cleanly


# ---------------------------------------------------------------------------
# fleet federation (PR 20): per-worker-labeled fold of registry snapshots
# ---------------------------------------------------------------------------

def _unitfed_source():
    """A worker-side registry with unique family names (FLEET is
    process-global across the pytest run)."""
    reg = MetricsRegistry()
    c = reg.counter("tpu_unitfed_queries_total", "h", ("status",))
    c.inc(3, status="ok")
    c.inc(1, status="error")
    g = reg.gauge("tpu_unitfed_live_bytes", "h")
    g.set(4096)
    h = reg.histogram("tpu_unitfed_wait_ms", "h", ("tenant",))
    for v in (0.5, 3.0, 900.0):
        h.observe(v, tenant="a")
    return reg


def test_fleet_fold_federates_counters_gauges_histograms():
    from spark_rapids_tpu.obs.registry import (FLEET, drop_fleet_worker,
                                               fold_fleet_snapshot)
    src = _unitfed_source()
    fold_fleet_snapshot("w1", src.snapshot())
    fold_fleet_snapshot("w2", src.snapshot())
    flat = FLEET.flat()
    # per-worker-labeled series, values EXACTLY the worker's own
    for w in ("w1", "w2"):
        assert flat[
            "tpu_fleet_unitfed_queries_total"
            f"{{worker={w},status=ok}}"] == 3
        assert flat[
            "tpu_fleet_unitfed_queries_total"
            f"{{worker={w},status=error}}"] == 1
        assert flat[f"tpu_fleet_unitfed_live_bytes{{worker={w}}}"] == 4096
        assert flat[
            f"tpu_fleet_unitfed_wait_ms{{worker={w},tenant=a}}"
            ".count"] == 3
    # histogram bucket state round-trips through the snapshot
    m = FLEET.get("tpu_fleet_unitfed_wait_ms")
    v = m.value(worker="w1", tenant="a")
    assert v["count"] == 3 and round(v["sum"], 1) == 903.5
    assert sum(v["buckets"].values()) == 3
    # folding the SAME cumulative snapshot again is idempotent (set,
    # not add — a dropped frame self-heals on the next beat)
    fold_fleet_snapshot("w1", src.snapshot())
    assert FLEET.flat() == flat
    # the fleet view renders as ordinary prometheus families
    text = FLEET.prometheus_text()
    assert "# TYPE tpu_fleet_unitfed_queries_total counter" in text
    assert 'worker="w1"' in text
    # a dead worker loses its GAUGES (point-in-time state), keeps its
    # counters/histograms (cumulative work the fleet really did)
    drop_fleet_worker("w1")
    flat2 = FLEET.flat()
    assert "tpu_fleet_unitfed_live_bytes{worker=w1}" not in flat2
    assert flat2["tpu_fleet_unitfed_live_bytes{worker=w2}"] == 4096
    assert flat2[
        "tpu_fleet_unitfed_queries_total{worker=w1,status=ok}"] == 3


def test_fleet_fold_shape_conflicts_are_skipped_not_raised():
    """A malformed or shape-conflicting family must never raise into
    the supervisor's reader loop (the worker would be falsely declared
    dead over telemetry)."""
    from spark_rapids_tpu.obs.registry import FLEET, fold_fleet_snapshot
    reg = MetricsRegistry()
    reg.counter("tpu_unitfed_conflict_total", "h", ("a",)).inc(1, a="x")
    fold_fleet_snapshot("w1", reg.snapshot())
    # same family name, different label shape: skipped silently
    reg2 = MetricsRegistry()
    reg2.counter("tpu_unitfed_conflict_total", "h", ("a", "b")) \
        .inc(1, a="x", b="y")
    fold_fleet_snapshot("w1", reg2.snapshot())
    # garbage frames: no raise
    fold_fleet_snapshot("w1", None)
    fold_fleet_snapshot("w1", {"families": [{"name": 7}]})
    fold_fleet_snapshot("w1", {"families": [
        {"name": "tpu_unitfed_conflict_total", "kind": "bogus"}]})
    assert FLEET.flat()[
        "tpu_fleet_unitfed_conflict_total{worker=w1,a=x}"] == 1


def test_worker_suffixed_path_keeps_pool_heartbeats_apart(monkeypatch):
    """Satellite: pool mode pointed every process at ONE heartbeatPath
    (interleaved, unparseable lines).  Each process now suffixes its
    worker id before the extension; the supervisor keeps the bare
    path."""
    from spark_rapids_tpu.obs.export import worker_suffixed_path
    monkeypatch.delenv("SPARK_RAPIDS_TPU_WORKER_ID", raising=False)
    assert worker_suffixed_path("/x/hb.jsonl") == "/x/hb.jsonl"
    assert worker_suffixed_path("") == ""
    monkeypatch.setenv("SPARK_RAPIDS_TPU_WORKER_ID", "w7")
    assert worker_suffixed_path("/x/hb.jsonl") == "/x/hb-w7.jsonl"
    assert worker_suffixed_path("/x/hb") == "/x/hb-w7.jsonl"


def test_heartbeat_lines_carry_role_worker_and_fleet(tmp_path,
                                                     monkeypatch):
    from spark_rapids_tpu.obs.export import Heartbeat
    from spark_rapids_tpu.obs.registry import fold_fleet_snapshot
    # a worker-role process stamps its id on every line
    monkeypatch.setenv("SPARK_RAPIDS_TPU_WORKER_ID", "w3")
    wpath = tmp_path / "hb-w.jsonl"
    hb = Heartbeat(str(wpath), interval_s=3600)
    hb.beat()
    hb.stop()
    rec = json.loads(wpath.read_text().splitlines()[0])
    assert rec["role"] == "worker" and rec["worker"] == "w3"
    # the supervisor's lines embed the non-empty FLEET view
    monkeypatch.delenv("SPARK_RAPIDS_TPU_WORKER_ID")
    fold_fleet_snapshot("w3", _unitfed_source().snapshot())
    spath = tmp_path / "hb-s.jsonl"
    hb = Heartbeat(str(spath), interval_s=3600)
    hb.beat()
    hb.stop()
    rec = json.loads(spath.read_text().splitlines()[0])
    assert rec["role"] == "supervisor" and rec["worker"] is None
    assert any(k.startswith("tpu_fleet_unitfed_")
               for k in rec["fleet"])


def test_fleet_view_served_on_metrics_endpoints():
    """ONE Prometheus endpoint serves the whole pool: the fleet
    families ride /metrics (exposition text) and /metrics.json."""
    from spark_rapids_tpu.obs.export import MetricsHttpServer
    from spark_rapids_tpu.obs.registry import (QUERIES_TOTAL,
                                               fold_fleet_snapshot)
    fold_fleet_snapshot("w9", _unitfed_source().snapshot())
    QUERIES_TOTAL.inc(status="ok", kind="device")   # ensure a series
    srv = MetricsHttpServer(0)
    port = srv.start()
    try:
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "# TYPE tpu_fleet_unitfed_queries_total counter" in text
        assert 'worker="w9"' in text
        # the single-process families still serve alongside
        assert "# TYPE tpu_queries_total counter" in text
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=5).read())
        assert any(f["name"] == "tpu_fleet_unitfed_queries_total"
                   for f in snap["fleet"]["families"])
    finally:
        srv.stop()


def test_flight_tail_bounded_trims_to_byte_budget():
    """Heartbeat telemetry is byte-bounded: the flight tail shrinks
    (newest-first survive) until it fits the frame budget."""
    from spark_rapids_tpu.obs.recorder import tail_bounded
    rec = FlightRecorder(capacity=256)
    for i in range(200):
        rec.record("instant", "e", "cat",
                   attrs={"payload": "x" * 50, "i": i})
    full = tail_bounded(rec, 64, 1 << 20)
    assert len(full) == 64
    small = tail_bounded(rec, 64, 2048)
    assert 0 < len(small) < 64
    # the NEWEST events survive the trim
    assert small[-1]["attrs"]["i"] == full[-1]["attrs"]["i"]
    assert len(json.dumps(small, default=str)) <= 2048
