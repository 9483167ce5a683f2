"""Chaos suite: site-addressable fault injection through real queries.

The recovery ladder is a *tested contract* (ISSUE 4): every registered
injection site (spark_rapids_tpu.runtime.faults.SITES) is exercised here
— scripts/check_fault_sites.py lints that this file covers all of them.
Recoverable fault classes must produce BIT-IDENTICAL results vs the
clean run; fatal classes must end in a classified FatalDeviceError whose
crash dump carries the injected-fault record.

Fast representative cases run in tier-1; the full query x fault sweep is
marked `slow`.
"""
import json
import os

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import tpcds, tpch
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.plan import expressions as E
from spark_rapids_tpu.runtime.failure import (CORRUPTION, FATAL_DEVICE, IO,
                                              FatalDeviceError, classify)
from spark_rapids_tpu.runtime.faults import (SITES, FaultInjector,
                                             InjectedIOError,
                                             InjectedQueryError,
                                             NULL_INJECTOR, get_injector,
                                             parse_spec, set_active)
from spark_rapids_tpu.runtime.memory import CorruptBlockError
from spark_rapids_tpu.session import DataFrame, TpuSession, col


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

#: knobs that force the spill/retry machinery through small inputs
TINY_MEMORY = {
    "spark.rapids.tpu.memory.tpu.budgetBytes": 1 << 16,
    "spark.rapids.tpu.memory.host.spillStorageSize": 1 << 14,
    "spark.rapids.tpu.sql.batchSizeRows": 1024,
    "spark.rapids.tpu.sql.shape.minBucketRows": 256,
    # keep chaos-run backoffs out of the tier-1 wall budget
    "spark.rapids.tpu.retry.io.backoffMs": 0,
}


@pytest.fixture(scope="module")
def tpch_tables():
    return tpch.gen_tables(scale=0.001)


@pytest.fixture(scope="module")
def tpcds_tables():
    return tpcds.gen_tables(scale=0.0005)


def run_query(build, conf=None, faults=None):
    """Build + collect a DataFrame query on a FRESH session (fresh
    injector hit counters) and return (table, session, DataFrame)."""
    settings = dict(conf or {})
    if faults:
        settings["spark.rapids.tpu.test.faults"] = faults
    s = TpuSession(settings)
    df = build(s)
    return df.collect(), s, df


def sort_tbl(n=40_000, seed=5):
    rng = np.random.default_rng(seed)
    return pa.table({"v": pa.array(rng.standard_normal(n))})


def sort_query(tbl):
    return lambda s: s.from_arrow(tbl).sort(("v", True, True))


def assert_identical(clean: pa.Table, chaos: pa.Table):
    assert clean.to_pydict() == chaos.to_pydict()


def fired_sites(session):
    return {rec["site"] for rec in get_injector(session.conf).log}


# ---------------------------------------------------------------------------
# per-site: recoverable classes are bit-identical to the clean run
# ---------------------------------------------------------------------------

def test_reserve_oom_recovers_spilling_sort():
    tbl = sort_tbl()
    clean, _, _ = run_query(sort_query(tbl), TINY_MEMORY)
    chaos, s, df = run_query(sort_query(tbl), TINY_MEMORY,
                             faults="reserve:oom:nth=20")
    assert_identical(clean, chaos)
    assert "reserve" in fired_sites(s)
    m = df.metrics()
    assert m.get("memory.oom_retries", 0) + \
        m.get("query_ooc_escalations", 0) + \
        m.get("query_oom_replays", 0) >= 1


def test_execute_oom_replays_query():
    """An OOM escaping every operator rung now escalates into the
    OUT-OF-CORE rung first (ISSUE 15 ladder): the replay runs with the
    OOC context forced, bit-identical, and the final whole-query replay
    rung stays in reserve."""
    tbl = sort_tbl(2_000, seed=9)
    build = lambda s: s.from_arrow(tbl).filter(
        E.GreaterThan(col("v"), E.Literal(0.0)))
    clean, _, _ = run_query(build)
    chaos, s, df = run_query(build, faults="execute:oom:nth=1")
    assert_identical(clean, chaos)
    assert "execute" in fired_sites(s)
    assert df.metrics().get("query_ooc_escalations") == 1
    assert df.metrics().get("query_oom_replays") is None

    # with the OOC tier disabled the legacy replay rung still owns it
    chaos2, s2, df2 = run_query(
        build, {"spark.rapids.tpu.sql.ooc.enabled": "false"},
        faults="execute:oom:nth=1")
    assert_identical(clean, chaos2)
    assert df2.metrics().get("query_oom_replays") == 1


def test_h2d_ioerror_recovers():
    tbl = sort_tbl(3_000, seed=11)
    build = sort_query(tbl)
    clean, _, _ = run_query(build, TINY_MEMORY)
    chaos, s, _ = run_query(build, TINY_MEMORY,
                            faults="h2d:ioerror:every=3")
    assert_identical(clean, chaos)
    assert "h2d" in fired_sites(s)


def test_d2h_ioerror_recovers():
    tbl = sort_tbl(2_000, seed=12)
    build = lambda s: s.from_arrow(tbl).filter(
        E.LessThan(col("v"), E.Literal(1.0)))
    clean, _, _ = run_query(build)
    chaos, s, _ = run_query(build, faults="d2h:ioerror:nth=1")
    assert_identical(clean, chaos)
    assert "d2h" in fired_sites(s)


def test_spill_write_and_read_ioerror_recover():
    # tiny device + host budgets force the disk tier; transient IO faults
    # on both the write and the read-back must be absorbed by retry.io
    tbl = sort_tbl()
    clean, _, _ = run_query(sort_query(tbl), TINY_MEMORY)
    chaos, s, df = run_query(
        sort_query(tbl), TINY_MEMORY,
        faults="spill_write:ioerror:nth=1;spill_read:ioerror:nth=1")
    assert_identical(clean, chaos)
    assert {"spill_write", "spill_read"} <= fired_sites(s)
    assert df.metrics().get("memory.io_retries", 0) >= 2
    assert df.metrics().get("memory.disk_batches", 0) >= 1


def test_shuffle_write_and_fetch_ioerror_recover():
    rng = np.random.default_rng(55)
    tbl = pa.table({"k": pa.array(rng.integers(0, 50, 3_000), pa.int64()),
                    "v": pa.array(np.ones(3_000))})
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.exec.plan import ExecContext, HostScanExec
    from spark_rapids_tpu.shuffle.partition import HashPartitioning

    def run(conf):
        ctx = ExecContext(conf)
        scan = HostScanExec.from_table(tbl, max_rows=512)
        ex = ShuffleExchangeExec(HashPartitioning([E.ColumnRef("k")], 4),
                                 scan)
        out = ex.collect(ctx)
        rows = sorted(zip(out.column("k").to_pylist(),
                          out.column("v").to_pylist()))
        return rows, conf

    clean, _ = run(TpuConf({"spark.rapids.tpu.retry.io.backoffMs": 0}))
    chaos, conf = run(TpuConf({
        "spark.rapids.tpu.retry.io.backoffMs": 0,
        "spark.rapids.tpu.test.faults":
            "shuffle_write:ioerror:nth=1;shuffle_fetch:ioerror:nth=1"}))
    assert clean == chaos
    assert {"shuffle_write", "shuffle_fetch"} <= \
        {r["site"] for r in get_injector(conf).log}


def test_compile_oom_falls_back_to_eager():
    tbl = sort_tbl(2_000, seed=13)
    build = lambda s: s.from_arrow(tbl).filter(
        E.GreaterThan(col("v"), E.Literal(0.0)))
    clean, _, _ = run_query(build)
    compiled_on = {"spark.rapids.tpu.sql.compile.wholePlan": "ON"}
    chaos, s, df = run_query(build, compiled_on,
                             faults="compile:oom:nth=1")
    assert_identical(clean, chaos)
    assert "compile" in fired_sites(s)
    assert df.metrics().get("whole_plan_fallbacks", 0) >= 1


SPLIT_BG = {
    "spark.rapids.tpu.sql.compile.wholePlan": "ON",
    "spark.rapids.tpu.sql.compile.seamSplitMinRows": "1024",
    # ONE speculative candidate -> deterministic fire ordering: hit #1
    # is segment 0's inline compile, hit #2 the background segment task
    "spark.rapids.tpu.compile.background.speculateBuckets": "1",
}


def _split_build(s):
    # ONE-bucket inputs (1000 rows -> the 1024 minimum bucket): every
    # seam output re-buckets to the single speculative candidate's
    # prediction, so the background task the fault fires in is the one
    # the seam CONSUMES — a mispredicted candidate would swallow the
    # injection and the query would sail through
    n = 1000
    t1 = pa.table({"k": (np.arange(n) % 20).astype(np.int64),
                   "v": np.arange(n, dtype=np.float64)})
    t2 = pa.table({"k": np.arange(20, dtype=np.int64),
                   "w": np.arange(20, dtype=np.float64)})
    from spark_rapids_tpu.plan.aggregates import Sum
    from spark_rapids_tpu.session import lit
    return (s.from_arrow(t1).join(s.from_arrow(t2), on="k")
            .filter(col("v") > lit(100.0))
            .group_by("k").agg((Sum(col("w")), "sw"))
            .sort(("k", True, True)))


def test_background_compile_oom_falls_back_bit_identical():
    """An injected OOM inside a BACKGROUND segment compile re-raises on
    the consuming query thread at the seam and rides the normal ladder:
    whole-plan falls back to the eager engine, bit-identical output."""
    clean, _, _ = run_query(_split_build, SPLIT_BG)
    chaos, s, df = run_query(_split_build, SPLIT_BG,
                             faults="compile:oom:nth=2")
    assert_identical(clean, chaos)
    inj = get_injector(s.conf)
    assert [r["site"] for r in inj.log] == ["compile"]
    assert inj.log[0]["hit"] == 2       # fired in the background task
    assert df.metrics().get("whole_plan_fallbacks", 0) >= 1


def test_background_compile_fatal_crash_dump(tmp_path):
    """A fatal fault in the background compile service surfaces as a
    classified FatalDeviceError on the query thread, with the injected-
    fault record in the crash dump — same contract as inline compiles."""
    with pytest.raises(FatalDeviceError) as ei:
        run_query(_split_build,
                  {**SPLIT_BG,
                   "spark.rapids.tpu.coredump.path": str(tmp_path)},
                  faults="compile:fatal:nth=2")
    assert classify(ei.value) == FATAL_DEVICE
    dump = json.load(open(ei.value.dump_path))
    rec = dump["injected_faults"]
    assert rec and rec[0]["site"] == "compile" and rec[0]["hit"] == 2


def test_exchange_fault_site(eight_devices):
    # the collective fabric has no conf in reach: it fires on the ACTIVE
    # injector (installed per query scope; armed directly here)
    import jax.numpy as jnp
    from spark_rapids_tpu.parallel.multihost import (make_cluster_mesh,
                                                     two_level_all_to_all)
    mesh = make_cluster_mesh(ici_size=4, devices=eight_devices)
    n = mesh.devices.size * 8
    lanes = [jnp.arange(n, dtype=jnp.int32)]
    live = jnp.ones((n,), bool)
    dest = jnp.arange(n, dtype=jnp.int32) % mesh.devices.size
    inj = FaultInjector("exchange:error:nth=1")
    set_active(inj)
    try:
        with pytest.raises(InjectedQueryError):
            two_level_all_to_all(mesh, lanes, live, dest)
        # one-shot: the replay goes through and moves every live row
        outs, out_live = two_level_all_to_all(mesh, lanes, live, dest)
        assert int(out_live.sum()) == n
        assert sorted(np.asarray(outs[0])[np.asarray(out_live)]) == \
            list(range(n))
    finally:
        set_active(NULL_INJECTOR)
    assert [r["site"] for r in inj.log] == ["exchange"]


def _ragged_fixture(eight_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from spark_rapids_tpu.parallel.exchange import RaggedExchange
    from spark_rapids_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(8)
    cap, n = 64, 8 * 64
    rng = np.random.default_rng(51)
    vals = rng.integers(0, 3000, n).astype(np.int64)
    flag = rng.random(n) < 0.5
    live = rng.random(n) < 0.9
    dest = rng.integers(0, 8, n).astype(np.int32)
    shard = NamedSharding(mesh, P(mesh.axis_names[0]))
    put = lambda a: jax.device_put(jnp.asarray(a), shard)  # noqa: E731
    ex = RaggedExchange(mesh, nlanes=2, cap=cap, kinds=["raw", "flag"])
    args = ([put(vals), put(flag)], put(live), put(dest))
    exp = sorted(zip(vals[live].tolist(), flag[live].tolist()))
    return ex, args, exp


def _ragged_rows(out):
    (rv, rf), rlive, _ = out
    rl = np.asarray(rlive)
    return sorted(zip(np.asarray(rv)[rl].tolist(),
                      np.asarray(rf)[rl].tolist()))


def test_exchange_fault_site_ragged_compressed(eight_devices):
    """The exchange site fires on the COMPRESSED ragged path (bitpacked
    flag lane + FOR-narrowed value lane) and the replay recovers
    bit-identically."""
    ex, args, exp = _ragged_fixture(eight_devices)
    inj = FaultInjector("exchange:error:nth=1")
    set_active(inj)
    try:
        with pytest.raises(InjectedQueryError):
            ex(*args)
        assert _ragged_rows(ex(*args)) == exp    # one-shot, bit-identical
    finally:
        set_active(NULL_INJECTOR)
    assert [r["site"] for r in inj.log] == ["exchange"]
    assert ex.last_stats["wire_post"] < ex.last_stats["wire_pre"]


def test_exchange_fatal_dump_embeds_round_state(eight_devices, tmp_path):
    """A fatal on the exchange fabric: the crash dump's flight-recorder
    tail carries the per-round `exchange_round` instants, so the
    post-mortem shows exactly which round of which schedule died."""
    from spark_rapids_tpu.runtime.failure import crash_capture
    ex, args, exp = _ragged_fixture(eight_devices)
    clean = _ragged_rows(ex(*args))              # also warms programs
    assert clean == exp
    conf = TpuConf({"spark.rapids.tpu.coredump.path": str(tmp_path)})
    # nth=2: hit #1 is the plan-time site check, hit #2 fires INSIDE
    # the round loop — after round 0's state instant hit the recorder
    inj = FaultInjector("exchange:fatal:nth=2")
    set_active(inj)
    try:
        with pytest.raises(FatalDeviceError) as ei:
            with crash_capture(conf):
                ex(*args)                        # dies mid-round 0
    finally:
        set_active(NULL_INJECTOR)
    dump = json.load(open(ei.value.dump_path))
    rec = dump["injected_faults"]
    assert rec and rec[0]["site"] == "exchange" and \
        rec[0]["kind"] == "fatal" and rec[0].get("round") == "0"
    rounds = [e for e in dump["flight_recorder"]
              if e.get("name") == "exchange_round"]
    assert rounds, "dump carries no exchange round state"
    attrs = rounds[-1]["attrs"]
    assert {"r", "rounds", "quota", "recv_cap"} <= set(attrs)
    # recovery after the one-shot fatal: same bits as the clean run
    assert _ragged_rows(ex(*args)) == exp


# ---------------------------------------------------------------------------
# fatal / corruption classes: clean classified failure + dump record
# ---------------------------------------------------------------------------

def test_execute_fatal_crash_dump_has_fault_record(tmp_path):
    tbl = sort_tbl(1_000, seed=14)
    s = TpuSession({
        "spark.rapids.tpu.test.faults": "execute:fatal:nth=1",
        "spark.rapids.tpu.coredump.path": str(tmp_path)})
    df = s.from_arrow(tbl).filter(E.GreaterThan(col("v"), E.Literal(0.0)))
    with pytest.raises(FatalDeviceError) as ei:
        df.collect()
    assert classify(ei.value) == FATAL_DEVICE
    dump = json.load(open(ei.value.dump_path))
    rec = dump["injected_faults"]
    assert rec and rec[0]["site"] == "execute" and rec[0]["kind"] == "fatal"


def test_compile_fatal_crash_dump(tmp_path):
    tbl = sort_tbl(1_000, seed=15)
    s = TpuSession({
        "spark.rapids.tpu.sql.compile.wholePlan": "ON",
        "spark.rapids.tpu.test.faults": "compile:fatal:nth=1",
        "spark.rapids.tpu.coredump.path": str(tmp_path)})
    df = s.from_arrow(tbl).filter(E.GreaterThan(col("v"), E.Literal(0.0)))
    with pytest.raises(FatalDeviceError) as ei:
        df.collect()
    dump = json.load(open(ei.value.dump_path))
    assert dump["injected_faults"][0]["site"] == "compile"


def test_spill_read_corrupt_fails_cleanly():
    # a corrupted spill block must surface as a classified
    # CorruptBlockError through the REAL checksum verification path —
    # never a raw native error, and never an infinite IO retry
    tbl = sort_tbl()
    with pytest.raises(CorruptBlockError) as ei:
        run_query(sort_query(tbl), TINY_MEMORY,
                  faults="spill_read:corrupt:nth=1")
    assert classify(ei.value) == CORRUPTION
    assert ei.value.path and "spill" in os.path.basename(ei.value.path)


def test_io_retry_exhaustion_classifies_io():
    tbl = sort_tbl(1_000, seed=16)
    build = lambda s: s.from_arrow(tbl).filter(
        E.GreaterThan(col("v"), E.Literal(0.0)))
    with pytest.raises(OSError) as ei:
        run_query(build, {"spark.rapids.tpu.retry.io.maxAttempts": 2,
                          "spark.rapids.tpu.retry.io.backoffMs": 0},
                  faults="d2h:ioerror:always")
    assert isinstance(ei.value, InjectedIOError)
    assert classify(ei.value) == IO


# ---------------------------------------------------------------------------
# deterministic triggers
# ---------------------------------------------------------------------------

def test_probabilistic_trigger_is_deterministic():
    a = FaultInjector("reserve:oom:p=0.3,seed=7")
    b = FaultInjector("reserve:oom:p=0.3,seed=7")
    outcomes = []
    for inj in (a, b):
        hits = []
        for i in range(50):
            try:
                inj.fire("reserve")
                hits.append(False)
            except Exception:                    # noqa: BLE001
                hits.append(True)
        outcomes.append(hits)
    assert outcomes[0] == outcomes[1]
    assert 1 <= sum(outcomes[0]) <= 30            # ~p=0.3 of 50, seeded

    c = FaultInjector("reserve:oom:p=0.3,seed=8")
    hits_c = []
    for i in range(50):
        try:
            c.fire("reserve")
            hits_c.append(False)
        except Exception:                        # noqa: BLE001
            hits_c.append(True)
    assert hits_c != outcomes[0]                  # seed actually matters


def test_every_trigger_and_log_cap():
    inj = FaultInjector("reserve:ioerror:every=2")
    fired = 0
    for i in range(10):
        try:
            inj.fire("reserve")
        except InjectedIOError:
            fired += 1
    assert fired == 5
    assert all(r["hit"] % 2 == 0 for r in inj.log)


def test_spec_grammar_rejects_garbage():
    for bad in ("nope:oom:nth=1", "reserve:zap:nth=1", "reserve:oom",
                "reserve:oom:banana", "reserve:oom:nth=0",
                "reserve:oom:p=1.5", "shuffle_write:corrupt:nth=1"):
        with pytest.raises(ValueError):
            parse_spec(bad)
    # and the conf checker surfaces it at get time
    from spark_rapids_tpu.config import TEST_FAULTS
    with pytest.raises(ValueError):
        TpuConf({"spark.rapids.tpu.test.faults": "nope:oom:nth=1"}
                ).get(TEST_FAULTS)


# ---------------------------------------------------------------------------
# representative TPC-H / TPC-DS queries under recoverable fault classes
# ---------------------------------------------------------------------------

RECOVERABLE_CLASSES = [
    "execute:oom:nth=1",
    "h2d:ioerror:nth=1",
    "d2h:ioerror:nth=1",
    "reserve:oom:nth=5",
]


def _run_tpch(qname, tables, faults=None):
    settings = {"spark.rapids.tpu.retry.io.backoffMs": 0}
    if faults:
        settings["spark.rapids.tpu.test.faults"] = faults
    s = TpuSession(settings)
    return tpch.QUERIES[qname](s, tables).collect()


def _run_tpcds(qname, tables, faults=None):
    settings = {"spark.rapids.tpu.retry.io.backoffMs": 0}
    if faults:
        settings["spark.rapids.tpu.test.faults"] = faults
    s = TpuSession(settings)
    return tpcds.QUERIES[qname](s, tables).collect()


@pytest.mark.parametrize("faults", RECOVERABLE_CLASSES)
def test_tpch_q6_recoverable_sweep(tpch_tables, faults):
    clean = _run_tpch("q6", tpch_tables)
    chaos = _run_tpch("q6", tpch_tables, faults)
    assert_identical(clean, chaos)


@pytest.mark.parametrize("faults", RECOVERABLE_CLASSES)
def test_tpcds_q3_recoverable_sweep(tpcds_tables, faults):
    clean = _run_tpcds("q3", tpcds_tables)
    chaos = _run_tpcds("q3", tpcds_tables, faults)
    assert_identical(clean, chaos)


def test_tpch_q1_fatal_produces_classified_dump(tpch_tables, tmp_path):
    s = TpuSession({
        "spark.rapids.tpu.test.faults": "execute:fatal:nth=1",
        "spark.rapids.tpu.coredump.path": str(tmp_path)})
    with pytest.raises(FatalDeviceError) as ei:
        tpch.QUERIES["q1"](s, tpch_tables).collect()
    dump = json.load(open(ei.value.dump_path))
    assert dump["classification"] == FATAL_DEVICE
    assert dump["injected_faults"][0]["kind"] == "fatal"


@pytest.mark.slow
@pytest.mark.parametrize("qname", ["q1", "q3", "q6", "q14"])
@pytest.mark.parametrize("faults", RECOVERABLE_CLASSES)
def test_tpch_full_recoverable_sweep(tpch_tables, qname, faults):
    clean = _run_tpch(qname, tpch_tables)
    chaos = _run_tpch(qname, tpch_tables, faults)
    assert_identical(clean, chaos)


@pytest.mark.slow
@pytest.mark.parametrize("qname", ["q3", "q7", "q19", "q42"])
@pytest.mark.parametrize("faults", RECOVERABLE_CLASSES)
def test_tpcds_full_recoverable_sweep(tpcds_tables, qname, faults):
    clean = _run_tpcds(qname, tpcds_tables)
    chaos = _run_tpcds(qname, tpcds_tables, faults)
    assert_identical(clean, chaos)


# ---------------------------------------------------------------------------
# serving plane: admission-timeout and result-cache corruption recovery
# ---------------------------------------------------------------------------

def _serving_fixture(faults=None, **serving_settings):
    settings = {"spark.rapids.tpu.sql.compile.wholePlan": "ON",
                **serving_settings}
    if faults:
        settings["spark.rapids.tpu.test.faults"] = faults
    s = TpuSession(settings)
    from spark_rapids_tpu.plan.aggregates import Sum
    tbl = pa.table({"k": [i % 5 for i in range(400)],
                    "x": [float(i) for i in range(400)]})
    build = lambda: s.from_arrow(tbl).filter(       # noqa: E731
        E.GreaterThan(col("x"), E.Literal(7.0))).group_by("k").agg(
        (Sum(col("x")), "sx"))
    return s, build


def test_serving_admission_timeout_recovers_bit_identical():
    """`serving:timeout:nth=1` (the admission-backpressure fault): the
    tenant handle's single bounded re-admission recovers and the result
    is bit-identical to the clean run — under CONCURRENT load, every
    other in-flight query unaffected."""
    from spark_rapids_tpu.serving import InjectedAdmissionTimeout
    s_clean, build_clean = _serving_fixture()
    clean = build_clean().collect()
    s, build = _serving_fixture(
        faults="serving:timeout:nth=3",
        **{"spark.rapids.tpu.serving.workers": "4",
           "spark.rapids.tpu.serving.resultCache.bytes": "0"})
    try:
        rt = s.serving()
        a = rt.tenant("a")
        # 6 concurrent submits through collect(): hit #3 fires the
        # injected timeout; the handle re-admits once and succeeds
        import threading
        results, errs = [], []

        def client():
            try:
                results.append(a.collect(build()))
            except Exception as e:                   # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client) for _ in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errs, errs
        assert len(results) == 6
        for r in results:
            assert_identical(clean, r)
        log = get_injector(s.conf).log
        assert [r["site"] for r in log] == ["serving"]
        assert log[0]["kind"] == "timeout"
        # the raw submit path DOES surface the classified signal
        s2, build2 = _serving_fixture(faults="serving:timeout:nth=1")
        rt2 = s2.serving()
        with pytest.raises(InjectedAdmissionTimeout):
            rt2.submit(build2())
        s2.close()
    finally:
        s.close()
        s_clean.close()


def test_result_cache_corrupt_recomputes_bit_identical():
    """`result_cache:corrupt:nth=1`: the first cache READ gets its IPC
    payload corrupted in place; the REAL checksum verification rejects
    it, the entry drops, the query recomputes — bit-identical — and the
    refreshed entry serves the next hit."""
    from spark_rapids_tpu.obs.registry import SERVING_RESULT_CACHE
    s_clean, build_clean = _serving_fixture()
    clean = build_clean().collect()
    s, build = _serving_fixture(faults="result_cache:corrupt:nth=1")
    try:
        rt = s.serving()
        a = rt.tenant("a")
        c0 = SERVING_RESULT_CACHE.value(outcome="corrupt") or 0
        h0 = SERVING_RESULT_CACHE.value(outcome="hit") or 0
        first = a.collect(build())       # miss + store
        second = a.collect(build())      # read -> corrupt -> recompute
        third = a.collect(build())       # clean hit off the re-store
        for r in (first, second, third):
            assert_identical(clean, r)
        assert (SERVING_RESULT_CACHE.value(outcome="corrupt") or 0) \
            - c0 == 1
        assert (SERVING_RESULT_CACHE.value(outcome="hit") or 0) - h0 >= 1
        log = get_injector(s.conf).log
        assert [r["site"] for r in log] == ["result_cache"]
        assert "payload" not in log[0]   # bulk bytes stay out of logs
    finally:
        s.close()
        s_clean.close()


def test_serving_fault_kind_gates():
    """`timeout` only means something at the admission site; `corrupt`
    only at sites with a payload (a disk block or a cached result)."""
    parse_spec("serving:timeout:nth=1")
    parse_spec("result_cache:corrupt:nth=1")
    parse_spec("spill_read:corrupt:nth=1")
    with pytest.raises(ValueError):
        parse_spec("reserve:timeout:nth=1")
    with pytest.raises(ValueError):
        parse_spec("execute:corrupt:nth=1")


# ---------------------------------------------------------------------------
# kernel site: the encoded-execution dispatch gate (ops/encodings.py)
# ---------------------------------------------------------------------------

def _join_df(s):
    rng = np.random.default_rng(21)
    fact = s.from_arrow(pa.table({
        "fk": pa.array(rng.integers(0, 40, 3000), pa.int64()),
        "v": pa.array(rng.standard_normal(3000))}))
    dim = s.from_arrow(pa.table({
        "k": pa.array(np.arange(50), pa.int64()),
        "w": pa.array(np.arange(50) * 1.5)}))
    return fact.join(dim, left_on=["fk"], right_on=["k"],
                     how="inner").sort(("v", True, True))


def test_compile_and_execute_sites_fire_on_whole_plan_join():
    """The compile/execute recovery rungs on the engine the chip runs
    (compile.wholePlan=ON, default conf otherwise): a whole-plan
    compile OOM falls back (eager re-run) and an execute OOM replays —
    both bit-identical to the clean whole-plan run."""
    wp = {"spark.rapids.tpu.sql.compile.wholePlan": "ON"}
    clean, _s, _df = run_query(_join_df, wp)
    for faults in ("compile:oom:nth=1", "execute:oom:nth=1"):
        chaos, s, _df = run_query(_join_df, wp, faults=faults)
        assert_identical(clean, chaos)
        assert fired_sites(s) == {faults.split(":")[0]}


def _encoded_probe_df(s):
    """A code-space pipeline: dictionary equality predicate feeding a
    dict-key join probe — every string stage runs in code space
    (ops/encodings.py) under the default encoded policy."""
    rng = np.random.default_rng(29)
    keys = ["k%02d" % i for i in range(30)]
    fact = s.from_arrow(pa.table({
        "fk": pa.array([keys[i] for i in rng.integers(0, 30, 2000)],
                       pa.string()),
        "v": pa.array(rng.standard_normal(2000))}))
    dim = s.from_arrow(pa.table({
        "k": pa.array(keys, pa.string()),
        "w": pa.array(np.arange(30) * 1.5)}))
    return (fact.filter(E.NotEqual(col("fk"), E.Literal("k07")))
            .join(dim, left_on=["fk"], right_on=["k"], how="inner")
            .sort(("v", True, True)))


def test_kernel_oom_sheds_encoded_probe_to_decoded_tier():
    """ISSUE 13 chaos rung: an injected OOM at the kernel site during a
    CODE-SPACE dispatch (the dictionary-predicate election feeding the
    join probe) sheds that dispatch onto the DECODED tier — the legacy
    remap-gather path — and the query completes BIT-IDENTICAL,
    observable as tpu_encoded_dispatch_total{outcome=oom_shed}."""
    from spark_rapids_tpu.obs.registry import ENCODED_DISPATCH
    clean, _s, _df = run_query(_encoded_probe_df)
    base = ENCODED_DISPATCH.value(site="predicate_code",
                                  outcome="oom_shed") or 0
    chaos, s, _df = run_query(_encoded_probe_df,
                              faults="kernel:oom:nth=1")
    assert_identical(clean, chaos)
    assert (ENCODED_DISPATCH.value(site="predicate_code",
                                   outcome="oom_shed") or 0) > base
    log = get_injector(s.conf).log
    assert log[0]["site"] == "kernel"
    # the injected-fault record names the encoded dispatch that shed
    assert log[0]["kernel"] == "predicate_code"


def test_kernel_fatal_dump_names_kernel(tmp_path):
    """kind 'fatal' at the kernel site surfaces as a classified
    FATAL_DEVICE crash dump whose injected-fault record names the
    encoded dispatch that was electing."""
    s = TpuSession({"spark.rapids.tpu.test.faults": "kernel:fatal:nth=1",
                    "spark.rapids.tpu.coredump.path": str(tmp_path)})
    with pytest.raises(FatalDeviceError) as ei:
        _encoded_probe_df(s).collect()
    assert classify(ei.value) == FATAL_DEVICE
    dump = json.load(open(ei.value.dump_path))
    rec = dump["injected_faults"][0]
    assert rec["site"] == "kernel" and rec["kind"] == "fatal"
    assert rec["kernel"] == "predicate_code"


def test_kernel_error_kind_propagates_as_query_error():
    with pytest.raises(InjectedQueryError):
        run_query(_encoded_probe_df, faults="kernel:error:nth=1")


# ---------------------------------------------------------------------------
# ooc site: chaos INSIDE the out-of-core window (ISSUE 15)
# ---------------------------------------------------------------------------

#: forces the OOC tier through small inputs (join byte gate + agg)
OOC_CONF = {
    "spark.rapids.tpu.memory.tpu.budgetBytes": 1 << 17,
    "spark.rapids.tpu.sql.batchSizeRows": 1024,
    "spark.rapids.tpu.sql.shape.minBucketRows": 256,
    "spark.rapids.tpu.sql.ooc.force": "true",
    "spark.rapids.tpu.retry.io.backoffMs": 0,
}


def _ooc_join_agg_df(s):
    from spark_rapids_tpu.plan.aggregates import Sum
    rng = np.random.default_rng(43)
    fact = s.from_arrow(pa.table({
        "fk": pa.array(rng.integers(0, 40, 4000), pa.int64()),
        "v": pa.array(rng.standard_normal(4000))}))
    dim = s.from_arrow(pa.table({
        "k": pa.array(np.arange(50), pa.int64()),
        "w": pa.array(np.arange(50) * 1.5)}))
    return (fact.join(dim, left_on=["fk"], right_on=["k"], how="inner")
            .group_by("fk").agg((Sum(col("v")), "sv")))


def test_ooc_oom_mid_join_recovers_bit_identical():
    """`ooc:oom:nth=1` fires at the FIRST out-of-core partition pass
    (after its `ooc_state` instant): the OOM rides the ladder into the
    OOC escalation rung and the replay — already spill-partitioned —
    is bit-identical to the clean degraded run."""
    clean, _s, _df = run_query(_ooc_join_agg_df, OOC_CONF)
    chaos, s, df = run_query(_ooc_join_agg_df, OOC_CONF,
                             faults="ooc:oom:nth=1")
    assert_identical(clean, chaos)
    log = get_injector(s.conf).log
    assert log and log[0]["site"] == "ooc"
    assert log[0]["op"] in ("join", "agg", "sort")
    assert df.metrics().get("query_ooc_escalations", 0) == 1


def test_ooc_fatal_dump_embeds_bucket_state(tmp_path):
    """kind 'fatal' at the ooc site: the classified crash dump's
    flight-recorder tail carries the `ooc_state` instants, so the
    post-mortem names the exact partition pass that died."""
    settings = {**OOC_CONF,
                "spark.rapids.tpu.coredump.path": str(tmp_path)}
    with pytest.raises(FatalDeviceError) as ei:
        run_query(_ooc_join_agg_df, settings, faults="ooc:fatal:nth=2")
    assert classify(ei.value) == FATAL_DEVICE
    dump = json.load(open(ei.value.dump_path))
    rec = dump["injected_faults"]
    assert rec and rec[0]["site"] == "ooc" and rec[0]["kind"] == "fatal"
    states = [e for e in dump["flight_recorder"]
              if e.get("name") == "ooc_state"]
    assert states, "dump carries no ooc bucket state"
    attrs = states[-1]["attrs"]
    assert "op" in attrs and "bucket" in attrs and "depth" in attrs


# ---------------------------------------------------------------------------
# mid-merge chaos inside the OutOfCoreSorter window (ISSUE 15 satellite:
# the sweeps above never fired INSIDE the OOC merge — these do, by
# splitting each site's deterministic hit counter at the add->merge
# phase boundary and scheduling nth= just past it)
# ---------------------------------------------------------------------------

OOC_SORT_CONF = {
    "spark.rapids.tpu.memory.tpu.budgetBytes": 1 << 16,
    "spark.rapids.tpu.memory.host.spillStorageSize": 1 << 14,
    "spark.rapids.tpu.sql.batchSizeRows": 1024,
    "spark.rapids.tpu.sql.shape.minBucketRows": 256,
    "spark.rapids.tpu.retry.io.backoffMs": 0,
}

#: never-firing counting rules: one per site whose add/merge hit split
#: the scheduler below needs (hits increment identically in every run
#: up to the first fire, so a dry run's counters place later runs'
#: nth= triggers INSIDE the merge window deterministically)
_COUNTING_SPEC = ("spill_read:ioerror:nth=999983;"
                  "spill_write:ioerror:nth=999983;"
                  "reserve:oom:nth=999983")


def _drive_ooc_sorter(faults, n=24_000, seed=61):
    """Feed the OutOfCoreSorter directly, recording each armed site's
    hit counter AT THE ADD->MERGE BOUNDARY, then drain the merge.
    Returns (values, ctx, injector, marks_at_merge_start)."""
    from spark_rapids_tpu.columnar.device import to_host
    from spark_rapids_tpu.exec.ooc_sort import OutOfCoreSorter
    from spark_rapids_tpu.exec.plan import ExecContext, HostScanExec
    from spark_rapids_tpu.ops.sort import SortKey
    settings = dict(OOC_SORT_CONF)
    settings["spark.rapids.tpu.test.faults"] = faults
    conf = TpuConf(settings)
    ctx = ExecContext(conf)
    rng = np.random.default_rng(seed)
    tbl = pa.table({"v": pa.array(rng.standard_normal(n))})
    scan = HostScanExec.from_table(tbl, max_rows=1024)
    sorter = OutOfCoreSorter([SortKey(0, True, True)], ctx)
    for db in scan.execute(ctx):
        sorter.add(db)
    inj = get_injector(conf)
    marks = {}
    for r in getattr(inj, "rules", []):
        marks[r.site] = marks.get(r.site, 0) + r.hits
    out = []
    for b in sorter.results():
        hb = to_host(b)
        out.extend(hb.rb.column(0).to_pylist()[:int(b.num_rows)])
    return out, ctx, inj, marks


def test_ooc_sorter_merge_actually_hits_spill_sites():
    """Dry run (never-firing counters): the merge phase itself drives
    spill reads/writes and budget reservations — the window the armed
    tests below schedule their faults into."""
    out, ctx, inj, marks = _drive_ooc_sorter(_COUNTING_SPEC)
    assert out == sorted(out) and len(out) == 24_000
    assert ctx.metrics.get("sort_merge_passes", 0) >= 2
    totals = {r.site: r.hits for r in inj.rules}
    for site in ("spill_read", "reserve"):
        assert totals[site] > marks[site], \
            f"{site} never fired inside the merge window"
    # cache the split for the armed runs (deterministic per spec)
    global _MERGE_MARKS
    _MERGE_MARKS = marks


_MERGE_MARKS = None


def _merge_mark(site):
    global _MERGE_MARKS
    if _MERGE_MARKS is None:
        _drive = _drive_ooc_sorter(_COUNTING_SPEC)
        _MERGE_MARKS = _drive[3]
    return _MERGE_MARKS[site]


def test_spill_read_ioerror_mid_merge_recovers():
    clean, _, _, _ = _drive_ooc_sorter(_COUNTING_SPEC)
    nth = _merge_mark("spill_read") + 1
    out, ctx, inj, _ = _drive_ooc_sorter(f"spill_read:ioerror:nth={nth}")
    assert out == clean                    # bit-identical through retry.io
    assert inj.log and inj.log[0]["site"] == "spill_read"
    assert inj.log[0]["hit"] == nth        # fired INSIDE the merge
    assert ctx.budget.metrics["io_retries"] >= 1


def test_spill_write_ioerror_mid_merge_recovers():
    clean, _, _, _ = _drive_ooc_sorter(_COUNTING_SPEC)
    nth = _merge_mark("spill_write") + 1
    out, ctx, inj, _ = _drive_ooc_sorter(
        f"spill_write:ioerror:nth={nth}")
    assert out == clean
    assert inj.log and inj.log[0]["site"] == "spill_write"
    assert inj.log[0]["hit"] == nth


def test_reserve_oom_mid_merge_replays_bit_identical():
    """A budget OOM INSIDE the merge window escapes the sorter; the
    query ladder's answer is spill-everything + replay — re-driving the
    sorter after spill_all reproduces the clean output bit-for-bit
    (the one-shot rule already fired)."""
    from spark_rapids_tpu.runtime.memory import TpuRetryOOM
    from spark_rapids_tpu.columnar.device import to_host
    from spark_rapids_tpu.exec.ooc_sort import OutOfCoreSorter
    from spark_rapids_tpu.exec.plan import ExecContext, HostScanExec
    from spark_rapids_tpu.ops.sort import SortKey
    clean, _, _, _ = _drive_ooc_sorter(_COUNTING_SPEC)
    nth = _merge_mark("reserve") + 1
    settings = dict(OOC_SORT_CONF)
    settings["spark.rapids.tpu.test.faults"] = f"reserve:oom:nth={nth}"
    conf = TpuConf(settings)
    ctx = ExecContext(conf)
    rng = np.random.default_rng(61)
    tbl = pa.table({"v": pa.array(rng.standard_normal(24_000))})

    def drive():
        scan = HostScanExec.from_table(tbl, max_rows=1024)
        sorter = OutOfCoreSorter([SortKey(0, True, True)], ctx)
        for db in scan.execute(ctx):
            sorter.add(db)
        out = []
        for b in sorter.results():
            hb = to_host(b)
            out.extend(hb.rb.column(0).to_pylist()[:int(b.num_rows)])
        return out

    with pytest.raises(TpuRetryOOM):
        drive()                            # dies INSIDE the merge
    inj = get_injector(conf)
    assert inj.log and inj.log[0]["site"] == "reserve" and \
        inj.log[0]["hit"] == nth
    ctx.budget.spill_all()                 # the ladder's replay recipe
    assert drive() == clean


def test_spill_read_corrupt_mid_merge_classified():
    nth = _merge_mark("spill_read") + 1
    with pytest.raises(CorruptBlockError) as ei:
        _drive_ooc_sorter(f"spill_read:corrupt:nth={nth}")
    assert classify(ei.value) == CORRUPTION
    assert ei.value.path and "spill" in os.path.basename(ei.value.path)


def test_ooc_fatal_mid_sorter_merge_dump_names_pass(tmp_path):
    """`ooc:fatal:nth=2`: the SECOND merge pass dies; the crash dump's
    flight tail shows the sort-window state (op=sort, merge_pass)."""
    from spark_rapids_tpu.exec.ooc_sort import OutOfCoreSorter
    from spark_rapids_tpu.exec.plan import ExecContext, HostScanExec
    from spark_rapids_tpu.ops.sort import SortKey
    from spark_rapids_tpu.runtime.failure import crash_capture
    conf = TpuConf({**OOC_SORT_CONF,
                    "spark.rapids.tpu.test.faults": "ooc:fatal:nth=2",
                    "spark.rapids.tpu.coredump.path": str(tmp_path)})
    ctx = ExecContext(conf)
    rng = np.random.default_rng(61)
    tbl = pa.table({"v": pa.array(rng.standard_normal(24_000))})
    with pytest.raises(FatalDeviceError) as ei:
        with crash_capture(conf):       # same conf: the dump embeds the
            scan = HostScanExec.from_table(tbl, max_rows=1024)
            sorter = OutOfCoreSorter([SortKey(0, True, True)], ctx)
            for db in scan.execute(ctx):    # injected-fault record
                sorter.add(db)
            for _ in sorter.results():
                pass
    dump = json.load(open(ei.value.dump_path))
    rec = dump["injected_faults"]
    assert rec and rec[0]["site"] == "ooc" and rec[0]["kind"] == "fatal"
    assert rec[0]["op"] == "sort" and rec[0]["merge_pass"] == "1"
    states = [e for e in dump["flight_recorder"]
              if e.get("name") == "ooc_state" and
              e["attrs"].get("op") == "sort"]
    assert states and states[-1]["attrs"].get("merge_pass") == 1
    assert "runs" in states[-1]["attrs"]


# ---------------------------------------------------------------------------
# history site: the performance-history plane must never fail work
# ---------------------------------------------------------------------------

def _history_build(tbl):
    return lambda s: s.from_arrow(tbl).filter(
        E.GreaterThan(col("v"), E.Literal(0.0))).sort(("v", True, True))


def test_history_ioerror_skips_entry_query_unaffected(tmp_path):
    """`history:ioerror:always`: every history append fails — the store
    skips the entry (tpu_history_records_total{outcome=io_error}), the
    file never materializes, and the query result is BIT-IDENTICAL to
    the clean run: telemetry loss must never cost work."""
    from spark_rapids_tpu.obs.registry import HISTORY_RECORDS
    tbl = sort_tbl(2_000, seed=31)
    clean, _s, _df = run_query(_history_build(tbl))
    hd = tmp_path / "hist"
    io0 = HISTORY_RECORDS.value(outcome="io_error") or 0
    chaos, s, _df = run_query(
        _history_build(tbl),
        {"spark.rapids.tpu.history.dir": str(hd)},
        faults="history:ioerror:always")
    assert_identical(clean, chaos)
    assert "history" in fired_sites(s)
    assert (HISTORY_RECORDS.value(outcome="io_error") or 0) - io0 >= 1
    from spark_rapids_tpu.obs.history import get_store
    store = get_store(s.conf)
    assert store is not None and store.recorded == 0
    assert not os.path.exists(store.path)


def test_history_fatal_classified_dump(tmp_path):
    """`history:fatal:nth=1`: a fatal on the history write path surfaces
    through the query's crash-capture scope as a classified
    FatalDeviceError whose dump's injected-fault record names the
    site."""
    tbl = sort_tbl(1_500, seed=33)
    with pytest.raises(FatalDeviceError) as ei:
        run_query(
            _history_build(tbl),
            {"spark.rapids.tpu.history.dir": str(tmp_path / "hist"),
             "spark.rapids.tpu.coredump.path": str(tmp_path)},
            faults="history:fatal:nth=1")
    dump = json.load(open(ei.value.dump_path))
    rec = dump["injected_faults"]
    assert rec and rec[0]["site"] == "history" and \
        rec[0]["kind"] == "fatal"


# ---------------------------------------------------------------------------
# memattr site: memory-attribution sampling must never cost work
# ---------------------------------------------------------------------------

#: profiled whole-plan conf — the memattr census fires per segment
#: dispatch only when the plane is armed
MEMATTR_ON = {"spark.rapids.tpu.sql.compile.wholePlan": "ON",
              "spark.rapids.tpu.profile.segments": "true"}


def _memattr_build(tbl):
    return lambda s: s.from_arrow(tbl).filter(
        E.GreaterThan(col("v"), E.Literal(0.0))).sort(("v", True, True))


def test_memattr_ioerror_skips_sample_query_bit_identical():
    """`memattr:ioerror:always`: every segment census read fails — the
    HBM sample is SKIPPED (memattr_census_skipped) and the query
    result is BIT-IDENTICAL to the clean profiled run: memory
    sampling must never cost work."""
    tbl = sort_tbl(2_000, seed=35)
    clean, _s, _df = run_query(_memattr_build(tbl), MEMATTR_ON)
    chaos, s, df = run_query(_memattr_build(tbl), MEMATTR_ON,
                             faults="memattr:ioerror:always")
    assert_identical(clean, chaos)
    assert "memattr" in fired_sites(s)
    m = df.metrics()
    assert m.get("memattr_census_skipped", 0) >= 1
    # skipped means skipped: no segment hbm attribution recorded
    assert not any(k.endswith(".hbm_peak_bytes") for k in m), sorted(m)


def test_memattr_fatal_dump_embeds_partial_timeline(tmp_path):
    """`memattr:fatal:nth=1`: a fatal on the census read surfaces as a
    classified FATAL_DEVICE crash dump that embeds the PARTIAL HBM
    timeline collected up to the fault (the forensics contract)."""
    tbl = sort_tbl(1_500, seed=37)
    with pytest.raises(FatalDeviceError) as ei:
        run_query(
            _memattr_build(tbl),
            {**MEMATTR_ON,
             "spark.rapids.tpu.coredump.path": str(tmp_path)},
            faults="memattr:fatal:nth=1")
    assert classify(ei.value) == FATAL_DEVICE
    dump = json.load(open(ei.value.dump_path))
    rec = dump["injected_faults"]
    assert rec and rec[0]["site"] == "memattr" and \
        rec[0]["kind"] == "fatal"
    # the partial timeline rides the dump (at least the start marker)
    assert isinstance(dump.get("hbm_timeline"), list)
    assert dump["hbm_timeline"] and \
        dump["hbm_timeline"][0]["ev"] == "start"
    assert "hbm_census" in dump


# ---------------------------------------------------------------------------
# coverage lint: every registered site is exercised by this file
# ---------------------------------------------------------------------------

def test_every_registered_site_has_a_chaos_test():
    import subprocess
    import sys
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "scripts",
             "check_fault_sites.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_sites_registry_matches_docs():
    # the fault-spec grammar doc (docs/ROBUSTNESS.md) must name every
    # site so operators can discover them without reading source
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = open(os.path.join(root, "docs", "ROBUSTNESS.md")).read()
    missing = [site for site in SITES if f"`{site}`" not in doc]
    assert not missing, f"docs/ROBUSTNESS.md missing sites: {missing}"


# ---------------------------------------------------------------------------
# worker / deadline sites: fault-isolated multi-process serving
# ---------------------------------------------------------------------------

#: pool shape for the worker-site tests: 2 processes, fast health
#: detection (the hang window is heartbeatMs x heartbeatMisses)
MP_POOL = {
    "spark.rapids.tpu.serving.pool.processes": "2",
    "spark.rapids.tpu.serving.pool.heartbeatMs": "100",
    "spark.rapids.tpu.serving.pool.heartbeatMisses": "6",
}


def _serving_tbl(n=400):
    return pa.table({"k": [i % 5 for i in range(n)],
                     "x": [float(i % 13) for i in range(n)]})


def _serving_query(s, tbl):
    from spark_rapids_tpu.plan.aggregates import Sum
    return (s.from_arrow(tbl).filter(col("x") > E.Literal(1.0))
            .group_by("k").agg((Sum(col("x")), "sx")))


def _rows(table):
    d = table.to_pydict()
    names = sorted(d)
    return sorted(zip(*(d[n] for n in names)))


def test_worker_kill_mid_query_redrives_bit_identically():
    """The headline crash-containment proof: `worker:kill` SIGKILLs a
    worker process the moment its dispatched query is mid-flight;
    under multi-tenant load ONLY that query redrives — on a surviving
    worker, bit-identically vs the CPU oracle — while other tenants'
    queries complete uninterrupted."""
    tbl = _serving_tbl()
    s = TpuSession({"spark.rapids.tpu.test.faults": "worker:kill:nth=1"})
    try:
        rt = s.serving(dict(MP_POOL))
        bi, etl = rt.tenant("bi"), rt.tenant("etl")
        expected = _rows(_serving_query(s, tbl).collect())
        tickets = [t.submit(_serving_query(s, tbl))
                   for t in (bi, etl, bi, etl)]
        for tk in tickets:
            assert _rows(tk.result(timeout=240)) == expected
        st = rt.stats()["pool"]
        assert st["restarts"].get("crash") == 1    # exactly one victim
        assert st["redrives"] >= 1
        assert sum(tk.redrives for tk in tickets) >= 1
        # containment: every query completed, none failed
        assert all(tk.error is None for tk in tickets)
    finally:
        s.close()


def test_worker_hang_heartbeat_window_detects_and_redrives():
    """`worker:hang` wedges a worker (heartbeats stop, the query never
    answers): the supervisor's heartbeat-miss window SIGKILLs it and
    the in-flight query redrives bit-identically."""
    tbl = _serving_tbl()
    s = TpuSession({"spark.rapids.tpu.test.faults": "worker:hang:nth=1"})
    try:
        rt = s.serving(dict(MP_POOL))
        ses = rt.tenant("bi")
        expected = _rows(_serving_query(s, tbl).collect())
        tk = ses.submit(_serving_query(s, tbl))
        assert _rows(tk.result(timeout=240)) == expected
        st = rt.stats()["pool"]
        assert st["restarts"].get("hang") == 1
        assert st["redrives"] >= 1
    finally:
        s.close()


def test_worker_fatal_dump_names_worker_then_redrives(tmp_path):
    """`worker:fatal` arms the in-worker fatal injector: the victim
    writes a classified crash dump naming its worker id + pid, self-
    terminates (the executor-self-termination contract), and the query
    redrives cleanly — the redrive conf carries no injected fatal."""
    tbl = _serving_tbl()
    s = TpuSession({"spark.rapids.tpu.test.faults": "worker:fatal:nth=1",
                    "spark.rapids.tpu.coredump.path": str(tmp_path)})
    try:
        rt = s.serving(dict(MP_POOL))
        ses = rt.tenant("bi")
        expected = _rows(_serving_query(s, tbl).collect())
        tk = ses.submit(_serving_query(s, tbl))
        assert _rows(tk.result(timeout=240)) == expected
        st = rt.stats()["pool"]
        assert st["restarts"].get("fatal") == 1
        assert st["redrives"] >= 1
        import glob
        dumps = glob.glob(str(tmp_path / "tpu-coredump-*.json"))
        assert len(dumps) == 1
        info = json.load(open(dumps[0]))
        assert info["classification"] == FATAL_DEVICE
        assert info["worker_id"] in ("w1", "w2")
        # dump filename embeds the WORKER's pid, not the supervisor's
        assert str(info["pid"]) in os.path.basename(dumps[0])
        assert info["pid"] != os.getpid()
    finally:
        s.close()


def test_deadline_timeout_injected_cancels_and_releases():
    """`deadline:timeout` fires a synthetic expiry at a cancellation
    checkpoint: the query fails with InjectedDeadlineExceeded (a
    QUERY-class failure — no retry, no dump), its whole device
    reservation releases (DeviceCensus zero residual), and the runtime
    keeps serving."""
    from spark_rapids_tpu.exec.plan import (InjectedDeadlineExceeded,
                                            QueryDeadlineExceeded)
    from spark_rapids_tpu.obs.memattr import CENSUS
    from spark_rapids_tpu.runtime.failure import QUERY
    tbl = _serving_tbl()
    s = TpuSession(
        {"spark.rapids.tpu.test.faults": "deadline:timeout:nth=1"})
    # CENSUS is process-wide: other tests' not-yet-collected budgets can
    # hold bytes, so assert zero RESIDUAL GROWTH, not an absolute zero
    import gc
    gc.collect()
    base_live = CENSUS.totals()["live_bytes"]
    try:
        rt = s.serving()
        ses = rt.tenant("bi")
        tk = ses.submit(_serving_query(s, tbl))
        with pytest.raises(InjectedDeadlineExceeded):
            tk.result(timeout=120)
        assert classify(tk.error) == QUERY     # fails cleanly, no dump
        assert isinstance(tk.error, QueryDeadlineExceeded)
        assert rt.stats()["deadline_cancellations"] == 1
        assert rt._device_bytes == 0
        gc.collect()
        assert CENSUS.totals()["live_bytes"] <= base_live
        assert "deadline" in fired_sites(s)
        # unharmed: the next query completes
        expected = _rows(_serving_query(s, tbl).collect())
        assert _rows(ses.collect(_serving_query(s, tbl),
                                 timeout=120)) == expected
    finally:
        s.close()


def test_worker_kinds_grammar_is_site_restricted():
    """kill/hang are process-level faults: only the `worker` site may
    carry them, and `worker` carries nothing else."""
    parse_spec("worker:kill:nth=3")                  # valid
    parse_spec("worker:hang:always")                 # valid
    parse_spec("worker:fatal:p=0.5,seed=7")          # valid
    with pytest.raises(ValueError):
        parse_spec("seam:kill:always")               # kill off-site
    with pytest.raises(ValueError):
        parse_spec("spill:hang:nth=1")               # hang off-site
    with pytest.raises(ValueError):
        parse_spec("worker:oom:always")              # non-worker kind


# ---------------------------------------------------------------------------
# fleet site: observability federation must never cost work
# ---------------------------------------------------------------------------


def test_fleet_grammar_is_telemetry_restricted():
    """The federation fold can lose a frame (ioerror) or dump-and-
    survive (fatal); process-level and query-level kinds are illegal
    at the `fleet` site."""
    parse_spec("fleet:ioerror:nth=1")                # valid
    parse_spec("fleet:fatal:always")                 # valid
    with pytest.raises(ValueError):
        parse_spec("fleet:kill:nth=1")               # process-level kind
    with pytest.raises(ValueError):
        parse_spec("fleet:oom:always")               # non-telemetry kind
    with pytest.raises(ValueError):
        parse_spec("fleet:timeout:nth=1")            # timeout off-site


def test_fleet_ioerror_drops_one_frame_then_converges():
    """`fleet:ioerror` drops exactly ONE telemetry heartbeat frame
    SUPERVISOR-side: the in-flight query stays bit-identical, no worker
    is falsely declared dead over lost telemetry, and because workers
    ship CUMULATIVE registry snapshots the fleet view converges on the
    very next beat — the per-worker tenant counter still lands."""
    import time as _time

    from spark_rapids_tpu.obs.registry import REGISTRY
    tbl = _serving_tbl()

    def dropped():
        return REGISTRY.flat().get(
            "tpu_fleet_frames_total{outcome=dropped}", 0)

    base_dropped = dropped()
    s = TpuSession({"spark.rapids.tpu.test.faults": "fleet:ioerror:nth=1"})
    try:
        rt = s.serving(dict(MP_POOL))
        ses = rt.tenant("fleet_io_tenant")
        expected = _rows(_serving_query(s, tbl).collect())
        tk = ses.submit(_serving_query(s, tbl))
        assert _rows(tk.result(timeout=240)) == expected
        assert tk.error is None and tk.redrives == 0
        # the drop happened (nth=1: the FIRST telemetry frame died)...
        deadline = _time.time() + 60
        while dropped() == base_dropped and _time.time() < deadline:
            _time.sleep(0.05)
        assert dropped() == base_dropped + 1
        # ...and the federation converged anyway: the next beats carry
        # the same cumulative counters, so the fleet view still shows
        # this tenant's worker-side device time
        key_frag = "tenant=fleet_io_tenant"
        while _time.time() < deadline:
            fleet = rt.stats().get("fleet") or {}
            hit = [k for k in fleet
                   if k.startswith("tpu_fleet_serving_tenant_"
                                   "device_us_total{")
                   and key_frag in k]
            if hit:
                break
            _time.sleep(0.05)
        assert hit, f"fleet view never converged: {sorted(fleet)[:8]}"
        assert all("worker=" in k for k in hit)
        # telemetry loss is not worker loss
        assert rt.stats()["pool"]["restarts"] == {}
        # the fold fires on the RUNTIME conf's injector (the supervisor
        # owns the fold), not the submitting session's
        assert "fleet" in {r["site"]
                           for r in get_injector(rt._rconf).log}
    finally:
        s.close()


@pytest.mark.slow
def test_fleet_fatal_dump_names_site_and_pool_survives(tmp_path):
    """`fleet:fatal` in the supervisor's fold path writes a classified
    FATAL_DEVICE dump whose injected-fault record names the site, drops
    that frame — and the pool keeps serving: telemetry must never take
    serving down."""
    import glob
    import time as _time
    tbl = _serving_tbl()
    s = TpuSession({"spark.rapids.tpu.test.faults": "fleet:fatal:nth=1",
                    "spark.rapids.tpu.coredump.path": str(tmp_path)})
    try:
        rt = s.serving(dict(MP_POOL))
        ses = rt.tenant("bi")
        expected = _rows(_serving_query(s, tbl).collect())
        tk = ses.submit(_serving_query(s, tbl))
        assert _rows(tk.result(timeout=240)) == expected
        # the fold fires on the heartbeat cadence: wait for the dump
        deadline = _time.time() + 60
        dumps = []
        while not dumps and _time.time() < deadline:
            dumps = glob.glob(str(tmp_path / "tpu-coredump-*.json"))
            _time.sleep(0.05)
        assert len(dumps) == 1
        info = json.load(open(dumps[0]))
        assert info["classification"] == FATAL_DEVICE
        # written by the SUPERVISOR (this process), not a worker
        assert info["pid"] == os.getpid()
        assert any(r.get("site") == "fleet"
                   for r in info.get("injected_faults", []))
        # the pool survived the telemetry fault: no worker died, and
        # the next query completes
        assert rt.stats()["pool"]["restarts"] == {}
        assert _rows(ses.collect(_serving_query(s, tbl),
                                 timeout=240)) == expected
    finally:
        s.close()


def test_worker_kill_stitched_record_and_black_box(tmp_path):
    """The PR-20 acceptance drill: a `worker:kill` chaos run must leave
    (a) a WorkerLost black-box dump embedding the victim's last
    heartbeat-carried flight snapshot plus its in-flight ticket state,
    and (b) ONE stitched event-log record spanning admission -> worker
    A execution -> loss -> redrive -> worker B completion, renderable
    by the profile report."""
    import glob

    from spark_rapids_tpu.obs.profile import QueryProfile
    from spark_rapids_tpu.obs.tracer import read_event_log
    log_dir = tmp_path / "events"
    dump_dir = tmp_path / "dumps"
    tbl = _serving_tbl()
    s = TpuSession({"spark.rapids.tpu.test.faults": "worker:kill:nth=1",
                    "spark.rapids.tpu.coredump.path": str(dump_dir),
                    "spark.rapids.tpu.eventLog.dir": str(log_dir)})
    try:
        rt = s.serving(dict(MP_POOL))
        ses = rt.tenant("bi")
        expected = _rows(_serving_query(s, tbl).collect())
        tk = ses.submit(_serving_query(s, tbl))
        assert _rows(tk.result(timeout=240)) == expected
        assert tk.redrives == 1
        assert rt.stats()["pool"]["restarts"].get("crash") == 1
        # (a) the black box: the victim could not write its own dump —
        # the supervisor wrote it from heartbeat-carried state
        dumps = glob.glob(str(dump_dir / "tpu-workerlost-*.json"))
        assert len(dumps) == 1
        bb = json.load(open(dumps[0]))
        assert bb["type"] == "worker_lost"
        assert bb["reason"] == "crash"
        assert bb["supervisor_pid"] == os.getpid()
        assert isinstance(bb["flight_recorder"], list)
        # the dispatch instant rides the `started` frame, so even a
        # worker killed milliseconds into its FIRST query leaves a
        # snapshot naming the query it died on
        assert any(e.get("name") == "serving_dispatch"
                   and (e.get("attrs") or {}).get("qid") == tk.id
                   for e in bb["flight_recorder"])
        infl = bb["inflight_tickets"]
        assert len(infl) == 1
        assert infl[0]["qid"] == tk.id
        assert infl[0]["tenant"] == "bi"
        assert infl[0]["started"] is True     # killed MID-query
        # (b) ONE stitched record keyed by the global ticket id
        stitched = []
        for p in sorted(glob.glob(str(log_dir / "*.jsonl"))):
            try:
                log = read_event_log(p)
            except Exception:                    # noqa: BLE001
                continue
            if (log.meta or {}).get("stitched"):
                stitched.append((p, log))
        assert len(stitched) == 1
        path, log = stitched[0]
        assert f"query_{tk.id}" in os.path.basename(path)
        assert log.meta["status"] == "ok"
        assert log.meta["redrives"] == 1
        execs = sorted([sp for sp in log.spans if sp.cat == "execute"],
                       key=lambda sp: sp.t0)
        assert len(execs) == 2                   # attempt 0 + redrive
        w_lost = execs[0].attrs["worker"]
        w_done = execs[1].attrs["worker"]
        assert w_lost != w_done                  # two distinct workers
        assert execs[0].attrs["lost"] == "crash"
        assert "lost" not in execs[1].attrs
        assert log.meta["workers"] == [w_lost, w_done]
        assert log.meta["worker"] == w_done
        losses = [e for e in log.events if e.name == "worker_lost"]
        assert len(losses) == 1
        assert losses[0].attrs["worker"] == w_lost
        names = {sp.name for sp in log.spans}
        assert {"admission", "grant", "query"} <= names
        # and the offline report renders the redrive chain
        text = QueryProfile.from_event_log(path).render()
        assert "stitched serving record" in text
        assert "LOST (crash) -> redrive" in text
        assert f"execute@{w_done}" in text
    finally:
        s.close()
