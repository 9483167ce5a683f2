"""Concurrent serving plane: admission, fair share, the plan+result
cache, conf snapshots and concurrent event logs (serving/runtime.py,
serving/cache.py — docs/SERVING.md).
"""
import gc
import glob
import threading
import time

import pyarrow as pa
import pytest

from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs.registry import (SERVING_RESULT_CACHE,
                                           SERVING_TENANT_DEVICE_US)
from spark_rapids_tpu.plan.aggregates import Count, Sum
from spark_rapids_tpu.serving import AdmissionTimeout
from spark_rapids_tpu.session import TpuSession, col, lit

WHOLE_PLAN = {"spark.rapids.tpu.sql.compile.wholePlan": "ON"}


def _table(n=600, seed=0):
    return pa.table({"k": [(i + seed) % 7 for i in range(n)],
                     "x": [float(i % 101) for i in range(n)],
                     "y": list(range(n))})


def _query(session, table, cut=10):
    return (session.from_arrow(table)
            .filter(col("y") > lit(cut))
            .group_by("k").agg((Sum(col("x")), "sx"),
                               (Count(None), "ct")))


def _outcome(name):
    return SERVING_RESULT_CACHE.value(outcome=name) or 0


def _rows(table):
    """Order-insensitive row multiset (group-by output order differs
    between the device and host engines)."""
    d = table.to_pydict()
    names = sorted(d)
    return sorted(zip(*(d[n] for n in names)))


# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------

def test_submit_collect_matches_plain():
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        t = _table()
        df = _query(s, t)
        expected = df.collect()
        rt = s.serving()
        got = rt.tenant("a").collect(df)
        assert got.to_pydict() == expected.to_pydict()
        st = rt.stats()
        assert st["completed"] == 1 and st["inflight"] == 0
        assert st["tenants"]["a"]["queries"] == 1
    finally:
        s.close()


def test_result_cache_hit_bit_identical():
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        t = _table()
        df = _query(s, t)
        rt = s.serving()
        a = rt.tenant("a")
        h0, s0 = _outcome("hit"), _outcome("store")
        cold = a.collect(df)
        warm = a.collect(df)
        assert _outcome("store") - s0 >= 1
        assert _outcome("hit") - h0 >= 1
        # bit-identical: the IPC round trip preserves exact bytes
        assert warm.equals(cold.select(warm.column_names)) or \
            warm.to_pydict() == cold.to_pydict()
        assert warm.schema == cold.schema
    finally:
        s.close()


def test_result_cache_literal_variants_no_false_sharing():
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        t = _table()
        rt = s.serving()
        a = rt.tenant("a")
        r10 = a.collect(_query(s, t, cut=10))
        r50 = a.collect(_query(s, t, cut=50))
        assert r10.to_pydict() == _query(s, t, cut=10).collect().to_pydict()
        assert r50.to_pydict() == _query(s, t, cut=50).collect().to_pydict()
        assert r10.to_pydict() != r50.to_pydict()
        # and each repeat still hits its OWN entry
        assert a.collect(_query(s, t, cut=10)).to_pydict() == \
            r10.to_pydict()
    finally:
        s.close()


def test_result_cache_invalidated_when_table_dies():
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        rt = s.serving()
        a = rt.tenant("a")
        i0 = _outcome("invalidate")
        t2 = _table(seed=3)
        tk = a.submit(_query(s, t2))
        tk.result()
        assert len(rt.cache) >= 1
        before = len(rt.cache)
        del tk, t2
        gc.collect()
        assert len(rt.cache) == before - 1
        assert _outcome("invalidate") - i0 >= 1
    finally:
        s.close()


def test_result_cache_byte_cap_evicts_lru():
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        rt = s.serving(
            {"spark.rapids.tpu.serving.resultCache.bytes": "900"})
        a = rt.tenant("a")
        e0 = _outcome("evict")
        t = _table()
        a.collect(_query(s, t, cut=10))
        a.collect(_query(s, t, cut=50))
        a.collect(_query(s, t, cut=90))
        assert _outcome("evict") - e0 >= 1
        assert rt.cache.stats()["bytes"] <= 900
    finally:
        s.close()


def test_result_cache_disabled_bypasses():
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        rt = s.serving(
            {"spark.rapids.tpu.serving.resultCache.bytes": "0"})
        a = rt.tenant("a")
        t = _table()
        tk = a.submit(_query(s, t))
        tk.result()
        assert tk.cache == "bypass"
        assert len(rt.cache) == 0
    finally:
        s.close()


# ---------------------------------------------------------------------------
# admission / backpressure
# ---------------------------------------------------------------------------

def test_admission_backpressure_times_out():
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        rt = s.serving({
            "spark.rapids.tpu.serving.queueDepth": "1",
            "spark.rapids.tpu.serving.admitTimeoutMs": "120",
            "spark.rapids.tpu.serving.workers": "1",
            "spark.rapids.tpu.serving.resultCache.bytes": "0"})
        a = rt.tenant("a")
        slow = s.from_arrow(_table(64)).map_in_pandas(
            lambda it: (_sleep_frame(f) for f in it),
            pa.schema([("k", pa.int64()), ("x", pa.float64()),
                       ("y", pa.int64())]))
        tk = a.submit(slow)                      # fills the queue
        with pytest.raises(AdmissionTimeout):
            a.submit(_query(s, _table()))
        tk.result()                              # drains
        # and a post-drain submit admits instantly again
        got = a.collect(_query(s, _table()))
        assert got.num_rows > 0
        assert rt.stats()["admission_timeouts"] == 1
    finally:
        s.close()


def _sleep_frame(f):
    time.sleep(1.0)
    return f


# ---------------------------------------------------------------------------
# conf snapshot at admission (satellite: set_conf vs in-flight queries)
# ---------------------------------------------------------------------------

def test_conf_snapshot_at_admission_beats_set_conf_race():
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        rt = s.serving({
            "spark.rapids.tpu.serving.workers": "1",
            "spark.rapids.tpu.serving.resultCache.bytes": "0"})
        a = rt.tenant("a")
        t = _table()
        expected = _rows(_query(s, t).collect())
        # occupy the single worker so tk1 PLANS after the conf flip
        slow = s.from_arrow(_table(64)).map_in_pandas(
            lambda it: (_sleep_frame(f) for f in it),
            pa.schema([("k", pa.int64()), ("x", pa.float64()),
                       ("y", pa.int64())]))
        tk0 = a.submit(slow)
        tk1 = a.submit(_query(s, t))     # snapshot taken HERE
        s.set_conf("spark.rapids.tpu.sql.enabled", "false")
        tk2 = a.submit(_query(s, t))     # admitted after the flip
        tk0.result()
        r1, r2 = tk1.result(), tk2.result()
        # tk1 planned AFTER the flip but was admitted before it: its
        # snapshot keeps the device plan; tk2 honors the new conf
        assert tk1.plan_kind == "device"
        assert tk2.plan_kind == "host"
        assert _rows(r1) == expected
        assert _rows(r2) == expected
    finally:
        s.set_conf("spark.rapids.tpu.sql.enabled", "true")
        s.close()


def test_set_conf_concurrent_flips_never_corrupt_results():
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        rt = s.serving(
            {"spark.rapids.tpu.serving.resultCache.bytes": "0"})
        a = rt.tenant("a")
        t = _table()
        expected = _rows(_query(s, t).collect())
        stop = threading.Event()

        def flipper():
            i = 0
            while not stop.is_set():
                s.set_conf("spark.rapids.tpu.sql.enabled",
                           "false" if i % 2 else "true")
                i += 1
                time.sleep(0.002)

        th = threading.Thread(target=flipper)
        th.start()
        try:
            tickets = [a.submit(_query(s, t)) for _ in range(12)]
            results = [tk.result() for tk in tickets]
        finally:
            stop.set()
            th.join()
        for r in results:
            assert _rows(r) == expected
    finally:
        s.set_conf("spark.rapids.tpu.sql.enabled", "true")
        s.close()


# ---------------------------------------------------------------------------
# event logs under concurrency (satellite: filename/id collisions)
# ---------------------------------------------------------------------------

def test_concurrent_event_logs_distinct_ids(tmp_path):
    s = TpuSession({**WHOLE_PLAN,
                    "spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    try:
        t = _table()
        dfs = [_query(s, t, cut=10), _query(s, t, cut=50)]
        errs = []
        barrier = threading.Barrier(2)

        def run(df):
            try:
                barrier.wait()          # same-instant starts
                df.collect()
            except Exception as e:      # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=run, args=(df,)) for df in dfs]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errs
        logs = sorted(glob.glob(str(tmp_path / "*.jsonl")))
        assert len(logs) == 2, logs
        from spark_rapids_tpu.obs.tracer import read_event_log
        parsed = [read_event_log(p) for p in logs]
        ids = [p.query_id for p in parsed]
        assert len(set(ids)) == 2       # process-unique, no collision
        for p in parsed:
            # each log is self-consistent: exactly one root query span,
            # its own metrics, no cross-contamination from the sibling
            roots = [sp for sp in p.spans if sp.cat == "query"]
            assert len(roots) == 1
            assert not p.truncated
    finally:
        s.close()


def test_event_log_write_never_overwrites(tmp_path):
    """Two processes (or a restart) sharing one log dir: same id twice
    must yield two files, not one overwritten file."""
    from spark_rapids_tpu.obs.tracer import QueryTracer, read_event_log
    tr = QueryTracer(7)
    with tr.span("query", "query"):
        pass
    p1 = tr.write(str(tmp_path))["jsonl"]
    p2 = tr.write(str(tmp_path))["jsonl"]
    assert p1 != p2
    assert read_event_log(p1).query_id == read_event_log(p2).query_id == 7


def test_query_ids_monotonic_across_threads(tmp_path):
    from spark_rapids_tpu.obs.tracer import make_tracer
    conf = TpuConf({"spark.rapids.tpu.trace.enabled": "true"})
    out = []
    lock = threading.Lock()

    def grab():
        for _ in range(50):
            tr = make_tracer(conf)
            with lock:
                out.append(tr.query_id)

    threads = [threading.Thread(target=grab) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(set(out)) == 200         # unique under contention
    assert max(out) - min(out) == 199   # and monotonic (no gaps/reuse)


# ---------------------------------------------------------------------------
# fair share: the 8-thread hammer
# ---------------------------------------------------------------------------

def test_fair_share_hammer_eight_threads():
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        rt = s.serving({
            "spark.rapids.tpu.serving.workers": "8",
            "spark.rapids.tpu.serving.resultCache.bytes": "0"})
        t = _table()
        tenants = ["bi", "etl", "adhoc", "batch"]
        weights = {"bi": 2.0, "etl": 1.0, "adhoc": 1.0, "batch": 0.5}
        handles = {n: rt.tenant(n, weight=weights[n]) for n in tenants}
        cuts = {"bi": 5, "etl": 25, "adhoc": 45, "batch": 65}
        expected = {n: _query(s, t, cut=cuts[n]).collect().to_pydict()
                    for n in tenants}
        d0 = {n: SERVING_TENANT_DEVICE_US.value(tenant=n) or 0
              for n in tenants}
        tickets = {n: [] for n in tenants}
        errs = []
        barrier = threading.Barrier(8)

        def client(name, reps=4):
            try:
                barrier.wait()
                for _ in range(reps):
                    tk = handles[name].submit(_query(s, t, cut=cuts[name]))
                    tk.result()
                    with lock:
                        tickets[name].append(tk)
            except Exception as e:       # noqa: BLE001
                errs.append(e)

        lock = threading.Lock()
        threads = [threading.Thread(target=client, args=(n,))
                   for n in tenants for _ in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errs, errs
        st = rt.stats()
        # (a) starvation bound: a runnable tenant is never passed over
        # more than starvationBound grants (+ one round when several hit
        # the bound together)
        bound = 4 + len(tenants)
        assert st["max_skips"] <= bound, st
        for name in tenants:
            for tk in tickets[name]:
                assert tk.skips <= bound
        # (b) per-tenant device time: registry total == per-ticket sum
        # EXACTLY (integer microseconds; publication order cannot
        # perturb an integer counter)
        for name in tenants:
            reg = (SERVING_TENANT_DEVICE_US.value(tenant=name) or 0) \
                - d0[name]
            assert reg == sum(tk.device_us for tk in tickets[name])
        # (c) zero cross-tenant result leakage: every ticket's rows are
        # its own tenant's query's rows
        for name in tenants:
            assert len(tickets[name]) == 8
            for tk in tickets[name]:
                assert tk.result().to_pydict() == expected[name]
        assert st["completed"] == 32
    finally:
        s.close()


def test_scheduler_prefers_least_weighted_vtime_and_starving():
    """White-box scheduler unit: min virtual time wins; a tenant past
    the starvation bound preempts everyone."""
    from spark_rapids_tpu.serving.runtime import QueryTicket, _TenantState
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        rt = s.serving({"spark.rapids.tpu.serving.workers": "1"})
        a, b = _TenantState("a", 1.0), _TenantState("b", 1.0)
        rt._tenants = {"a": a, "b": b}
        ta = QueryTicket(None, s.conf, "a")
        tb = QueryTicket(None, s.conf, "b")
        ta._grant_est = tb._grant_est = 0
        a.vtime_us, b.vtime_us = 100.0, 50.0
        a.queue, b.queue = [ta], [tb]
        with rt._cond:
            assert not rt._try_grant(ta)     # b has less virtual time
            assert rt._try_grant(tb)
            rt._device_active = 0
            # starving a overrides b's lower vtime
            b.queue = [tb]
            a.skips = rt._starvation_bound
            b.vtime_us = 0.0
            assert not rt._try_grant(tb)
            assert rt._try_grant(ta)
            assert ta.skips == rt._starvation_bound
            rt._device_active = 0
    finally:
        s.close()


# ---------------------------------------------------------------------------
# phase overlap
# ---------------------------------------------------------------------------

def test_phases_overlap_across_queries():
    """The structural overlap proof: with several workers, some query's
    host phase (plan/compile/upload) runs while ANOTHER query holds the
    device — the device-never-idles-while-compiling property the
    serving plane exists for."""
    s = TpuSession(dict(WHOLE_PLAN))
    try:
        rt = s.serving({
            "spark.rapids.tpu.serving.workers": "4",
            "spark.rapids.tpu.serving.resultCache.bytes": "0"})
        a = rt.tenant("a")
        t = _table(2000)
        # distinct plan STRUCTURES so each pays its own plan+compile
        dfs = [
            _query(s, t, cut=10),
            s.from_arrow(t).filter(col("x") > lit(1.0))
             .group_by("k").agg((Count(None), "n")),
            s.from_arrow(t).join(s.from_arrow(_table(50, seed=1)),
                                 on="k").group_by("k")
             .agg((Sum(col("x")), "sx")),
            s.from_arrow(t).sort(col("y")).limit(17),
        ] * 2
        tickets = [a.submit(df) for df in dfs]
        for tk in tickets:
            tk.result()
        assert rt.stats()["overlap_observed"], rt.stats()
    finally:
        s.close()


def test_check_regression_gates_sv_entries(tmp_path):
    """scripts/check_regression.py mines `serving_latency_ms` into
    sv:-prefixed entries and fails on a 2x p99 regression, under the
    same backend-separation rule as qN / mc: timings."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "scripts", "check_regression.py")
    base = {"backend": "cpu",
            "serving_latency_ms": {"c8_p99": 1000.0, "c8_mean": 400.0}}
    good = {"backend": "cpu",
            "serving_latency_ms": {"c8_p99": 1050.0, "c8_mean": 380.0}}
    bad = {"backend": "cpu",
           "serving_latency_ms": {"c8_p99": 2000.0, "c8_mean": 900.0}}
    other_hw = {"backend": "tpu",
                "serving_latency_ms": {"c8_p99": 2000.0,
                                       "c8_mean": 900.0}}
    paths = {}
    for name, doc in (("base", base), ("good", good), ("bad", bad),
                      ("other", other_hw)):
        p = tmp_path / f"{name}.json"
        p.write_text(json.dumps(doc))
        paths[name] = str(p)

    def gate(current, trajectory):
        return subprocess.run(
            [sys.executable, script, "--current", current, *trajectory],
            capture_output=True, text=True)

    r = gate(paths["good"], [paths["base"]])
    assert r.returncode == 0, r.stdout + r.stderr
    r = gate(paths["bad"], [paths["base"]])
    assert r.returncode == 1
    assert "sv:c8_p99" in r.stdout
    # backend separation: a tpu-tagged 2x result never gates against
    # the cpu baseline
    r = gate(paths["other"], [paths["base"]])
    assert r.returncode == 2 or "skipping" in r.stdout + r.stderr


def test_oversized_query_admitted_ooc_does_not_serialize_queue():
    """ISSUE 15 serving regression: a query whose working-set estimate
    exceeds the HBM budget used to run SOLO — while it executed,
    `_device_bytes` sat above the limit and every small tenant waited.
    Now it is admitted in OUT-OF-CORE mode: the grant is sized to the
    OOC resident window, the query executes with the OOC tier forced
    (spilling, not betting on the OOM ladder), and small-tenant queries
    keep overlapping its execute phase with bounded latency."""
    import numpy as np
    rng = np.random.default_rng(47)
    n = 300_000
    big_tbl = pa.table({"k": pa.array(rng.integers(0, 20_000, n),
                                      pa.int64()),
                        "x": pa.array(rng.standard_normal(n)),
                        "y": pa.array(np.arange(n))})
    small_tbl = _table(400)
    s = TpuSession({"spark.rapids.tpu.memory.tpu.budgetBytes":
                        str(1 << 20)})
    try:
        rt = s.serving({
            "spark.rapids.tpu.serving.workers": "6",
            "spark.rapids.tpu.serving.deviceSlots": "4",
            "spark.rapids.tpu.serving.resultCache.bytes": "0"})
        big = rt.tenant("big")
        small = rt.tenant("small")
        big_df = _query(s, big_tbl)
        small_df = _query(s, small_tbl)
        expected_big = _rows(_query(s, big_tbl).collect())
        expected_small = _rows(small_df.collect())

        t0 = time.perf_counter()
        tk_big = big.submit(big_df)
        # wait until the big query actually holds a device grant
        deadline = time.perf_counter() + 60
        while rt._device_active == 0 and not tk_big.done() and \
                time.perf_counter() < deadline:
            time.sleep(0.005)
        small_lat = []
        lock = threading.Lock()

        def client():
            c0 = time.perf_counter()
            out = small.collect(_query(s, small_tbl))
            with lock:
                small_lat.append(time.perf_counter() - c0)
                assert _rows(out) == expected_small

        threads = [threading.Thread(target=client) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert _rows(tk_big.result(300)) == expected_big
        big_wall = time.perf_counter() - t0

        # admitted OOC, grant capped to the resident window
        assert tk_big.ooc is True
        assert tk_big._grant_est <= (1 << 20) // 2
        st = rt.stats()
        assert st["ooc_admissions"] == 1
        # NOT serialized: at least one small execute interval overlaps
        # the big query's execute interval
        with rt._cond:
            intervals = list(rt._intervals)
        big_exec = [iv for iv in intervals
                    if iv[0] == "execute" and iv[1] == tk_big.id]
        small_exec = [iv for iv in intervals
                      if iv[0] == "execute" and iv[1] != tk_big.id]
        assert big_exec and small_exec
        e0, e1 = big_exec[0][2], big_exec[0][3]
        assert any(t0_ < e1 and e0 < t1_
                   for _, _, t0_, t1_ in small_exec), \
            "small tenants serialized behind the oversized query"
        # small-tenant latency bounded while the big query spills
        assert max(small_lat) < big_wall
    finally:
        s.close()


def test_hbm_admission_gates_device_overlap():
    """With a tiny HBM budget, working-set estimates serialize device
    phases instead of overlapping them — and everything still
    completes correctly (queue, don't OOM)."""
    s = TpuSession({**WHOLE_PLAN,
                    "spark.rapids.tpu.memory.tpu.budgetBytes":
                        str(1 << 30)})
    try:
        rt = s.serving({
            "spark.rapids.tpu.serving.workers": "4",
            "spark.rapids.tpu.serving.deviceSlots": "2",
            "spark.rapids.tpu.serving.resultCache.bytes": "0"})
        assert rt._hbm_limit == (1 << 30)
        a = rt.tenant("a")
        t = _table()
        expected = _query(s, t).collect().to_pydict()
        tickets = [a.submit(_query(s, t)) for _ in range(6)]
        for tk in tickets:
            assert tk.result().to_pydict() == expected
    finally:
        s.close()


# ---------------------------------------------------------------------------
# fault-isolated multi-process pool (serving/workers.py)
# ---------------------------------------------------------------------------

MP_FAST = {
    # fast worker health detection keeps pool tests inside the tier-1
    # wall budget without weakening what they prove
    "spark.rapids.tpu.serving.pool.heartbeatMs": "100",
    "spark.rapids.tpu.serving.pool.heartbeatMisses": "6",
}


def test_pool_refuses_a_tpu_at_once_and_by_name(monkeypatch):
    """A chip belongs to one process and the supervisor holds it: on a
    TPU the pool raises at serving() — naming the cause — instead of
    spawning workers that cannot open the device and waiting on them."""
    import jax
    import spark_rapids_tpu.serving.workers as W
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        W.WorkerPool, "start",
        lambda self, timeout=0: pytest.fail("the pool was started"))
    s = TpuSession()
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="holds the chip") as ei:
        s.serving({"spark.rapids.tpu.serving.pool.processes": "2"})
    assert time.perf_counter() - t0 < 5.0
    assert "serving.pool.processes=2" in str(ei.value)
    assert s._serving is None
    # in-process serving is what a TPU host runs; it still comes up
    rt = s.serving()
    assert rt.stats()["device_slots"] >= 1
    s.close()


def test_pool_mode_matches_plain_and_isolates_sessions():
    """MULTI-PROCESS serving: queries execute in supervised worker
    processes (each its own TpuSession/budget) and match the in-process
    oracle bit-for-bit; the pool's stats and heartbeat-fed census show
    every live worker."""
    s = TpuSession({})
    try:
        rt = s.serving({"spark.rapids.tpu.serving.pool.processes": "2",
                        **MP_FAST})
        a, b = rt.tenant("a"), rt.tenant("b")
        t = _table()
        expected = _rows(_query(s, t).collect())
        tickets = [ses.submit(_query(s, t)) for ses in (a, b, a, b)]
        for tk in tickets:
            assert _rows(tk.result(timeout=240)) == expected
            assert tk.worker is not None       # answered by a pool worker
            assert tk.redrives == 0
        st = rt.stats()
        assert st["pool"]["live"] == 2
        assert st["pool"]["redrives"] == 0
        assert set(st["census"]["workers"]) == set(st["pool"]["workers"])
        # supervisor-side worker pids are real child processes
        for w in st["pool"]["workers"].values():
            assert isinstance(w["pid"], int) and w["pid"] > 0
    finally:
        s.close()


def test_pool_drain_empty_queue_no_orphans():
    """Graceful drain: admission closes (submit raises), in-flight
    queries finish, workers checkpoint + exit — and NO worker process
    survives the drain."""
    import os as _os
    s = TpuSession({})
    try:
        rt = s.serving({"spark.rapids.tpu.serving.pool.processes": "2",
                        **MP_FAST})
        ses = rt.tenant("a")
        t = _table()
        expected = _rows(_query(s, t).collect())
        tk = ses.submit(_query(s, t))
        pids = [w["pid"] for w in rt.stats()["pool"]["workers"].values()]
        assert len(pids) == 2
        assert _rows(tk.result(timeout=240)) == expected
        rt.drain()
        with pytest.raises(RuntimeError):
            ses.submit(_query(s, t))
        assert rt.stats()["inflight"] == 0
        orphans = []
        for pid in pids:
            try:
                _os.kill(pid, 0)
                orphans.append(pid)
            except ProcessLookupError:
                pass
        assert not orphans, f"workers survived drain: {orphans}"
    finally:
        s._serving = None      # drained above; close() must not re-drain
        s.close()


def test_deadline_expired_releases_reservation_and_keeps_serving():
    """A query whose wall-clock deadline expires cancels COOPERATIVELY
    at the next checkpoint bracket, releases its full device
    reservation (zero residual in the DeviceCensus and the admission
    ledger), and the runtime keeps serving."""
    from spark_rapids_tpu.exec.plan import QueryDeadlineExceeded
    from spark_rapids_tpu.obs.memattr import CENSUS
    s = TpuSession(dict(WHOLE_PLAN))
    # CENSUS is process-wide: other tests' not-yet-collected budgets can
    # hold bytes, so assert zero RESIDUAL GROWTH, not an absolute zero
    import gc
    gc.collect()
    base_live = CENSUS.totals()["live_bytes"]
    try:
        rt = s.serving()
        ses = rt.tenant("a")
        t = _table()
        # an already-expired deadline: the FIRST checkpoint cancels
        tk = ses.submit(_query(s, t), deadline_ms=1e-6)
        with pytest.raises(QueryDeadlineExceeded):
            tk.result(timeout=120)
        st = rt.stats()
        assert st["deadline_cancellations"] == 1
        assert rt._device_bytes == 0       # admission ledger released
        gc.collect()
        assert CENSUS.totals()["live_bytes"] <= base_live
        # the runtime is unharmed: the next (undeadlined) query works
        expected = _rows(_query(s, t).collect())
        assert _rows(ses.collect(_query(s, t), timeout=120)) == expected
        assert rt.stats()["deadline_cancellations"] == 1
    finally:
        s.close()


def test_serving_deadline_conf_applies_to_every_query():
    """serving.deadlineMs sets the default per-query deadline; a
    per-submit deadline_ms overrides it."""
    from spark_rapids_tpu.exec.plan import QueryDeadlineExceeded
    s = TpuSession({})
    try:
        rt = s.serving({"spark.rapids.tpu.serving.deadlineMs": "0.000001"})
        ses = rt.tenant("a")
        t = _table()
        with pytest.raises(QueryDeadlineExceeded):
            ses.collect(_query(s, t), timeout=120)
        # override: a generous explicit deadline lets the query finish
        expected = _rows(_query(s, t).collect())
        out = ses.collect(_query(s, t), timeout=120, deadline_ms=600_000)
        assert _rows(out) == expected
    finally:
        s.close()


# ---------------------------------------------------------------------------
# fleet observability federation (PR 20): one metrics plane for the pool
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fleet_registry_federates_exactly_across_workers():
    """The federation EXACTNESS invariant: the same worker-measured
    device-us integer is published on both sides of the socket, so the
    fleet view's per-worker-labeled tenant counters sum EXACTLY to the
    supervisor's own per-tenant counter — no sampling, no drift."""
    s = TpuSession({})
    # unique tenant names: both registries are process-wide across the
    # pytest run, so the series must be ours alone
    tenants = ("fedx_alpha", "fedx_beta")
    try:
        rt = s.serving({"spark.rapids.tpu.serving.pool.processes": "2",
                        **MP_FAST})
        sessions = [rt.tenant(t) for t in tenants]
        t = _table()
        expected = _rows(_query(s, t).collect())
        tickets = [ses.submit(_query(s, t))
                   for _ in range(3) for ses in sessions]
        for tk in tickets:
            assert _rows(tk.result(timeout=240)) == expected

        def fleet_sums():
            fleet = rt.stats().get("fleet") or {}
            sums = {t: 0 for t in tenants}
            for k, v in fleet.items():
                if not k.startswith(
                        "tpu_fleet_serving_tenant_device_us_total{"):
                    continue
                for t_ in tenants:
                    if f"tenant={t_}" in k:
                        assert "worker=" in k
                        sums[t_] += int(v)
            return sums

        sup = {t_: int(SERVING_TENANT_DEVICE_US.value(tenant=t_) or 0)
               for t_ in tenants}
        assert all(v > 0 for v in sup.values())
        # convergence is one heartbeat away: poll BEFORE drain/close
        deadline = time.time() + 60
        while fleet_sums() != sup and time.time() < deadline:
            time.sleep(0.05)
        assert fleet_sums() == sup       # exactly, to the microsecond
    finally:
        s.close()


@pytest.mark.slow
def test_worker_restart_publishes_fresh_fleet_label_and_live_gauge():
    """A replaced worker federates under a FRESH worker label: the
    victim's gauge series drop with the process (its counters — work
    the fleet really did — stay), the replacement's series appear under
    the new id, and `tpu_serving_workers_live` stays truthful through
    the restart."""
    import os as _os
    import signal as _signal

    from spark_rapids_tpu.obs.registry import SERVING_WORKERS_LIVE
    s = TpuSession({})
    try:
        rt = s.serving({"spark.rapids.tpu.serving.pool.processes": "2",
                        **MP_FAST})
        ses = rt.tenant("fedr_tenant")
        t = _table()
        expected = _rows(_query(s, t).collect())
        assert _rows(ses.collect(_query(s, t), timeout=240)) == expected
        pool = rt.stats()["pool"]
        assert pool["live"] == 2
        assert SERVING_WORKERS_LIVE.value() == 2
        victim_wid, victim = sorted(pool["workers"].items())[0]
        _os.kill(victim["pid"], _signal.SIGKILL)
        # the supervisor notices (reader EOF), restarts, and the gauge
        # tracks the dip and the recovery truthfully
        deadline = time.time() + 60
        while time.time() < deadline:
            pool = rt.stats()["pool"]
            assert SERVING_WORKERS_LIVE.value() == pool["live"]
            if pool["live"] == 2 and victim_wid not in pool["workers"]:
                break
            time.sleep(0.02)
        pool = rt.stats()["pool"]
        assert pool["live"] == 2
        assert victim_wid not in pool["workers"]
        fresh = set(pool["workers"]) - {victim_wid}
        assert fresh
        assert SERVING_WORKERS_LIVE.value() == 2
        # hammer enough concurrent work that every live worker serves
        tickets = [ses.submit(_query(s, t)) for _ in range(8)]
        for tk in tickets:
            assert _rows(tk.result(timeout=240)) == expected
        # the replacement publishes under its own fresh label
        deadline = time.time() + 60
        while time.time() < deadline:
            fleet = rt.stats().get("fleet") or {}
            new_labels = {w for w in fresh
                          if any(f"worker={w}" in k for k in fleet)}
            if new_labels:
                break
            time.sleep(0.05)
        assert new_labels, "replacement worker never federated"
        # the victim's cumulative counters survive it; its gauges died
        fleet = rt.stats().get("fleet") or {}
        victim_keys = [k for k in fleet if f"worker={victim_wid}" in k]
        for k in victim_keys:
            assert not k.startswith("tpu_fleet_memory_"), \
                f"dead worker gauge survived: {k}"
    finally:
        s.close()


def test_check_regression_gates_fleet_skew_entries(tmp_path):
    """scripts/check_regression.py mines `serving_fleet` (per-mp-level
    worker utilization skew from the federated registry) into sv:-
    prefixed entries under the same backend-separation rules as the
    latency gates: a dispatch-imbalance regression fails the gate."""
    import json as _json
    import os as _os
    import subprocess
    import sys as _sys
    root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    script = _os.path.join(root, "scripts", "check_regression.py")
    base = {"backend": "cpu",
            "serving_latency_ms": {"c8_p99": 1000.0},
            "serving_fleet": {"mp2_skew": 1.2}}
    good = {"backend": "cpu",
            "serving_latency_ms": {"c8_p99": 1000.0},
            "serving_fleet": {"mp2_skew": 1.3}}
    bad = {"backend": "cpu",
           "serving_latency_ms": {"c8_p99": 1000.0},
           "serving_fleet": {"mp2_skew": 3.0}}
    fleet_only = {"backend": "cpu",
                  "serving_fleet": {"mp2_skew": 1.2}}
    other_hw = {"backend": "tpu",
                "serving_fleet": {"mp2_skew": 4.0}}
    paths = {}
    for name, doc in (("base", base), ("good", good), ("bad", bad),
                      ("fleet_only", fleet_only), ("other", other_hw)):
        p = tmp_path / f"{name}.json"
        p.write_text(_json.dumps(doc))
        paths[name] = str(p)

    def gate(current, trajectory):
        return subprocess.run(
            [_sys.executable, script, "--current", current, *trajectory],
            capture_output=True, text=True)

    r = gate(paths["good"], [paths["base"]])
    assert r.returncode == 0, r.stdout + r.stderr
    r = gate(paths["bad"], [paths["base"]])
    assert r.returncode == 1
    assert "sv:mp2_skew" in r.stdout
    # a record carrying ONLY the fleet dict still mines
    r = gate(paths["fleet_only"], [paths["base"]])
    assert r.returncode == 0, r.stdout + r.stderr
    # backend separation: tpu-tagged skew never gates vs a cpu baseline
    r = gate(paths["other"], [paths["base"]])
    assert r.returncode == 2 or "skipping" in r.stdout + r.stderr
