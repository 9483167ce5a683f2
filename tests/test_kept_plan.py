"""An unchanged DataFrame keeps its physical plan (ISSUE 33): a second
`collect()` runs through the `PhysicalQuery` of the first, and with it
through the compiled plan object and its programs, when nothing that
planning read has changed; it plans anew, as every collect did, where
something may have.  The program is launched and its answer fetched in
every collect.  CPU backend, wholePlan=ON (the chip's engine)."""
import gc
import threading
import weakref

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec import compiled as C
from spark_rapids_tpu.plan.aggregates import Count, Sum
from spark_rapids_tpu.plan.overrides import PhysicalQuery
from spark_rapids_tpu.session import TpuSession, col, lit

WHOLE = {"spark.rapids.tpu.sql.compile.wholePlan": "ON",
         "spark.rapids.tpu.sql.compile.seamSplitMinRows": "1"}
SEAM_MIN = "spark.rapids.tpu.sql.compile.seamSplitMinRows"


def _tables(n=4000, seed=7):
    rng = np.random.default_rng(seed)
    return (pa.table({"k": pa.array(rng.integers(0, 8, n), pa.int64()),
                      "v": pa.array(rng.standard_normal(n))}),
            pa.table({"k2": pa.array(np.arange(8), pa.int64()),
                      "w": pa.array(rng.standard_normal(8))}))


def _q6(s, tables):
    """One fused program, no seam."""
    return (s.from_arrow(tables[0]).filter(col("v") > lit(0.0))
            .agg((Sum(col("v")), "sv")))


def _seams(s, tables):
    """Three programs: the join, the aggregate, the sort."""
    return (s.from_arrow(tables[0])
            .join(s.from_arrow(tables[1]), left_on=["k"], right_on=["k2"])
            .group_by("k").agg((Sum(col("w")), "sw"), (Count(None), "c"))
            .sort(col("k")))


SHAPES = {"q6": _q6, "seams": _seams}
COUNTERS = ("whole_plan_compiled_queries", "exec_dispatches", "host_syncs",
            "overhead.seam_count", "whole_plan_split_queries")


def _counts(df):
    m = df.metrics()
    return {k: m.get(k, 0) for k in COUNTERS}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_second_collect_runs_through_the_kept_plan(shape, monkeypatch):
    s, tables = TpuSession(WHOLE), _tables()
    df = SHAPES[shape](s, tables)
    first = df.collect()
    assert "plan.reused" not in df.metrics()
    # the bypassing path, held to what it read before DataFrames kept
    # plans: a DataFrame built again plans anew and adopts the programs
    rebuilt = SHAPES[shape](s, tables)
    rebuilt.collect()
    warm = _counts(rebuilt)
    assert "plan.reused" not in rebuilt.metrics()
    assert rebuilt.metrics()["whole_plan_structure_hits"] == \
        warm["exec_dispatches"]

    made = []
    real_build, real_init = C.build_plan, C.CompiledPlan.__init__
    monkeypatch.setattr(C, "build_plan",
                        lambda *a, **kw: made.append("plan") or
                        real_build(*a, **kw))
    monkeypatch.setattr(C.CompiledPlan, "__init__",
                        lambda self, *a, **kw: made.append("program") or
                        real_init(self, *a, **kw))
    for _ in range(2):
        again = df.collect()
        m = df.metrics()
        assert m["plan.reused"] == 1
        assert again.equals(first)
        assert _counts(df) == warm
        assert warm["whole_plan_compiled_queries"] == 1
        # no plan object, no key, no lookup: nothing was prepared
        assert made == []
        assert "whole_plan_structure_hits" not in m
        if shape == "q6":
            assert "overhead.prepare_ms" not in m
        # `tpu.plan` still opens around the check, and the keys add up
        assert 0.0 < m["overhead.plan_ms"]
        assert sum(v for k, v in m.items() if k.startswith("overhead.")
                   and k.endswith("_ms") and k not in (
                       "overhead.collect_ms", "overhead.offcpu_ms",
                       "overhead.seam_wait_ms", "overhead.seam_resolve_ms",
                       "overhead.launch_inputs_ms", "overhead.enqueue_ms",
                       "overhead.launch_outputs_ms",
                       "overhead.fetch_wait_ms",
                       "overhead.arrow_build_ms")) == \
            pytest.approx(m["overhead.collect_ms"], abs=1e-6)


def test_physical_still_hands_out_a_fresh_plan():
    df = _q6(TpuSession(WHOLE), _tables())
    df.collect()
    kept = df._kept[1]
    assert isinstance(kept, PhysicalQuery)
    assert df.physical() is not kept and df.physical() is not df.physical()
    df.collect()
    assert df._kept[1] is kept


def test_set_conf_between_two_collects_plans_anew():
    s, tables = TpuSession(WHOLE), _tables()
    df = _seams(s, tables)
    first = df.collect()
    df.collect()
    assert df.metrics()["plan.reused"] == 1
    assert df.metrics()["overhead.seam_count"] == 2
    s.set_conf(SEAM_MIN, str(1 << 30))          # no plan this small splits
    out = df.collect()
    m = df.metrics()
    assert "plan.reused" not in m and "overhead.seam_count" not in m
    assert m["exec_dispatches"] == 1 and out.equals(first)
    # and the plan made under the new conf is kept in its turn
    df.collect()
    m = df.metrics()
    assert m["plan.reused"] == 1 and m["exec_dispatches"] == 1


@pytest.mark.parametrize("leaf", ["read_parquet", "logical_cache"])
def test_a_leaf_that_reads_the_world_plans_anew(leaf, tmp_path):
    import pyarrow.parquet as pq
    s, tables = TpuSession(WHOLE), _tables()
    if leaf == "read_parquet":
        path = str(tmp_path / "t.parquet")
        pq.write_table(tables[0], path)
        src = s.read_parquet(path)
    else:
        src = s.from_arrow(tables[0]).cache()
    df = src.filter(col("v") > lit(0.0)).agg((Sum(col("v")), "sv"))
    outs = []
    for _ in range(3):
        outs.append(df.collect())
        assert "plan.reused" not in df.metrics()
        assert df._kept is None
    # a whole-plan program did answer: it is the leaf that decides
    assert df.metrics()["whole_plan_compiled_queries"] == 1
    assert outs[0].equals(outs[2])


def test_a_collect_answered_by_the_eager_engine_keeps_no_plan():
    """A join whose build side repeats its keys asks the host for a row
    count under trace: the eager engine answers, and keeps state on the
    plan's nodes that a plan made anew starts without."""
    s = TpuSession(WHOLE)
    left = pa.table({"k": np.arange(1000) % 10, "v": np.arange(1000.0)})
    right = pa.table({"k": np.arange(100) % 10, "w": np.arange(100.0)})
    df = (s.from_arrow(left).join(s.from_arrow(right), on="k")
          .agg((Sum(col("w")), "sw")))
    for _ in range(2):
        out = df.collect()
        m = df.metrics()
        assert m["whole_plan_fallbacks"] == 1
        assert "plan.reused" not in m and df._kept is None
        assert out.column("sw").to_pylist() == [495000.0]   # 100 x 4,950


def test_an_armed_fault_site_keeps_no_plan():
    s = TpuSession({**WHOLE,
                    "spark.rapids.tpu.test.faults": "d2h:ioerror:nth=100"})
    df = _q6(s, _tables())
    for _ in range(2):
        df.collect()
        assert df.metrics()["whole_plan_compiled_queries"] == 1
        assert "plan.reused" not in df.metrics() and df._kept is None


def test_two_threads_on_one_dataframe_both_answer_and_one_reuses(
        monkeypatch):
    """Both collects are inside `PhysicalQuery.collect` at once (the
    barrier): the one that found the kept plan free runs through it, the
    other plans anew and leaves the kept plan alone."""
    s, tables = TpuSession(WHOLE), _tables()
    df = _seams(s, tables)
    want = df.collect()
    kept = df._kept[1]
    barrier = threading.Barrier(2)
    real, seen, outs = PhysicalQuery.collect, [], []

    def meet(self, ctx=None):
        seen.append((self, ctx))
        barrier.wait(timeout=60)
        return real(self, ctx)
    monkeypatch.setattr(PhysicalQuery, "collect", meet)
    threads = [threading.Thread(target=lambda: outs.append(df.collect()))
               for _ in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert len(outs) == 2 and all(o.equals(want) for o in outs)
    reused = [ctx.metrics.get("plan.reused", 0) for _q, ctx in seen]
    assert sorted(reused) == [0, 1]
    assert [q is kept for q, _ctx in seen].count(True) == 1
    assert df._kept[1] is kept
    monkeypatch.setattr(PhysicalQuery, "collect", real)
    assert df.collect().equals(want) and df.metrics()["plan.reused"] == 1


def test_many_threads_never_share_a_plan(monkeypatch):
    """More collecting threads than cores on one DataFrame, the
    interpreter switching every few bytecodes: no `PhysicalQuery` is
    ever inside two collects at once (a split plan's tree is swapped
    while it runs), and every answer is the first one's."""
    import os
    import sys
    df = _seams(TpuSession(WHOLE), _tables())
    want = df.collect()
    real, inside, guard = PhysicalQuery.collect, {}, threading.Lock()
    shared, reused, wrong = [], [], []

    def watched(self, ctx=None):
        with guard:
            inside[id(self)] = inside.get(id(self), 0) + 1
            if inside[id(self)] > 1:
                shared.append(id(self))
        try:
            return real(self, ctx)
        finally:
            with guard:
                inside[id(self)] -= 1
            reused.append(ctx.metrics.get("plan.reused", 0))
    monkeypatch.setattr(PhysicalQuery, "collect", watched)

    def client():
        for _ in range(4):
            if not df.collect().equals(want):
                wrong.append(1)
    threads = [threading.Thread(target=client)
               for _ in range(2 * (os.cpu_count() or 4))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert shared == [] and wrong == []
    assert len(reused) == 4 * len(threads) and 0 < sum(reused)


def test_a_kept_plan_holds_no_seam_output():
    df = _seams(TpuSession(WHOLE), _tables())
    for _ in range(2):
        df.collect()
        plan = df._kept[1]._compiled_plan
        assert isinstance(plan, C.SplitCompiledPlan) and len(plan.leaves) == 2
        assert all(leaf.batches == [] for leaf in plan.leaves)
        assert all(n._device_cache is None
                   for n in C._find_scans(df._kept[1].root))


def test_a_kept_plan_leaves_the_upload_cache_its_say():
    """Two tables, an upload cache with room for one: the older table's
    device copy goes when the newer one arrives, although the DataFrame
    over it, and its kept plan, are alive; collected again, it uploads
    again, and the answer is the same."""
    C._SCAN_UPLOAD_CACHE.clear()
    s = TpuSession({**WHOLE, "spark.rapids.tpu.sql.scan.uploadCacheBytes":
                    str(32 * 1024)})
    older = pa.table({"v": np.arange(2000, dtype=np.float64)})
    newer = pa.table({"v": np.arange(2000, dtype=np.float64) + 1.0})
    df = s.from_arrow(older).agg((Sum(col("v")), "sv"))
    first = df.collect()
    df.collect()
    assert df.metrics()["plan.reused"] == 1
    (key, entry), = C._SCAN_UPLOAD_CACHE.items()
    lane = weakref.ref(entry[1][0].columns[0].data)
    del entry
    s.from_arrow(newer).agg((Sum(col("v")), "sv")).collect()
    gc.collect()
    assert key not in C._SCAN_UPLOAD_CACHE
    assert lane() is None, "the kept plan pinned the evicted upload"
    assert df.collect().equals(first) and df.metrics()["plan.reused"] == 1
    assert key in C._SCAN_UPLOAD_CACHE


def test_a_reused_plan_replays_no_planning_phase(tmp_path):
    """The planner's phases belong to the collect that planned: the
    event log of a collect through the kept plan holds `tpu.plan`, and
    under it nothing."""
    from spark_rapids_tpu.obs.tracer import read_event_log
    s = TpuSession({**WHOLE, "spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    df = _q6(s, _tables())
    phases = []
    for _ in range(2):
        df.collect()
        log = read_event_log(df.metrics()["event_log_files"]["jsonl"])
        assert [sp.name for sp in log.spans if sp.name == "tpu.plan"] == \
            ["tpu.plan"]
        whole = [sp for sp in log.spans if sp.name == "tpu.collect"][0]
        mine = [sp for sp in log.spans if sp.cat == "plan"]
        assert all(whole.t0 <= sp.t0 and sp.t1 <= whole.t1 for sp in mine)
        phases.append(sorted(sp.name for sp in mine))
    assert phases == [["plan.convert", "plan.rewrite", "plan.wrap_tag"], []]
    assert log.metrics["plan.reused"] == 1


@pytest.mark.parametrize("why", ["in_memory", "read_parquet", "fault_armed"])
def test_the_planner_says_once_whether_a_plan_may_be_kept(why, tmp_path,
                                                          monkeypatch):
    """What does not change between collects is decided when the plan is
    made (`PhysicalQuery.keepable`, every caller of `apply_overrides`
    reads it); a collect through a kept plan walks no logical plan and
    asks for no fault injector."""
    import pyarrow.parquet as pq
    from spark_rapids_tpu.plan import overrides as O
    conf = dict(WHOLE)
    if why == "fault_armed":
        conf["spark.rapids.tpu.test.faults"] = "d2h:ioerror:nth=100"
    s, tables = TpuSession(conf), _tables()
    if why == "read_parquet":
        path = str(tmp_path / "t.parquet")
        pq.write_table(tables[0], path)
        src = s.read_parquet(path)
    else:
        src = s.from_arrow(tables[0])
    df = src.filter(col("v") > lit(0.0)).agg((Sum(col("v")), "sv"))
    assert df.physical().keepable == (why == "in_memory")
    df.collect()
    assert (df._kept is not None) == (why == "in_memory")
    if why == "in_memory":
        monkeypatch.setattr(O, "_walk", None)      # a walk would raise
        df.collect()
        assert df.metrics()["plan.reused"] == 1


def test_a_kept_plan_sums_its_scans_host_bytes_once():
    """A kept plan's scan asks Arrow for its batches' sizes once, for
    the `h2d_bytes` every collect counts, not at every collect (0.2 ms
    at 15 batches of 7 columns, in `tpu.launch`)."""
    from spark_rapids_tpu.exec.plan import HostScanExec
    s, tables = TpuSession(WHOLE), _tables()
    df = _q6(s, tables)
    df.collect()
    scan, = [n for n in C._find_scans(df._kept[1].root)
             if isinstance(n, HostScanExec)]
    want = sum(hb.rb.nbytes for hb in scan.batches)
    assert scan.host_nbytes() == want > 0
    scan.batches = None                   # summing again would raise
    df.collect()
    assert df.metrics()["plan.reused"] == 1
    assert scan.host_nbytes() == want
