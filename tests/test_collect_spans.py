"""The span seam of the collect path (obs/tracer.CollectSpan, ISSUE 25):
one `tpu.<name>` span per layer boundary of DataFrame.collect(), always
on — a profiler annotation on the device's clock, one `ctx.metrics` key,
and the QueryTracer's record where tracing is enabled — and the plan-node
names that `jax.named_scope` puts on the ops of a whole-plan program.
CPU backend, wholePlan=ON (the chip's engine)."""
import glob
import importlib.util
import os
import re

import jax
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.plan.aggregates import Count, Sum
from spark_rapids_tpu.session import TpuSession, col, lit

WHOLE = {"spark.rapids.tpu.sql.compile.wholePlan": "ON",
         # the seam gate keeps a 4000-row plan in one program; the split
         # is what the second shape is here for
         "spark.rapids.tpu.sql.compile.seamSplitMinRows": "1"}


def _tbl(n=4000, seed=7):
    rng = np.random.default_rng(seed)
    return pa.table({"k": pa.array(rng.integers(0, 8, n), pa.int64()),
                     "v": pa.array(rng.standard_normal(n))})


def _tables(n=4000):
    rng = np.random.default_rng(11)
    return (_tbl(n), pa.table({"k2": pa.array(np.arange(8), pa.int64()),
                               "w": pa.array(rng.standard_normal(8))}))


def _seam_df(s, tables=None):
    """The two-seam shape of test_wall_breakdown.py: a sort over a join
    under an aggregate splits after the join and after the aggregate."""
    fact, dim = tables or _tables()
    return (s.from_arrow(fact)
            .join(s.from_arrow(dim), left_on=["k"], right_on=["k2"])
            .group_by("k").agg((Sum(col("w")), "sw"), (Count(None), "c"))
            .sort(col("k")))


def _q6_df(s, tables=None):
    """Filter under a global aggregate: one fused program, no seam."""
    fact, _dim = tables or _tables()
    return (s.from_arrow(fact).filter(col("v") > lit(0.0))
            .agg((Sum(col("v")), "sv")))


SHAPES = {"q6": _q6_df, "seams": _seam_df}

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _by_operator():
    spec = importlib.util.spec_from_file_location(
        "trace_by_operator",
        os.path.join(_ROOT, "scripts", "trace_by_operator.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

#: key of every span whose own time adds up to the collect's wall
ADDITIVE = ("plan_ms", "host_prep_ms", "prepare_ms", "speculate_ms",
            "launch_ms", "shard_ms", "seam_ms", "fetch_ms", "finish_ms",
            "unattributed_ms")
#: the spans a one-program collect opens, and those only a split one does
COMMON = {"tpu.collect", "tpu.plan", "tpu.scope_enter", "tpu.prepare",
          "tpu.launch", "tpu.fetch", "tpu.finish", "tpu.launch_inputs",
          "tpu.enqueue", "tpu.launch_outputs", "tpu.fetch_wait",
          "tpu.arrow_build"}
SPLIT_ONLY = {"tpu.speculate", "tpu.seam", "tpu.seam_wait",
              "tpu.seam_resolve"}
#: split span -> the span it is a part of (its key holds its own time)
PART_OF = {"tpu.launch_inputs": "tpu.launch", "tpu.enqueue": "tpu.launch",
           "tpu.launch_outputs": "tpu.launch", "tpu.fetch_wait": "tpu.fetch",
           "tpu.arrow_build": "tpu.fetch", "tpu.seam_wait": "tpu.seam",
           "tpu.seam_resolve": "tpu.seam"}


def _spans_of(shape, kept=False):
    """`kept`: a DataFrame collected again runs through its kept plan; a
    lone program then has nothing to prepare, a split plan still opens
    the span around the lookup of each segment among its own."""
    if shape == "seams":
        return COMMON | SPLIT_ONLY
    return COMMON - ({"tpu.prepare"} if kept else set())


@pytest.mark.parametrize("shape", list(SHAPES))
def test_default_conf_fills_every_key_and_they_add_up(shape):
    df = SHAPES[shape](TpuSession(WHOLE))
    df.collect()
    m = df.metrics()
    ov = {k: m.get("overhead." + k, 0.0) for k in
          ADDITIVE + ("collect_ms", "seam_wait_ms")}
    assert all(v >= 0.0 for v in ov.values()), ov
    always = {"collect_ms", "plan_ms", "host_prep_ms", "prepare_ms",
              "launch_ms", "fetch_ms", "finish_ms",
              "unattributed_ms"}
    if shape == "seams":
        always |= {"speculate_ms", "seam_ms", "seam_wait_ms"}
        assert m["overhead.seam_count"] == 2
        assert m["compile_speculative_submitted"] >= 1
        assert m["host_syncs"] >= 3        # two row counts + the fetch
    else:
        assert m["host_syncs"] >= 1
    assert all("overhead." + k in m and ov[k] > 0.0 for k in always), ov
    # the spans nest or follow one another on one thread, and each key
    # holds its span's own time: they add up to the wall by construction
    assert sum(ov[k] for k in ADDITIVE) == \
        pytest.approx(ov["collect_ms"], abs=1e-6)
    # the wait is a part of the seam, not a category beside it
    assert ov["seam_wait_ms"] <= ov["seam_ms"] + 1e-9


@pytest.mark.parametrize("shape", list(SHAPES))
def test_split_spans_are_parts_of_their_parent(shape):
    """The launch, the fetch and the seam are cut where the chip waits:
    each part's key holds its own time, and the parts of one span add
    up to no more than it.  The cut leaves the ADDITIVE keys adding up
    to the wall."""
    df = SHAPES[shape](TpuSession(WHOLE))
    df.collect()
    m = df.metrics()
    ov = {k.removeprefix("overhead."): v for k, v in m.items()
          if k.startswith("overhead.")}
    parts = {"launch_ms": ("launch_inputs_ms", "enqueue_ms",
                           "launch_outputs_ms"),
             "fetch_ms": ("fetch_wait_ms", "arrow_build_ms")}
    if shape == "seams":
        parts["seam_ms"] = ("seam_wait_ms", "seam_resolve_ms")
    for whole, keys in parts.items():
        assert all(ov.get(k, 0.0) > 0.0 for k in keys), (whole, ov)
        assert sum(ov[k] for k in keys) <= ov[whole] + 1e-9, (whole, ov)
    assert sum(ov.get(k, 0.0) for k in ADDITIVE) == \
        pytest.approx(ov["collect_ms"], abs=1e-6)
    # the one timer of the program call
    assert "exec_device_ms" not in m
    assert 0.0 <= ov["offcpu_ms"] <= ov["collect_ms"]


def test_a_thread_holding_the_gil_shows_as_offcpu_time():
    """A Python thread that spins while a collect runs takes the GIL from
    the collecting thread now and then, and keeps it for a switch
    interval: the off-CPU time of 40 collects rises above that of the
    same 40 collects uncontended."""
    import threading
    df = _q6_df(TpuSession(WHOLE))
    df.collect()

    def offcpu(n=40):
        total = 0.0
        for _ in range(n):
            df.collect()
            total += df.metrics()["overhead.offcpu_ms"]
        return total

    alone = offcpu()
    stop = threading.Event()

    def spin():
        x = 0
        while not stop.is_set():
            x += 1

    t = threading.Thread(target=spin, daemon=True)
    t.start()
    try:
        contended = offcpu()
    finally:
        stop.set()
        t.join(timeout=10)
    assert not t.is_alive()
    assert contended > alone and contended > 2.0, (alone, contended)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_direct_physical_collect_adds_up_to_its_wall(shape):
    """PhysicalQuery.collect(ctx) called without DataFrame.collect() (a
    serving ticket, bench.py) opens its own tpu.collect: the ADDITIVE keys
    are filled and add up to overhead.collect_ms."""
    from spark_rapids_tpu.exec.plan import ExecContext
    q = SHAPES[shape](TpuSession(WHOLE)).physical()
    for _ in range(2):                 # a context each, or one used again
        ctx = ExecContext(q.conf)
        q.collect(ctx)
        m = ctx.metrics
        assert ctx.open_spans == [] and ctx.query_seq == 0
        assert m["overhead.collect_ms"] > 0.0 and "overhead.plan_ms" not in m
        assert sum(m.get("overhead." + k, 0.0) for k in ADDITIVE) == \
            pytest.approx(m["overhead.collect_ms"], abs=1e-6)


@pytest.mark.parametrize("frame", ["same", "rebuilt"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_counters_repeat_over_warm_collects(shape, frame):
    """`same`: one DataFrame collected again keeps its physical plan and
    runs its own programs.  `rebuilt`: a DataFrame built again over the
    same tables (a serving ticket, SQL text) plans anew and adopts every
    program from the process-wide cache, as every collect did before
    DataFrames kept their plans."""
    s = TpuSession(WHOLE)
    tables = _tables()
    df = SHAPES[shape](s, tables)
    df.collect()                               # cold: upload + compile
    counts = []
    for _ in range(2):
        if frame == "rebuilt":
            df = SHAPES[shape](s, tables)
        df.collect()
        m = df.metrics()
        counts.append({k: m.get(k, 0) for k in (
            "host_syncs", "exec_dispatches", "overhead.seam_count",
            "compile_speculative_submitted", "compile_speculative_cached",
            "compile_background_used", "whole_plan_structure_hits",
            "whole_plan_compiled_queries", "plan.reused")})
    assert counts[0] == counts[1], counts
    seams = 2 if shape == "seams" else 0
    kept = frame == "same"
    assert counts[0]["exec_dispatches"] == seams + 1
    assert counts[0]["whole_plan_compiled_queries"] == 1
    assert counts[0]["plan.reused"] == (1 if kept else 0)
    # a warm collect runs its own programs, or adopts every one of them,
    # and speculates at no seam
    assert counts[0]["whole_plan_structure_hits"] == \
        (0 if kept else seams + 1)
    assert counts[0]["compile_speculative_cached"] == (0 if kept else seams)
    assert counts[0]["compile_speculative_submitted"] == 0
    assert counts[0]["compile_background_used"] == 0


@pytest.mark.parametrize("shape", list(SHAPES))
def test_spans_land_in_the_profiler_trace(shape, tmp_path):
    """Under jax.profiler the host plane holds every span of the table,
    inside the enclosing tpu.collect, which lies inside the caller's
    own annotation; all carry the collect's `query` stat.  The traced
    collects are the second and third of one DataFrame: its kept plan."""
    from jax.profiler import ProfileData
    df = SHAPES[shape](TpuSession(WHOLE))
    df.collect()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("collect:x"):
                df.collect()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert files, "the profiler wrote no .xplane.pb"
    data = ProfileData.from_file(files[0])
    host = [p for p in data.planes if p.name == "/host:CPU"]
    assert host, [p.name for p in data.planes]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats).get("query"))
              for line in host[0].lines for e in line.events
              if e.name.startswith(("tpu.", "collect:"))]
    outer = sorted(e for e in events if e[0] == "collect:x")
    wholes = sorted(e for e in events if e[0] == "tpu.collect")
    assert len(outer) == len(wholes) == 2
    assert wholes[0][3] != wholes[1][3]        # one sequence number each
    for (_n, o0, o1, _q), (_n2, w0, w1, query) in zip(outer, wholes):
        assert o0 <= w0 and w1 <= o1
        inside = [e for e in events
                  if e[0] not in ("collect:x", "tpu.collect")
                  and w0 <= e[1] and e[2] <= w1]
        assert {e[0] for e in inside} == \
            _spans_of(shape, kept=True) - {"tpu.collect"}
        assert {e[3] for e in inside} == {query}
        # each part lies inside an instance of the span it is a part of
        for name, s0, s1, _q in inside:
            if name in PART_OF:
                assert any(p[0] == PART_OF[name] and p[1] <= s0
                           and s1 <= p[2] for p in inside), name
    # scripts/trace_by_operator.py reads the same file: each span's own
    # time, which adds up to the annotation's wall
    tool = _by_operator()
    _dev, annotations, seen = tool.load(files[0])
    host_events = [e for lines in seen["/host:CPU"].values() for e in lines]
    row = tool.host_by_span(host_events, annotations, [])["collect:x"]
    assert row["collects"] == 2 and \
        set(row["spans"]) == _spans_of(shape, kept=True)
    assert row["in_spans_pct"] > 90.0 and row["children_pct"] > 50.0
    assert sum(own for own, _idle in row["spans"].values()) == \
        pytest.approx(row["wall_ms"] * row["in_spans_pct"] / 100.0, rel=1e-6)
    # nothing of the seam lies outside a tpu.collect
    assert sum(1 for e in events if e[0].startswith("tpu.")) == \
        sum(1 for e in events for w in wholes
            if e[0].startswith("tpu.") and w[1] <= e[1] and e[2] <= w[2])


@pytest.mark.parametrize("shape", list(SHAPES))
def test_event_log_holds_the_same_spans(shape, tmp_path):
    """With tracing enabled the QueryTracer, and the event log written
    from it, hold the same spans under tpu.collect."""
    from spark_rapids_tpu.obs.tracer import read_event_log
    s = TpuSession({**WHOLE, "spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    df = SHAPES[shape](s)
    df.collect()
    m = df.metrics()
    log = read_event_log(m["event_log_files"]["jsonl"])
    seam = {sp.sid: sp for sp in log.spans if sp.name.startswith("tpu.")}
    assert {sp.name for sp in seam.values()} == _spans_of(shape)
    whole = [sp for sp in seam.values() if sp.name == "tpu.collect"]
    assert len(whole) == 1 and whole[0].parent is None
    for sp in seam.values():
        if sp is whole[0]:
            continue
        parent = seam[sp.parent]               # a tpu.* span, never "query"
        assert whole[0].t0 <= sp.t0 and sp.t1 <= whole[0].t1 + 1e-9
        assert 0.0 <= sp.attrs["cpu_ms"]
        # the program call is the execute interval of the profile readers
        assert (sp.cat == "execute") == (sp.name == "tpu.enqueue")
        if sp.name in PART_OF:
            assert parent.name == PART_OF[sp.name]
        elif sp.name == "tpu.prepare":         # a lone program looks its
            assert parent.name in ("tpu.collect", "tpu.launch")  # executable up inside its launch
        else:
            assert parent is whole[0], (sp.name, parent.name)
    # the planner's phases hang under tpu.plan, everything else that the
    # tracer records under the query span, which tpu.collect encloses
    plan = [sp for sp in seam.values() if sp.name == "tpu.plan"][0]
    phases = [sp for sp in log.spans if sp.cat == "plan"]
    assert phases and all(sp.parent == plan.sid for sp in phases)
    # the log's metrics are those of the finished collect
    assert log.metrics["overhead.collect_ms"] == \
        pytest.approx(m["overhead.collect_ms"])
    assert whole[0].dur_ms == pytest.approx(m["overhead.collect_ms"],
                                            abs=0.01)


def test_tracing_off_records_no_span():
    df = _q6_df(TpuSession(WHOLE))
    df.collect()
    from spark_rapids_tpu.obs.tracer import NULL_TRACER
    assert df._last_ctx.tracer is NULL_TRACER
    assert df._last_ctx.open_spans == []


def test_metrics_only_wall_breakdown_reads_the_keys():
    """Tracing off: wall_breakdown() takes the wall, the categories and
    the residual from the span seam's always-on keys."""
    df = _seam_df(TpuSession(WHOLE))
    df.collect()
    m = df.metrics()
    bd = df.profile().wall_breakdown()
    assert bd["wall_ms"] == pytest.approx(m["overhead.collect_ms"], abs=1e-3)
    assert bd["unattributed_ms"] == \
        pytest.approx(m["overhead.unattributed_ms"], abs=1e-3)
    named = sum(bd[k] for k in (
        "device_compute_ms", "dispatch_ms", "seam_ms", "compile_ms",
        "fetch_ms", "shuffle_ms", "host_prep_ms", "prepare_ms",
        "speculate_ms", "launch_ms", "finish_ms", "plan_ms"))
    assert named + bd["unattributed_ms"] == \
        pytest.approx(bd["wall_ms"], abs=0.02)


def test_lowered_program_names_every_plan_node():
    """The whole-plan program of a join under an aggregate carries, in
    its ops' debug names, the id of every node that traces an op — the
    ids EXPLAIN prints."""
    from spark_rapids_tpu.exec.compiled import _TRACE_LOCK, CompiledPlan
    from spark_rapids_tpu.exec.plan import ExecContext
    q = _seam_df(TpuSession(WHOLE)).physical()
    in_explain = re.findall(r"<(\w+#\d+)>", q.physical_tree())
    assert len(in_explain) == len(set(in_explain)) == 5, q.physical_tree()
    ctx = ExecContext(q.conf)
    plan = CompiledPlan(q.root, q.conf)
    flat_in, in_specs = plan._flatten_inputs(plan._leaf_batches(ctx))
    with _TRACE_LOCK:
        lowered = jax.jit(plan._make_runner(in_specs, ctx, {})).lower(flat_in)
    named = set(re.findall(r"\w+Exec#\d+", lowered.as_text(debug_info=True)))
    # a scan hands its resident lanes on and traces no op of its own
    assert named == {n for n in in_explain if not n.startswith("HostScan")}
    assert any("HashAggregateExec#1/AdaptiveShuffledJoinExec#2/" in line
               for line in lowered.as_text(debug_info=True).splitlines())
    # the scopes are for the trace only
    assert all("execute" not in vars(n) or getattr(n, "_metered", False)
               for n in [q.root, q.root.child, q.root.child.child])


def test_trace_by_operator_reads_op_names_off_a_chip_trace():
    """The benchmark's kept chip sample (PR 24, before any node was named):
    the script finds each op's `op_name`, program and source file in the
    event metadata that ProfileData does not hand out, and files every op
    under the source that traced it, since no program names a node."""
    tool = _by_operator()
    sample = os.path.join(_ROOT, "benchmarks", "tests", "data",
                          "q6_12.xplane.pb.gz")
    names, programs, sources = tool.op_names(sample)
    fused = [n for n in names if n.startswith("%convert_reduce_fusion")]
    assert fused and all(names[n] == {"jit(run)/jit(run)/reduce_sum:"}
                         and len(programs[n]) == 1
                         and sources[n].endswith("ops/groupby.py")
                         for n in fused)
    device_lines, annotations, _seen = tool.load(sample)
    dev = tool.device_by_node(device_lines, sorted(
        annotations, key=lambda a: a[1]), names, programs, sources)
    assert all(k[1].startswith(tool.EAGER) for k in dev["by_node"])
    assert sum(dev["by_node"].values()) == pytest.approx(0.0392, abs=5e-4)
    assert max(dev["by_node"], key=dev["by_node"].get) == \
        ("collect:q6", tool.EAGER + "ops/groupby.py")
    assert [tool.node_of({"jit(run)/SortExec#0/HashJoinExec#4/" + rest})
            for rest in ("mul:", "sink/jit(_take)/gather:",
                         "seam/jit(run)/sort:", "sinker:")] == \
        ["HashJoinExec#4", "HashJoinExec#4/sink", "HashJoinExec#4/seam",
         "HashJoinExec#4"]
