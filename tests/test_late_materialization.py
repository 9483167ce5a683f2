"""Late-materialization join pipelines (columnar/lanes.py).

Chained equi-joins must produce oracle-identical results whether payload
columns materialize eagerly (lateMaterialization.enabled=false) or ride
as row-id lanes to the pipeline sink (default).  The scenarios cover the
shapes the legality pass (plan/overrides.py _negotiate_thin) admits:
outer/semi/anti joins chained 2+ deep, null-extended rows, filters and
projections BETWEEN the joins — including a mid-chain filter that
references a deferred column and therefore forces early materialization
of exactly that column — and aggregate / sort / whole-plan-boundary
sinks."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec.adaptive import AdaptiveShuffledJoinExec
from spark_rapids_tpu.exec.join import HashJoinExec
from spark_rapids_tpu.exec.plan import ExecContext
from spark_rapids_tpu.plan.aggregates import Count, Sum
from spark_rapids_tpu.session import DataFrame, TpuSession, col, lit

OFF = {"spark.rapids.tpu.sql.join.lateMaterialization.enabled": "false"}
CPU = {"spark.rapids.tpu.sql.enabled": "false"}


def _tables(seed=7, n_fact=3000, n_d1=60, n_d2=35):
    rng = np.random.default_rng(seed)
    fact = pa.table({
        # keys range past the dimension domains (unmatched rows) and
        # carry nulls (never match, null-extend under outer joins)
        "fk1": pa.array(rng.integers(0, n_d1 + 8, n_fact), pa.int64(),
                        mask=rng.random(n_fact) < 0.06),
        "fk2": pa.array(rng.integers(0, n_d2 + 8, n_fact), pa.int64(),
                        mask=rng.random(n_fact) < 0.06),
        "fv": pa.array(rng.integers(0, 1000, n_fact), pa.int64()),
    })
    d1 = pa.table({
        "k1": pa.array(np.arange(n_d1), pa.int64()),
        "p1": pa.array(rng.integers(0, 100, n_d1), pa.int64()),
        "s1": pa.array([f"grp_{i % 7}" for i in range(n_d1)]),
    })
    d2 = pa.table({
        "k2": pa.array(np.arange(n_d2), pa.int64()),
        "p2": pa.array(rng.integers(0, 50, n_d2), pa.int64()),
    })
    return fact, d1, d2


def _norm(t: pa.Table):
    rows = [tuple(row) for row in
            zip(*[t.column(c).to_pylist() for c in t.schema.names])]
    return sorted(rows, key=lambda r: tuple(
        (v is None, v if v is not None else 0) for v in r))


def _check(build_df, extra_conf=None):
    """Run the same logical plan on (device, thin ON), (device, thin
    OFF) and the CPU oracle; all three row sets must agree.  Returns the
    ON-run ExecContext for metric assertions."""
    dev_on = TpuSession(dict(extra_conf or {}))
    dev_off = TpuSession({**OFF, **(extra_conf or {})})
    cpu = TpuSession(CPU)
    df = build_df(dev_on)
    q = df.physical()
    ctx = ExecContext(dev_on.conf)
    got_on = q.collect(ctx)
    got_off = DataFrame(df._plan, dev_off).collect()
    want = DataFrame(df._plan, cpu).collect()
    assert got_on.schema.names == want.schema.names
    assert _norm(got_on) == _norm(want), "thin path != oracle"
    assert _norm(got_off) == _norm(want), "dense path != oracle"
    return q, ctx


def _joins(plan_node, out=None):
    out = [] if out is None else out
    if isinstance(plan_node, (HashJoinExec, AdaptiveShuffledJoinExec)):
        out.append(plan_node)
    for c in plan_node.children:
        _joins(c, out)
    return out


@pytest.mark.parametrize("how1,how2", [
    ("inner", "inner"), ("left_outer", "inner"),
    ("inner", "left_outer"), ("left_outer", "left_outer")])
def test_chained_joins_with_filters_match_oracle(how1, how2):
    """fact ⋈ d1 → filter → ⋈ d2 → sort: two chained joins with a
    filter between them and null-extended rows, against the oracle."""
    fact, d1, d2 = _tables()

    def build(s):
        f = s.from_arrow(fact)
        j1 = f.join(s.from_arrow(d1), how=how1,
                    left_on=["fk1"], right_on=["k1"])
        j1 = j1.filter(col("fv") > lit(200))      # probe-side column
        j2 = j1.join(s.from_arrow(d2), how=how2,
                     left_on=["fk2"], right_on=["k2"])
        return j2.sort(("fv", False), ("fk1", False))

    q, ctx = _check(build)
    joins = _joins(q.root)
    assert joins and all(j.thin_payload for j in joins), \
        "legality pass should mark both chained joins thin"


def test_mid_chain_filter_on_deferred_column():
    """The filter BETWEEN the joins references d1's payload column p1 —
    deferred by join 1, so the filter must force early materialization
    of exactly that column (materialize_refs), while s1 stays thin to
    the sort sink."""
    fact, d1, d2 = _tables()

    def build(s):
        f = s.from_arrow(fact)
        j1 = f.join(s.from_arrow(d1), how="left_outer",
                    left_on=["fk1"], right_on=["k1"])
        # p1 is a DEFERRED right-side column here; null-extended rows
        # must stay dropped by the filter (null > 30 is not true)
        j1 = j1.filter(col("p1") > lit(30))
        j2 = j1.join(s.from_arrow(d2), how="inner",
                     left_on=["fk2"], right_on=["k2"])
        return j2.sort(("fv", False), ("p2", False))

    q, ctx = _check(build)
    assert ctx.metrics.get("join_deferred_gathers", 0) > 0, \
        "the chain should actually defer payload gathers"


def test_semi_anti_through_chain():
    """semi/anti joins pass a thin probe stream through unchanged."""
    fact, d1, d2 = _tables()

    def build(s):
        f = s.from_arrow(fact)
        j1 = f.join(s.from_arrow(d1), how="left_outer",
                    left_on=["fk1"], right_on=["k1"])
        semi = j1.join(s.from_arrow(d2), how="left_semi",
                       left_on=["fk2"], right_on=["k2"])
        anti = j1.join(s.from_arrow(d2), how="left_anti",
                       left_on=["fk2"], right_on=["k2"])
        return semi.union(anti).sort(("fv", False), ("fk1", False)) \
            if hasattr(semi, "union") else semi.sort(("fv", False),
                                                     ("fk1", False))

    _check(build)


def test_aggregate_sink_materializes_referenced_only():
    """Group-by over a deferred dimension column: the aggregate sink
    materializes the key/input columns through the composed lanes."""
    fact, d1, d2 = _tables()

    def build(s):
        f = s.from_arrow(fact)
        j1 = f.join(s.from_arrow(d1), how="inner",
                    left_on=["fk1"], right_on=["k1"])
        j2 = j1.join(s.from_arrow(d2), how="left_outer",
                     left_on=["fk2"], right_on=["k2"])
        return (j2.group_by("s1")
                .agg((Sum(col("fv")), "sv"), (Count(col("p2")), "cnt"))
                .sort(("s1", False)))

    q, ctx = _check(build)
    assert ctx.metrics.get("join_deferred_gathers", 0) > 0


def test_projection_passes_deferred_columns_through():
    """A projection between the joins: plain refs to deferred columns
    pass through as lanes (project_batch), computed exprs materialize
    exactly their refs."""
    fact, d1, d2 = _tables()

    def build(s):
        f = s.from_arrow(fact)
        j1 = f.join(s.from_arrow(d1), how="left_outer",
                    left_on=["fk1"], right_on=["k1"])
        proj = j1.select(col("fk2"), col("fv"),
                         (col("fv") + lit(1)), col("s1"), col("p1"),
                         names=["fk2", "fv", "fv2", "s1", "p1"])
        j2 = proj.join(s.from_arrow(d2), how="inner",
                       left_on=["fk2"], right_on=["k2"])
        return j2.sort(("fv", False), ("p1", False))

    _check(build)


def test_whole_plan_compiled_thin_pipeline():
    """The compiled program boundary is a sink: thin outputs materialize
    INSIDE the traced program; results equal the oracle."""
    fact, d1, d2 = _tables(seed=11, n_fact=1500)
    conf = {"spark.rapids.tpu.sql.compile.wholePlan": "ON"}

    def build(s):
        f = s.from_arrow(fact)
        j1 = f.join(s.from_arrow(d1), how="left_outer",
                    left_on=["fk1"], right_on=["k1"])
        j1 = j1.filter(col("fv") > lit(100))
        j2 = j1.join(s.from_arrow(d2), how="inner",
                     left_on=["fk2"], right_on=["k2"])
        return (j2.group_by("s1")
                .agg((Sum(col("fv")), "sv"), (Count(col("p1")), "c1"))
                .sort(("s1", False)))

    q, ctx = _check(build, extra_conf=conf)
    assert ctx.metrics.get("whole_plan_compiled_queries", 0) == 1


def test_off_switch_disables_thin():
    fact, d1, _d2 = _tables()
    s = TpuSession(OFF)
    df = s.from_arrow(fact).join(s.from_arrow(d1), how="inner",
                                 left_on=["fk1"], right_on=["k1"]) \
        .group_by("s1").agg((Sum(col("fv")), "sv"))
    q = df.physical()
    assert all(j.thin_payload is None for j in _joins(q.root))


def test_deferred_string_rides_as_codes():
    """A deferred dictionary-coded string column keeps its dictionary on
    the placeholder and materializes as codes at the sink — values must
    round-trip exactly (incl. null-extended outer rows)."""
    fact, d1, d2 = _tables(seed=23)

    def build(s):
        f = s.from_arrow(fact)
        j1 = f.join(s.from_arrow(d1), how="left_outer",
                    left_on=["fk1"], right_on=["k1"])
        j2 = j1.join(s.from_arrow(d2), how="left_outer",
                     left_on=["fk2"], right_on=["k2"])
        return j2.select(col("fv"), col("s1"), col("p2"),
                         names=["fv", "s1", "p2"]) \
            .sort(("fv", False), ("s1", False), ("p2", False))

    _check(build)


# ---------------------------------------------------------------------------
# Split-plan seams: `sel` and `thin` cross the program boundary and are
# resolved AFTER the seam's row-count sync, at the shrunken bucket
# (exec/compiled.py SplitCompiledPlan._shrink, ISSUE 26)
# ---------------------------------------------------------------------------

SPLIT = {"spark.rapids.tpu.sql.compile.wholePlan": "ON",
         # the seam gate keeps a plan this small in one program
         "spark.rapids.tpu.sql.compile.seamSplitMinRows": "1"}


def _seam_query(how1, how2, key="s1", count="p1", thresh=900, n_fact=5000):
    """A join chain under an aggregate under a sort: two seams, the
    first at the chain's output."""
    fact, d1, d2 = _tables(seed=11, n_fact=n_fact)

    def build(s):
        f = s.from_arrow(fact)
        j1 = f.join(s.from_arrow(d1), how=how1,
                    left_on=["fk1"], right_on=["k1"])
        j1 = j1.filter(col("fv") > lit(thresh))
        j2 = j1.join(s.from_arrow(d2), how=how2,
                     left_on=["fk2"], right_on=["k2"])
        return (j2.group_by(key)
                .agg((Sum(col("fv")), "sv"), (Count(col(count)), "c"))
                .sort((key, False)))
    return build


SEAM_CASES = {
    "inner": (_seam_query("inner", "inner", count="p2"), {}),
    "left_outer": (_seam_query("left_outer", "left_outer", count="p2"), {}),
    "semi": (_seam_query("inner", "left_semi", key="p1", count="fv"), {}),
    # s1 is d1's string payload: deferred, it crosses the seam as codes
    "string_codes": (_seam_query("left_outer", "inner"), {}),
    # no thin state: the selection vector alone takes the same path
    "late_materialization_off": (_seam_query("left_outer", "inner"), OFF),
    # 2213 of 4096 rows live: the bucket of the live rows is the capacity
    "nothing_collapses": (_seam_query("inner", "inner", thresh=-1,
                                      n_fact=3500), {}),
    "empty": (_seam_query("inner", "inner", thresh=10 ** 6), {}),
}


def _collect_split(build, extra):
    s = TpuSession({**SPLIT, **extra})
    df = build(s)
    q = df.physical()
    ctx = ExecContext(s.conf)
    got = q.collect(ctx)
    want = DataFrame(df._plan, TpuSession(CPU)).collect()
    assert got.schema.names == want.schema.names
    assert _norm(got) == _norm(want), "split plan != oracle"
    return q, ctx.metrics


@pytest.mark.parametrize("case", sorted(SEAM_CASES))
def test_seam_resolves_lazily_at_shrunken_bucket(case, monkeypatch):
    from spark_rapids_tpu.exec import compiled as C
    resolved = []
    real = C._resolve_at

    def spy(db, cap, scope, conf):
        out = real(db, cap, scope, conf)
        resolved.append((db, cap, scope, out))
        return out
    monkeypatch.setattr(C, "_resolve_at", spy)
    build, extra = SEAM_CASES[case]
    q, m = _collect_split(build, extra)
    assert m.get("whole_plan_split_queries") == 1
    assert not m.get("whole_plan_fallbacks")
    assert m["overhead.seam_count"] == 2 and m["exec_dispatches"] == 3
    # seam 0 (the join chain's output) is lazy; seam 1 (the aggregate's
    # dense output) is sliced, except where the aggregate sorts on one
    # packed key lane (`semi`: grouped by the integer p1): it then leaves
    # each group at its run's last row, under a mask that the seam
    # resolves like any other (PR 35)
    in_place = case == "semi"
    assert m["overhead.seam_lazy_count"] == 1 + in_place
    assert m["overhead.seam_capacity_rows"] > m["overhead.seam_rows"]
    (db, cap, scope, out) = resolved[0]
    assert len(resolved) == 1 + in_place
    if in_place:
        assert resolved[1][2] == q.root.child._node_id
        assert resolved[1][0].sel is not None
        resolved_rows = resolved[1][0].capacity
    else:
        resolved_rows = 0
    assert scope == q.root.child.child._node_id
    assert db.sel is not None
    assert (db.thin is None) == (extra == OFF)
    assert out.sel is None and out.thin is None
    assert all(c.capacity == cap for c in out.columns)
    assert db.capacity + resolved_rows == m["overhead.seam_capacity_rows"]
    if case == "nothing_collapses":
        assert cap == db.capacity
    else:
        assert cap < db.capacity
    if case == "empty":
        assert m["overhead.seam_rows"] == 0 and cap == 1024
    if case == "string_codes":
        i = db.names.index("s1")
        assert i in db.thin.pending and db.columns[i].capacity == 0
        assert out.columns[i].dictionary is db.columns[i].dictionary


def _hand_batch(kind, cap=4096, src_rows=64, seed=5):
    """A seam batch built by hand: `kind` says whether it has a
    selection vector and deferred columns.  The deferred columns are a
    wide decimal (hi lane; no planned query carries one across a seam:
    its aggregate would run on the host) and a dictionary string."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.columnar.device import DeviceBatch, DeviceColumn
    from spark_rapids_tpu.columnar.lanes import (LaneSource, ThinState,
                                                 deferred_column)
    rng = np.random.default_rng(seed)
    n_live = 700
    ids = jnp.asarray(np.arange(cap, dtype=np.int64))
    live = np.zeros(cap, bool)
    if "sel" in kind:
        live[rng.choice(cap, n_live, replace=False)] = True
    else:
        live[:n_live] = True
    dictionary = pa.array([f"w{i}" for i in range(9)])
    src = DeviceBatch(
        [DeviceColumn(jnp.asarray(rng.integers(0, 1 << 40, src_rows)),
                      jnp.asarray(rng.random(src_rows) < 0.9),
                      t.DecimalType(30, 2), None,
                      jnp.asarray(rng.integers(-3, 3, src_rows))),
         DeviceColumn(jnp.asarray(rng.integers(0, 9, src_rows)
                                  .astype(np.int32)),
                      jnp.ones(src_rows, bool), t.StringType(),
                      dictionary)],
        src_rows - 4, ["dec", "word"])
    # -1: a null-extended row; 62: past the source's live rows
    lane = rng.integers(-1, src_rows - 1, cap).astype(np.int32)
    cols = [DeviceColumn(ids, jnp.asarray(live), t.LongType())]
    names = ["id"]
    thin = None
    if "thin" in kind:
        cols += [deferred_column(c) for c in src.columns]
        names += list(src.names)
        thin = ThinState(cap, [LaneSource(src, jnp.asarray(lane))],
                         {1: (0, 0), 2: (0, 1)})
    db = DeviceBatch(cols, jnp.int32(n_live), names,
                     sel=jnp.asarray(live) if "sel" in kind else None,
                     thin=thin)
    return db, src, lane, np.flatnonzero(live)


@pytest.mark.parametrize("kind", ["sel+thin", "sel", "thin", "dense"])
def test_shrink_by_batch_form(kind):
    """_shrink on hand-built batches of every form: a batch with `sel`
    or `thin` is resolved after the count, at the bucket of its live
    rows (a hi lane and dictionary codes included); a dense prefix
    batch is sliced and counts as not lazy."""
    from spark_rapids_tpu.exec.compiled import SplitCompiledPlan
    db, src, lane, live_rows = _hand_batch(kind)
    ctx = ExecContext(TpuSession(SPLIT).conf)
    (out,), rows = SplitCompiledPlan._shrink([db], ctx, "JoinExec#9")
    assert rows == len(live_rows) == 700 and ctx.metrics["host_syncs"] == 1
    assert out.capacity == 1024 and out.sel is None and out.thin is None
    assert int(out.num_rows) == rows
    lazy = kind != "dense"
    assert ctx.metrics.get("overhead.seam_lazy_count", 0) == int(lazy)
    assert ctx.metrics.get("overhead.seam_capacity_rows", 0) == \
        (4096 if lazy else 0)
    np.testing.assert_array_equal(np.asarray(out.columns[0].data)[:rows],
                                  live_rows)
    np.testing.assert_array_equal(
        np.asarray(out.columns[0].validity), np.arange(1024) < rows)
    if "thin" not in kind:
        return
    at = lane[live_rows]
    ok = (at >= 0) & (at < int(src.num_rows))
    for got, want in zip(out.columns[1:], src.columns):
        assert got.dtype == want.dtype and got.dictionary is want.dictionary
        valid = np.asarray(got.validity)
        np.testing.assert_array_equal(
            valid[:rows], ok & np.asarray(want.validity)[np.clip(at, 0, None)])
        assert not valid[rows:].any()
        keep = valid[:rows]
        np.testing.assert_array_equal(
            np.asarray(got.data)[:rows][keep],
            np.asarray(want.data)[at[keep]])
        if want.data_hi is not None:
            np.testing.assert_array_equal(
                np.asarray(got.data_hi)[:rows][keep],
                np.asarray(want.data_hi)[at[keep]])


@pytest.mark.parametrize("kind", ["sel+thin", "sel"])
@pytest.mark.parametrize("c", [1024, 256])
def test_compact_out_capacity_is_a_prefix_of_the_full_compaction(kind, c):
    """compact_batch / compact_thin with out_capacity=c: the first c
    rows of the full compaction, deferred columns included (c=256 cuts
    into the 700 kept rows)."""
    from spark_rapids_tpu.ops.filter import compact_batch
    db, _src, _lane, _live = _hand_batch(kind)
    full = compact_batch(db, db.sel)
    cut = compact_batch(db, db.sel, out_capacity=c)
    assert full.capacity == 4096 and cut.capacity == c
    assert int(full.num_rows) == 700 and int(cut.num_rows) == min(700, c)
    assert len(cut.columns) == len(full.columns) == len(db.columns)
    for a, b in zip(cut.columns, full.columns):
        assert a.dtype == b.dtype and a.dictionary is b.dictionary
        for lane in ("data", "validity", "data_hi"):
            x, y = getattr(a, lane), getattr(b, lane)
            assert (x is None) == (y is None)
            if x is not None:
                np.testing.assert_array_equal(np.asarray(x),
                                              np.asarray(y)[:c])


def _walk_named(jaxpr, prefix=""):
    """(equation, its full name stack): an equation inside a nested
    jit carries only the inner part, the outer scopes sit on the call."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        name = f"{prefix}/{eqn.source_info.name_stack}"
        yield eqn, name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _walk_named(sub, name)


def test_seam_segment_traces_no_gather_at_capacity_under_its_sink():
    """Segment 0 of a split plan hands `sel` and `thin` over: its sink
    traces no gather at the segment's capacity.  The same root as an
    unsplit program's still resolves them there (the fetch needs a
    dense batch)."""
    from spark_rapids_tpu.exec.compiled import (CompiledPlan,
                                                SplitCompiledPlan,
                                                build_plan)
    s = TpuSession(SPLIT)
    q = SEAM_CASES["string_codes"][0](s).physical()
    ctx = ExecContext(s.conf)
    plan = build_plan(q.root, ctx)
    assert isinstance(plan, SplitCompiledPlan)
    plan._install_leaves()
    try:
        seg = plan._segment(0, (), ctx)
        lazy = seg.make_jaxpr(ctx)
        dense = CompiledPlan(seg.root, s.conf).make_jaxpr(ctx)
    finally:
        plan._restore_leaves()
    assert seg.seam and seg.root is plan.seams[0]

    def sink_gathers(jx):
        return [e.outvars[0].aval.shape for e, name in _walk_named(jx)
                if e.primitive.name == "gather"
                and f"{seg.root._node_id}/sink" in name]
    # (capacity,) lanes, or same-dtype lanes stacked to (capacity, k)
    assert any(shape[0] == 16384 for shape in sink_gathers(dense))
    assert sink_gathers(lazy) == []


def test_second_seam_keeps_its_speculative_program(monkeypatch):
    """A two-seam plan: every later segment runs the program that was
    compiled speculatively against the seam's DENSE output (no inline
    recompile for a drifted signature), and a second collect of the
    same DataFrame, planned anew, finds the seam's own program and all
    three segments' in the module-level caches: nothing is traced
    again, and nothing is submitted to the compile service."""
    from spark_rapids_tpu.exec import compiled as C
    traces = []
    real = C._seam_trace

    def counting(spec, cap, scope, conf):
        run = real(spec, cap, scope, conf)

        def counted(flat):
            traces.append((scope, cap))
            return run(flat)
        return counted
    monkeypatch.setattr(C, "_seam_trace", counting)
    C._SEAM_CACHE.clear()
    s = TpuSession(SPLIT)
    df = SEAM_CASES["left_outer"][0](s)
    C._PLAN_EXEC_CACHE.clear()
    for warm in (False, True):
        ctx = ExecContext(s.conf)
        got = df.physical().collect(ctx)
        m = ctx.metrics
        assert not m.get("whole_plan_fallbacks")
        assert m["overhead.seam_lazy_count"] == 1
        # three segments, each program obtained once: compiled inline
        # (a miss), in the background, or adopted from the structure
        # cache; a drifted speculative program would add a miss
        assert m.get("compile_cache_misses", 0) + \
            m.get("compile_background_used", 0) + \
            m.get("whole_plan_structure_hits", 0) == 3
        if warm:
            # both seams remember their bucket and find its program
            assert m.get("whole_plan_structure_hits") == 3
            assert m.get("compile_speculative_cached") == 2
            assert not m.get("compile_speculative_submitted")
            assert not m.get("compile_background_used")
        else:
            assert m.get("compile_background_used", 0) >= 1
    assert traces == [(df.physical().root.child.child._node_id, 1024)]
    assert len(C._SEAM_CACHE) == 1
    want = DataFrame(df._plan, TpuSession(CPU)).collect()
    assert _norm(got) == _norm(want)
