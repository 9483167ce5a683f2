"""Sync-free join fast paths (round-3 perf work).

- probe-aligned joins when the build side's keys are unique (exact scan
  statistics / group-by structure): ops/join.py probe_aligned
- single-lane semi/anti matched flags without pair expansion
- scalar-subquery cross joins (static_row_count == 1)
- static uniqueness inference (PlanNode.keys_unique)

Every path is validated against the same queries on the slow/sized path
(uniqueness knowledge stripped) and against a pyarrow oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute  # noqa: F401
import pytest

from spark_rapids_tpu.exec.join import CrossJoinExec, HashJoinExec
from spark_rapids_tpu.exec.plan import (ExecContext, FilterExec,
                                        HashAggregateExec, HostScanExec,
                                        ProjectExec, SortExec)
from spark_rapids_tpu.plan import expressions as E
from spark_rapids_tpu.ops import join as J
from spark_rapids_tpu.plan.aggregates import Count, Sum


def _scan(d, chunk=None):
    return HostScanExec.from_table(pa.table(d), chunk)


def _dim():
    return _scan({"k": pa.array([1, 2, 3, 5, 8], pa.int64()),
                  "name": pa.array(["a", "b", "c", "d", "e"])})


def _fact():
    return _scan({"fk": pa.array([1, 1, 2, 5, 9, 8, 8, 8], pa.int64()),
                  "v": pa.array([10., 11., 20., 50., 90., 80., 81., 82.])})


class TestKeysUnique:
    def test_scan_statistics(self):
        dim = _dim()
        assert dim.keys_unique(["k"])
        assert not _fact().keys_unique(["fk"])
        assert not dim.keys_unique(["missing"])
        assert not dim.keys_unique([])

    def test_scan_multi_key(self):
        s = _scan({"a": pa.array([1, 1, 2]), "b": pa.array([1, 2, 1])})
        assert s.keys_unique(["a", "b"])
        assert not s.keys_unique(["a"])

    def test_nulls_do_not_break_uniqueness(self):
        # null keys never match — several nulls still count as unique
        s = _scan({"k": pa.array([1, None, None, 2], pa.int64())})
        assert s.keys_unique(["k"])

    def test_filter_sort_project_preserve(self):
        dim = _dim()
        f = FilterExec(E.GreaterThan(E.ColumnRef("k"), E.Literal(1)), dim)
        assert f.keys_unique(["k"])
        s = SortExec([(0, True, True)], f)
        assert s.keys_unique(["k"])
        p = ProjectExec([E.ColumnRef("k"), E.ColumnRef("name")],
                        ["kk", "nm"], s)
        assert p.keys_unique(["kk"])
        # computed expressions don't map to a source column
        p2 = ProjectExec([E.Add(E.ColumnRef("k"), E.Literal(1))], ["k1"], s)
        assert not p2.keys_unique(["k1"])
        # a genuinely non-unique column stays non-unique through project
        dup = _scan({"d": pa.array([1, 1, 2], pa.int64())})
        pd = ProjectExec([E.ColumnRef("d")], ["dd"], dup)
        assert not pd.keys_unique(["dd"])

    def test_groupby_keys_unique(self):
        agg = HashAggregateExec([E.ColumnRef("fk")], ["fk"],
                                [(Sum(E.ColumnRef("v")), "sv")], _fact())
        assert agg.keys_unique(["fk"])
        assert agg.keys_unique(["fk", "sv"])
        assert not agg.keys_unique(["sv"])

    def test_global_agg_static_row_count(self):
        agg = HashAggregateExec([], [], [(Sum(E.ColumnRef("v")), "sv")],
                                _fact())
        assert agg.static_row_count() == 1
        p = ProjectExec([E.ColumnRef("sv")], ["total"], agg)
        assert p.static_row_count() == 1

    def test_join_propagates_uniqueness(self):
        j = HashJoinExec("inner", [E.ColumnRef("fk")], [E.ColumnRef("k")],
                         _fact(), _dim())
        # fact keys stay non-unique; a unique left input would stay unique
        assert not j.keys_unique(["fk"])
        j2 = HashJoinExec("inner", [E.ColumnRef("k")], [E.ColumnRef("k2")],
                          _dim(),
                          _scan({"k2": pa.array([1, 2, 3], pa.int64())}))
        assert j2.keys_unique(["k"])


def _join_oracle(jt):
    """pyarrow oracle for fact-join-dim on fk == k."""
    fact = pa.table({"fk": [1, 1, 2, 5, 9, 8, 8, 8],
                     "v": [10., 11., 20., 50., 90., 80., 81., 82.]})
    dim = pa.table({"k": [1, 2, 3, 5, 8],
                    "name": ["a", "b", "c", "d", "e"]})
    return fact.join(dim, keys=["fk"], right_keys=["k"],
                     join_type=jt, right_suffix="_r")


@pytest.mark.parametrize("jt", ["inner", "left_outer", "left_semi",
                                "left_anti", "full_outer", "right_outer"])
def test_aligned_matches_sized_path(jt):
    """The unique-build aligned path and the generic sized path agree."""
    ctx = ExecContext()
    fast = HashJoinExec(jt, [E.ColumnRef("fk")], [E.ColumnRef("k")],
                        _fact(), _dim())
    assert fast._build_unique()
    out_fast = fast.collect(ctx)
    assert ctx.metrics.get("join_aligned_fastpath", 0) >= 1 or \
        jt in ("left_semi", "left_anti")

    # strip the statistics -> generic path
    dim_nostat = HostScanExec(_dim().batches, _dim().output_schema)
    slow = HashJoinExec(jt, [E.ColumnRef("fk")], [E.ColumnRef("k")],
                        _fact(), dim_nostat)
    assert not slow._build_unique()
    out_slow = slow.collect()

    def rows(tbl):
        cols = [tbl.column(n).to_pylist() for n in tbl.schema.names]
        return sorted(zip(*cols), key=repr)
    assert rows(out_fast) == rows(out_slow)


def test_aligned_inner_against_pyarrow():
    out = HashJoinExec("inner", [E.ColumnRef("fk")], [E.ColumnRef("k")],
                       _fact(), _dim()).collect()
    got = sorted(zip(out.column("fk").to_pylist(),
                     out.column("v").to_pylist(),
                     out.column("name").to_pylist()))
    ora = _join_oracle("inner")
    exp = sorted(zip(ora.column("fk").to_pylist(),
                     ora.column("v").to_pylist(),
                     ora.column("name").to_pylist()))
    assert got == exp


def test_aligned_with_filtered_probe_lazy_counts():
    """Probe comes through a filter (lazy num_rows) — still correct and
    still aligned."""
    fact = FilterExec(E.GreaterThan(E.ColumnRef("v"), E.Literal(15.0)),
                      _fact())
    ctx = ExecContext()
    out = HashJoinExec("inner", [E.ColumnRef("fk")], [E.ColumnRef("k")],
                       fact, _dim()).collect(ctx)
    assert ctx.metrics.get("join_aligned_fastpath") == 1
    got = sorted(zip(out.column("fk").to_pylist(),
                     out.column("name").to_pylist()))
    assert got == [(2, "b"), (5, "d"), (8, "e"), (8, "e"), (8, "e")]


def test_semi_anti_single_lane_no_expansion():
    for jt, exp in [("left_semi", [1, 1, 2, 5, 8, 8, 8]),
                    ("left_anti", [9])]:
        out = HashJoinExec(jt, [E.ColumnRef("fk")], [E.ColumnRef("k")],
                           _fact(),
                           # non-unique build: the lazy matched flag must
                           # not depend on uniqueness
                           _scan({"k": pa.array([1, 2, 3, 5, 8, 8],
                                                pa.int64())})).collect()
        assert sorted(out.column("fk").to_pylist()) == exp


def test_cross_join_scalar_subquery_fast_path():
    """HAVING-against-total shape: cross join vs a global aggregate."""
    fact = _fact()
    total = HashAggregateExec([], [], [(Sum(E.ColumnRef("v")), "tv")],
                              _fact())
    cross = CrossJoinExec(fact, total)
    out = cross.collect()
    assert out.num_rows == 8
    assert set(out.column("tv").to_pylist()) == {sum(
        [10., 11., 20., 50., 90., 80., 81., 82.])}


def test_aligned_join_string_build_keys():
    """Dictionary (string) build keys still work on the aligned path."""
    dim = _scan({"s": pa.array(["x", "y", "z"]),
                 "m": pa.array([1, 2, 3], pa.int64())})
    fact = _scan({"s": pa.array(["y", "x", "q", "y"]),
                  "v": pa.array([1., 2., 3., 4.])})
    ctx = ExecContext()
    j = HashJoinExec("inner", [E.ColumnRef("s")], [E.ColumnRef("s")],
                     fact, dim)
    assert j._build_unique()
    out = j.collect(ctx)
    got = sorted(zip(out.column("v").to_pylist(),
                     out.column("m").to_pylist()))
    assert got == [(1.0, 2), (2.0, 1), (4.0, 2)]


def test_aligned_null_keys_never_match():
    dim = _scan({"k": pa.array([1, None, 2], pa.int64()),
                 "m": pa.array([10, 99, 20], pa.int64())})
    fact = _scan({"k": pa.array([1, None, 3], pa.int64()),
                  "v": pa.array([1., 2., 3.])})
    out = HashJoinExec("left_outer", [E.ColumnRef("k")],
                       [E.ColumnRef("k")], fact, dim).collect()
    rows = dict(zip(out.column("v").to_pylist(),
                    out.column("m").to_pylist()))
    assert rows == {1.0: 10, 2.0: None, 3.0: None}


def test_multi_key_join_range_packing():
    """Composite keys WITH exact range statistics fold into one injective
    int64 lane (range packing) — the aligned path engages and is exact.
    Without statistics the composite hash could collide between distinct
    build tuples, so the aligned path must NOT engage."""
    dim_tbl = pa.table({"a": pa.array([1, 1, 2], pa.int64()),
                        "b": pa.array([1, 2, 1], pa.int64()),
                        "m": pa.array([10, 11, 12], pa.int64())})
    fact_tbl = pa.table({"a": pa.array([1, 2, 1], pa.int64()),
                         "b": pa.array([2, 1, 9], pa.int64()),
                         "v": pa.array([1., 2., 3.])})
    keys = [E.ColumnRef("a"), E.ColumnRef("b")]

    # with stats: packed single lane -> aligned
    ctx = ExecContext()
    j = HashJoinExec("inner", keys, keys,
                     HostScanExec.from_table(fact_tbl),
                     HostScanExec.from_table(dim_tbl))
    assert j._build_unique()
    assert j._range_pack_spec() is not None
    out = j.collect(ctx)
    assert ctx.metrics.get("join_aligned_fastpath") == 1
    assert sorted(zip(out.column("v").to_pylist(),
                      out.column("m").to_pylist())) == [(1.0, 11),
                                                        (2.0, 12)]

    # stats stripped: no packing -> multi-lane -> sized path only
    dim_ns = HostScanExec.from_table(dim_tbl)
    dim_ns._source_table = None
    j2 = HashJoinExec("inner", keys, keys,
                      HostScanExec.from_table(fact_tbl), dim_ns)
    assert j2._range_pack_spec() is None
    ctx2 = ExecContext()
    out2 = j2.collect(ctx2)
    assert "join_aligned_fastpath" not in ctx2.metrics
    assert sorted(zip(out2.column("v").to_pylist(),
                      out2.column("m").to_pylist())) == [(1.0, 11),
                                                         (2.0, 12)]


def test_limit_lazy_path_shrinks_capacity():
    """LIMIT over one big batch must not ship the full input capacity to
    host: the lazy path slices lanes down to the limit's bucket."""
    from spark_rapids_tpu.exec.plan import LocalLimitExec
    n = 200_000
    scan = _scan({"x": pa.array(np.arange(n), pa.int64())})
    lim = LocalLimitExec(7, scan)
    ctx = ExecContext()
    outs = list(lim.execute(ctx))
    assert len(outs) == 1
    assert outs[0].capacity < n        # sliced, not full input capacity
    tbl = lim.collect()
    assert tbl.column("x").to_pylist() == list(range(7))


def test_topn_output_capacity_bounded():
    from spark_rapids_tpu.exec.plan import TopNExec
    n = 100_000
    scan = HostScanExec.from_table(
        pa.table({"x": pa.array(np.random.default_rng(0).permutation(n))}),
        max_rows=30_000)   # multi-batch stream
    top = TopNExec(5, [(0, True, True)], scan)
    outs = list(top.execute(ExecContext()))
    assert len(outs) == 1
    assert outs[0].capacity <= 1024    # bucket_capacity(5) at defaults
    assert top.collect().column("x").to_pylist() == [0, 1, 2, 3, 4]


# -- a small sorted build side is probed by comparison ----------------------

_BLK = J._CMP_BLOCK
_CAP = 1024             # the build's bucket in every case but three
_SMALL_CASES = {        # case -> (build rows, live keys)
    "live_0": (_CAP, 0), "live_1": (_CAP, 1),
    "live_blk-1": (_CAP, _BLK - 1), "live_blk": (_CAP, _BLK),
    "live_blk+1": (_CAP, _BLK + 1), "live_full": (_CAP, _CAP),
    "null_keys": (_CAP, 600), "masked_probe": (_CAP, 600),
    "negative": (_CAP, 600), "above_2_32": (_CAP, 600),
    "duplicates": (_CAP, 600),
    "live_4096": (4096, 4096),      # the largest bucket under the cap
    "over_live": (J.SMALL_BUILD_CAP + 1, J.SMALL_BUILD_CAP + 1),
    "over_cap": (2 * J.SMALL_BUILD_CAP, 600),
}


def _small_build_tables(case):
    """(probe, build) pyarrow tables of a case: single int64 keys spread
    over far more than 16 x the build's capacity, so no direct-address
    table is built and the build is a sorted lane.  The build's first
    `live` rows (w < 10 x live) pass the filter the join is given; a
    third of the probe keys are live build keys, -1 is among them (the
    dead rows' sentinel)."""
    rows, live = _SMALL_CASES[case]
    rng = np.random.default_rng(sorted(_SMALL_CASES).index(case))
    lo, hi = {"negative": (-2**40, 0),
              "above_2_32": (2**32, 2**62)}.get(case, (-2**40, 2**40))
    keys = np.unique(rng.integers(lo, hi, rows + 64))
    keys = rng.permutation(keys)[:rows].astype(np.int64)
    if case == "negative":
        keys[keys == -1] = -2        # -1 probes against dead rows only
    if case == "duplicates":
        keys[1:live:3] = keys[0:live - 1:3]
    bmask = (rng.random(rows) < 0.2) if case == "null_keys" else None
    build = pa.table({"k": pa.array(keys, pa.int64(), mask=bmask),
                      "w": pa.array(np.arange(rows, dtype=np.int64) * 10)})
    n = 3000
    pk = rng.integers(lo, hi, n)
    if live:
        hit = rng.random(n) < 0.34
        pk[hit] = keys[rng.integers(0, live, int(hit.sum()))]
    pk[:3] = [-1, -1, keys[0]]
    if case == "above_2_32":
        pk[3] = keys[0] & 0xFFFFFFFF          # equal in the low word only
    pmask = (rng.random(n) < 0.05) if case == "null_keys" else None
    probe = pa.table({"fk": pa.array(pk, pa.int64(), mask=pmask),
                      "v": pa.array(np.arange(n, dtype=np.int64))})
    return probe, build


def _rows(tbl, names):
    return sorted(zip(*[tbl.column(c).to_pylist() for c in names]),
                  key=repr)


@pytest.mark.parametrize("case,jt", [
    (case, jt) for case in sorted(_SMALL_CASES)
    for jt in ("inner", "left_outer", "left_semi", "left_anti")
    # duplicate build keys are not a unique build: inner and outer joins
    # take the sized path there
    if case != "duplicates" or jt in ("left_semi", "left_anti")])
def test_small_sorted_build_probe(case, jt):
    """The comparison lookup of a small sorted build side: the join equals
    pyarrow's and each probe equals the merge-rank path called directly,
    bit for bit.  A build over SMALL_BUILD_CAP rows of capacity, whether
    its keys are all live (over_live) or few (over_cap), merge-ranks and
    counts no comparison."""
    probe, build = _small_build_tables(case)
    rows, live = _SMALL_CASES[case]
    left = HostScanExec.from_table(probe)
    if case == "masked_probe":
        left = FilterExec(E.GreaterThan(E.ColumnRef("v"), E.Literal(700)),
                          left)
        probe = probe.filter(pa.compute.greater(probe["v"], 700))
    right = FilterExec(E.LessThan(E.ColumnRef("w"), E.Literal(10 * live)),
                       HostScanExec.from_table(build))
    j = HashJoinExec(jt, [E.ColumnRef("fk")], [E.ColumnRef("k")], left,
                     right)
    ctx = ExecContext()
    out = j.collect(ctx)
    assert "join_dense_domain" not in ctx.metrics
    small = case not in ("over_live", "over_cap")
    assert ctx.metrics.get("join.small_build_probes", 0) == int(small)
    oracle = probe.join(build.filter(pa.compute.less(build["w"], 10 * live)),
                        keys="fk", right_keys="k",
                        join_type=jt.replace("_", " "))
    names = ["fk", "v"] + (["w"] if jt in ("inner", "left_outer") else [])
    assert _rows(out, names) == _rows(oracle, names)

    # the probe itself, against the merge-rank path called directly
    bdb = next(iter(HostScanExec.from_table(build).execute(ExecContext())))
    pdb = next(iter(HostScanExec.from_table(probe).execute(ExecContext())))
    bt = J.BuildTable(bdb, [bdb.columns[0]], extra_valid=bdb.row_mask() &
                      (bdb.columns[1].data < 10 * live))
    assert bt.domain is None and J.compares_small_build(bt) == small
    assert (bt.capacity <= J.SMALL_BUILD_CAP) == small
    lanes = J.key_cols_lanes([pdb.columns[0]])
    mask = jnp.asarray(np.random.default_rng(live).random(pdb.capacity)
                       < 0.9)
    pvalid = pdb.row_mask() & pdb.columns[0].validity & mask
    args = (bt.perm, bt.sorted_hash, bt.valid_count,
            J.composite_hash(lanes), pvalid)
    pctx = ExecContext()
    if jt in ("inner", "left_outer"):
        idx, ok = J.probe_aligned(bt, lanes, pvalid, pctx)
        want_idx, want_ok = jax.jit(J._aligned_merge)(*args)
        assert np.array_equal(np.asarray(ok), np.asarray(want_ok))
        assert np.array_equal(np.asarray(idx)[np.asarray(ok)],
                              np.asarray(want_idx)[np.asarray(want_ok)])
    else:
        assert np.array_equal(
            np.asarray(J.probe_matched_lazy(bt, lanes, pvalid, pctx)),
            np.asarray(jax.jit(J._matched_merge)(*args)))
    assert pctx.metrics.get("join.small_build_probes", 0) == int(small)


def _whole_plan_join(s, probe, build):
    df = s.from_arrow(probe).join(s.from_arrow(build), left_on=["fk"],
                                  right_on=["k"], how="inner")
    return df.group_by("g").agg((Sum(E.ColumnRef("w")), "sw")).sort("g")


@pytest.mark.parametrize("dense", [False, True])
def test_small_build_probe_counter(dense):
    """`join.small_build_probes` counts the probes compiled with the
    comparison lookup once a program run, through a kept plan: one a probe
    batch for a small sorted build, 0 where the build keys have a dense
    domain (the join cell's q3 and q5 shapes)."""
    from spark_rapids_tpu.session import TpuSession
    rng = np.random.default_rng(38)
    keys = np.arange(500, dtype=np.int64) * (1 if dense else 2**31 + 11)
    build = pa.table({"k": keys, "w": np.arange(500, dtype=np.int64)})
    fk = keys[rng.integers(0, 500, 5000)]
    fk[::7] = -5                    # no build key
    probe = pa.table({"fk": fk, "g": np.arange(5000, dtype=np.int64) % 5})
    conf = {"spark.rapids.tpu.sql.compile.wholePlan": "ON",
            "spark.rapids.tpu.sql.batchSizeRows": "2048"}
    df = _whole_plan_join(TpuSession(conf), probe, build)
    cpu = _whole_plan_join(
        TpuSession({"spark.rapids.tpu.sql.enabled": "false"}), probe,
        build).collect()
    for _ in range(2):              # compiled, then through the kept plan
        assert df.collect().to_pydict() == cpu.to_pydict()
        m = df.metrics()
        assert m["whole_plan_compiled_queries"] == 1
        assert m.get("join.small_build_probes", 0) == (0 if dense else 3)
    assert m.get("plan.reused") == 1
