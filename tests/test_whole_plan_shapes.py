"""Adversarial join, aggregate and compaction shapes on the engine the
chip runs.

Off the TPU `compile.wholePlan=AUTO` is the eager per-operator engine,
so most of tier-1 never walks the whole-plan programs of
exec/compiled.py.  Every query here runs `compile.wholePlan=ON` (one
program, or split at its seams with `compile.seamSplitMinRows=1`) and
must equal the CPU oracle (`spark.rapids.tpu.sql.enabled=false`)
exactly: collision-heavy and null join keys under all six join types,
duplicate and empty build sides, all-null probe keys, every group-by
strategy of exec/aggregate.py `elect_aggregate` — which both engines
must elect alike — and the compaction order against a numpy take.
"""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec.plan import ExecContext
from spark_rapids_tpu.ops.groupby import MASKED_DOMAIN_MAX
from spark_rapids_tpu.plan.aggregates import (BoolAnd, BoolOr, Count,
                                              First, Last, Max, Min, Sum)
from spark_rapids_tpu.session import DataFrame, TpuSession, col

WHOLE = {"spark.rapids.tpu.sql.compile.wholePlan": "ON"}
EAGER = {"spark.rapids.tpu.sql.compile.wholePlan": "OFF"}
#: the seam gate keeps plans this small in one program
SPLIT = {**WHOLE, "spark.rapids.tpu.sql.compile.seamSplitMinRows": "1"}
CPU = {"spark.rapids.tpu.sql.enabled": "false"}
STRATEGIES = ("reduce", "dense_masked", "dense", "packed_sort", "lexsort")


def _collect(df, conf, may_fall_back=False):
    """(result as a dict of lists, ctx.metrics) of `df`'s plan under
    `conf`; a whole-plan run must not have fallen back to the eager
    engine unless the caller expects it."""
    q = DataFrame(df._plan, TpuSession(conf)).physical()
    ctx = ExecContext(q.conf)
    got = q.collect(ctx).to_pydict()
    if conf is not EAGER and not may_fall_back:
        assert not ctx.metrics.get("whole_plan_fallbacks"), ctx.metrics
        assert ctx.metrics.get("whole_plan_compiled_queries") == 1
    return got, ctx.metrics


def _oracle(df):
    return DataFrame(df._plan, TpuSession(CPU)).collect().to_pydict()


def _strategies(metrics) -> set:
    """The strategies `HashAggregate._note` counted; it counts a
    `dense_masked` program under `dense` too."""
    named = {k for k in STRATEGIES if metrics.get(f"agg.strategy.{k}")}
    if metrics.get("agg.strategy.dense") == \
            metrics.get("agg.strategy.dense_masked"):
        named.discard("dense")
    return named


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

JOIN_TYPES = ("inner", "left_outer", "left_semi", "left_anti",
              "right_outer", "full_outer")


def _join_frames(s, key, n=5000, null_every=11, seed=3):
    """Collision-heavy keys: about 50 distinct over 5000 fact rows, every
    11th null, against a 60-row dimension; `i` numbers the fact rows."""
    rng = np.random.default_rng(seed)
    as_key = int if key == "int64" else (lambda v: f"k{v:02d}")
    kt = pa.int64() if key == "int64" else pa.string()
    fk = [None if i % null_every == 0 else as_key(int(v))
          for i, v in enumerate(rng.integers(0, 50, n))]
    fact = s.from_arrow(pa.table({
        "fk": pa.array(fk, kt),
        "i": pa.array(np.arange(n), pa.int64()),
        "v": pa.array(rng.standard_normal(n))}))
    dim = s.from_arrow(pa.table({
        "k": pa.array([as_key(i) for i in range(60)], kt),
        "name": pa.array([f"n{i}" for i in range(60)])}))
    return fact, dim


@pytest.mark.parametrize("split", ["one_program", "split"])
@pytest.mark.parametrize("key", ["int64", "dict_string"])
@pytest.mark.parametrize("how", JOIN_TYPES)
def test_join_shapes(how, key, split):
    """Every output row of the join is one group of the aggregate above
    it (grouped by all its columns, counted): the answer is the join's
    rows with their multiplicities, and the aggregate gives the plan
    its seams, so `split` resolves a probe-aligned join's selection
    vector and its null-extended deferred lanes in exec/compiled.py
    `_resolve_at` (a right or full outer join appends the unmatched
    build rows as a batch of their own: its seam output is dense and
    is sliced)."""
    s = TpuSession(WHOLE)
    fact, dim = _join_frames(s, key)
    joined = fact.join(dim, left_on=["fk"], right_on=["k"], how=how)
    keys = ["i", "fk"] if how in ("left_semi", "left_anti") \
        else ["i", "fk", "k", "name"]
    df = joined.group_by(*keys).agg(
        (Count(None), "rows"), (Min(col("v")), "v")) \
        .sort(*((k, True, True) for k in keys))
    got, metrics = _collect(df, SPLIT if split == "split" else WHOLE)
    assert got == _oracle(df)
    assert len(got["rows"]) > 50
    assert metrics.get("whole_plan_split_queries", 0) == \
        int(split == "split")
    if split == "split":
        assert metrics["overhead.seam_count"] == 2
        assert metrics.get("overhead.seam_lazy_count", 0) == \
            (0 if how in ("right_outer", "full_outer") else 1)


@pytest.mark.parametrize("how", ["inner", "left_outer"])
def test_join_duplicate_build_rows(how):
    """A build side with three rows a key: the sized expand path reads
    its pair count on the host, which no trace can, so today the
    whole-plan engine hands this plan to the eager one."""
    rng = np.random.default_rng(5)
    s = TpuSession(WHOLE)
    left = s.from_arrow(pa.table({
        "k": pa.array(rng.integers(0, 20, 997), pa.int64()),
        "x": pa.array(np.arange(997))}))
    right = s.from_arrow(pa.table({
        "k2": pa.array(np.repeat(np.arange(25), 3), pa.int64()),
        "y": pa.array(np.arange(75))}))
    df = left.join(right, left_on=["k"], right_on=["k2"], how=how) \
        .group_by("x", "y").agg((Count(None), "rows"),
                                (Max(col("k")), "k")) \
        .sort(("x", True, True), ("y", True, True))
    got, _m = _collect(df, WHOLE, may_fall_back=True)
    assert got == _oracle(df)
    assert len(got["rows"]) == 3 * 997 and set(got["rows"]) == {1}


@pytest.mark.parametrize("how", ["inner", "left_outer", "left_anti"])
def test_join_empty_build_side(how):
    s = TpuSession(WHOLE)
    left = s.from_arrow(pa.table({
        "k": pa.array([1, 2, 3], pa.int64()),
        "x": pa.array([1.0, 2.0, 3.0])}))
    right = s.from_arrow(pa.table({
        "k2": pa.array([], pa.int64()), "y": pa.array([], pa.int64())}))
    df = left.join(right, left_on=["k"], right_on=["k2"], how=how) \
        .sort(("x", True, True))
    got, _m = _collect(df, WHOLE)
    assert got == _oracle(df)
    assert len(got["x"]) == (0 if how == "inner" else 3)


@pytest.mark.parametrize("how", ["inner", "left_outer", "left_semi",
                                 "left_anti"])
def test_join_all_null_probe_keys(how):
    s = TpuSession(WHOLE)
    left = s.from_arrow(pa.table({
        "k": pa.array([None, None, None], pa.int64()),
        "x": pa.array([1, 2, 3])}))
    right = s.from_arrow(pa.table({
        "k2": pa.array([1, 2], pa.int64()), "y": pa.array([10, 20])}))
    df = left.join(right, left_on=["k"], right_on=["k2"], how=how) \
        .sort(("x", True, True))
    got, _m = _collect(df, WHOLE)
    assert got == _oracle(df)
    assert len(got["x"]) == (0 if how in ("inner", "left_semi") else 3)


# ---------------------------------------------------------------------------
# aggregates
# ---------------------------------------------------------------------------

def _agg_frame(s, n=4096):
    rng = np.random.default_rng(11)
    return s.from_arrow(pa.table({
        "flag": pa.array([["A", "B", "C", None][i % 4]
                          for i in range(n)]),
        "qty": pa.array(rng.integers(-(10 ** 12), 10 ** 12, n),
                        pa.int64()),
        "price": pa.array(rng.standard_normal(n))}))


def _sums(s):
    return _agg_frame(s).group_by("flag").agg(
        (Sum(col("qty")), "sq"), (Min(col("qty")), "mn"),
        (Max(col("qty")), "mx"), (Sum(col("price")), "sp"),
        (Count(col("qty")), "c")).sort(("flag", True, True))


def _first_last(s, n=2048):
    tbl = pa.table({
        "g": pa.array([i % 5 for i in range(n)], pa.int64()),
        "b": pa.array([i % 3 == 0 for i in range(n)]),
        "v": pa.array([None if i % 7 == 0 else i for i in range(n)],
                      pa.int64())})
    return s.from_arrow(tbl).group_by("g").agg(
        (First(col("v")), "f"), (Last(col("v")), "l"),
        (BoolOr(col("b")), "anyb"), (BoolAnd(col("b")), "allb"),
        (Count(col("v")), "c")).sort(("g", True, True))


def _wide_dictionary(s, codes=MASKED_DOMAIN_MAX, n=6000):
    """A string key of `codes` distinct values: with the null slot its
    domain is one bucket past MASKED_DOMAIN_MAX, the scatter
    realisation of the dense group-by."""
    rng = np.random.default_rng(13)
    words = [f"w{i:04d}" for i in range(codes)]
    picks = np.concatenate([np.arange(codes),
                            rng.integers(0, codes, n - codes)])
    return s.from_arrow(pa.table({
        "w": pa.array([None if i % 97 == 0 else words[p]
                       for i, p in enumerate(picks)]),
        "x": pa.array(rng.integers(-1000, 1000, n), pa.int64())})) \
        .group_by("w").agg((Sum(col("x")), "sx"), (Count(None), "c"),
                           (Max(col("x")), "mx")) \
        .sort(("w", True, True))


def _tpch_q1(s):
    from spark_rapids_tpu import tpch
    return tpch.QUERIES["q1"](s, tpch.gen_tables(scale=0.001))


AGG_SHAPES = {
    # (query, the strategy of its programs, columns compared to 1e-12)
    "int_sums_exact_float_sums_close": (_sums, "dense_masked", ("sp",)),
    "first_last_any_every": (_first_last, "packed_sort", ()),
    "domain_above_masked_max": (_wide_dictionary, "dense", ()),
    "tpch_q1": (_tpch_q1, "dense_masked",
                ("sum_disc_price", "sum_charge", "avg_qty", "avg_price",
                 "avg_disc")),
}


@pytest.mark.parametrize("shape", sorted(AGG_SHAPES))
def test_agg_shapes(shape):
    build, strategy, close = AGG_SHAPES[shape]
    df = build(TpuSession(WHOLE))
    got, metrics = _collect(df, WHOLE)
    want = _oracle(df)
    assert set(got) == set(want) and len(got) > len(close)
    for name in got:
        if name in close:
            assert got[name] == pytest.approx(want[name], rel=1e-12), name
        else:
            assert got[name] == want[name], name
    assert _strategies(metrics) == {strategy}, metrics


def _keyless(s):
    return _agg_frame(s).agg((Sum(col("qty")), "sq"), (Count(None), "c"))


def _small_dictionary(s):
    return _agg_frame(s).group_by("flag").agg(
        (Sum(col("qty")), "sq"), (Count(col("qty")), "c")) \
        .sort(("flag", True, True))


def _packable_ints(s, n=3000):
    rng = np.random.default_rng(17)
    return s.from_arrow(pa.table({
        "a": pa.array(rng.integers(0, 40, n), pa.int64()),
        "b": pa.array(rng.integers(-5, 5, n), pa.int64()),
        "x": pa.array(rng.integers(0, 100, n), pa.int64())})) \
        .group_by("a", "b").agg((Sum(col("x")), "sx")) \
        .sort(("a", True, True), ("b", True, True))


def _unpackable_tuple(s, n=3000):
    rng = np.random.default_rng(19)
    return s.from_arrow(pa.table({
        "a": pa.array(rng.integers(0, 40, n), pa.int64()),
        "d": pa.array(rng.integers(0, 4, n) / 4.0),
        "x": pa.array(rng.integers(0, 100, n), pa.int64())})) \
        .group_by("a", "d").agg((Sum(col("x")), "sx")) \
        .sort(("a", True, True), ("d", True, True))


ELECTIONS = {
    "no_keys": (_keyless, "reduce"),
    "small_dictionary": (_small_dictionary, "dense_masked"),
    "dictionary_over_1024_codes": (_wide_dictionary, "dense"),
    "packable_integer_keys": (_packable_ints, "packed_sort"),
    "unpackable_key_tuple": (_unpackable_tuple, "lexsort"),
}


@pytest.mark.parametrize("shape", sorted(ELECTIONS))
def test_both_engines_elect_the_same_strategy(shape):
    """exec/aggregate.py `elect_aggregate` is the one place an
    aggregate's program is chosen; the eager engine (`_run_groupby`)
    and the whole-plan one (`HashAggregate.partial_fused`) find its
    inputs differently and must come to the same strategy."""
    build, strategy = ELECTIONS[shape]
    df = build(TpuSession(WHOLE))
    whole, m_whole = _collect(df, WHOLE)
    eager, m_eager = _collect(df, EAGER)
    assert not m_eager.get("whole_plan_compiled_queries")
    assert _strategies(m_whole) == _strategies(m_eager) == {strategy}
    assert whole == eager == _oracle(df)


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("capacity,selectivity", [
    (1024, 0.5), (4096, 0.03), (4097, 0.5), (2048, 0.0), (2048, 1.0)])
def test_compaction_matches_numpy(capacity, selectivity):
    """ops/filter.py `compact_batch` on a dense batch and
    columnar/lanes.py `compact_thin` on one with a deferred column, full
    and cut to `out_capacity`: the kept rows, in order, as numpy takes
    them."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.columnar.device import DeviceBatch, DeviceColumn
    from spark_rapids_tpu.columnar.lanes import (LaneSource, ThinState,
                                                 compact_thin,
                                                 deferred_column)
    from spark_rapids_tpu.ops.filter import compact_batch
    rng = np.random.default_rng(int(capacity * 1000 + selectivity * 10))
    keep = rng.random(capacity) < selectivity
    kept = np.flatnonzero(keep)
    ids = np.arange(capacity, dtype=np.int64)
    vals = rng.standard_normal(capacity)
    valid = rng.random(capacity) < 0.9
    src_vals = rng.integers(0, 1 << 40, 64)
    lane = rng.integers(0, 64, capacity).astype(np.int32)

    def dense():
        return [DeviceColumn(jnp.asarray(ids), jnp.ones(capacity, bool),
                             t.LongType()),
                DeviceColumn(jnp.asarray(vals), jnp.asarray(valid),
                             t.DoubleType())]

    def check(out, cap, deferred):
        n = min(len(kept), cap)
        assert out.capacity == cap and int(out.num_rows) == n
        at = kept[:n]
        np.testing.assert_array_equal(
            np.asarray(out.columns[0].data)[:n], ids[at])
        np.testing.assert_array_equal(
            np.asarray(out.columns[0].validity), np.arange(cap) < n)
        np.testing.assert_array_equal(
            np.asarray(out.columns[1].validity)[:n], valid[at])
        ok = valid[at]
        np.testing.assert_array_equal(
            np.asarray(out.columns[1].data)[:n][ok], vals[at][ok])
        if deferred:
            np.testing.assert_array_equal(
                np.asarray(out.columns[2].data)[:n], src_vals[lane[at]])

    cut = max(capacity // 2, 1) if len(kept) <= capacity // 2 \
        else capacity
    db = DeviceBatch(dense(), jnp.int32(capacity), ["id", "v"])
    check(compact_batch(db, jnp.asarray(keep)), capacity, False)
    check(compact_batch(db, jnp.asarray(keep), out_capacity=cut), cut,
          False)
    src = DeviceBatch([DeviceColumn(jnp.asarray(src_vals),
                                    jnp.ones(64, bool), t.LongType())],
                      64, ["payload"])
    thin = DeviceBatch(
        dense() + [deferred_column(src.columns[0])],
        jnp.int32(capacity), ["id", "v", "payload"],
        thin=ThinState(capacity, [LaneSource(src, jnp.asarray(lane))],
                       {2: (0, 0)}))
    check(compact_thin(thin, jnp.asarray(keep)), capacity, True)
    check(compact_thin(thin, jnp.asarray(keep), out_capacity=cut), cut,
          True)
