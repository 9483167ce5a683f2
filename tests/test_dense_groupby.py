"""Dense bounded-domain groupby (ops/groupby.py dense_groupby_trace).

Pins the contract directly: on fuzzed null-heavy inputs the dense path
must produce the same GROUP MULTISET as the generic sorted path for every
aggregate kind, and the eligibility gates must flip exactly at the
domain budget."""
import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as t
from spark_rapids_tpu.exec.aggregate import (_DENSE_DOMAIN_MAX,
                                             _dense_domains)
from spark_rapids_tpu.columnar.device import DeviceColumn
from spark_rapids_tpu.ops import groupby as G


def _run(trace_fn, keys, kvalid, data, dvalid, live):
    out_keys, outs, ng = jax.jit(trace_fn)(
        tuple(keys), tuple(kvalid), tuple(data), tuple(dvalid), live)
    n = int(ng)
    rows = {}
    nkeys = len(out_keys)
    kd = [np.asarray(k[0])[:n] for k in out_keys]
    kv = [np.asarray(k[1])[:n] for k in out_keys]
    for i in range(n):
        key = tuple(int(kd[j][i]) if kv[j][i] else None
                    for j in range(nkeys))
        vals = []
        for data_o, valid_o in outs:
            v = np.asarray(data_o)[:n][i]
            ok = bool(np.asarray(valid_o)[:n][i])
            vals.append(v.item() if ok else None)
        rows[key] = vals
    return n, rows


SPEC_SETS = [
    [G.AggSpec(G.SUM, 0, t.LongType()), G.AggSpec(G.COUNT, 0, t.LongType()),
     G.AggSpec(G.COUNT_ALL, -1, t.LongType())],
    [G.AggSpec(G.MIN, 0, t.LongType()), G.AggSpec(G.MAX, 0, t.LongType()),
     G.AggSpec(G.FIRST, 0, t.LongType()),
     G.AggSpec(G.LAST_NN, 0, t.LongType())],
    [G.AggSpec(G.SUM, 0, t.DoubleType()),
     G.AggSpec(G.MIN, 0, t.DoubleType())],
]


# the kinds the three sets above leave out
NEW_KIND_SETS = [
    [G.AggSpec(G.ANY, 0, t.BooleanType()),
     G.AggSpec(G.EVERY, 0, t.BooleanType()),
     G.AggSpec(G.MIN, 0, t.BooleanType())],
    [G.AggSpec(G.FIRST_NN, 0, t.LongType()),
     G.AggSpec(G.LAST, 0, t.LongType())],
    [G.AggSpec(G.MAX, 0, t.DoubleType()),       # with NaNs among the rows
     G.AggSpec(G.MIN, 0, t.DoubleType())],
    [G.AggSpec(G.SUM, 0, t.DecimalType(38, 6))],        # total over 2^53
]
MASKED_SPEC_SETS = SPEC_SETS + NEW_KIND_SETS

# (dom1, dom2) -> D = (dom1 + 1) * (dom2 + 1): Q1's 12 buckets, a domain
# at the threshold and the first one above it
_AT = G.MASKED_DOMAIN_MAX
DOMAINS = [(5, 3), (3, 2), (_AT // 2 - 1, 1), (_AT // 2, 1)]


def _inputs(specs, seed, dom1, dom2, cap, live):
    rng = np.random.default_rng(seed)
    k1 = jnp.asarray(rng.integers(0, dom1, cap).astype(np.int32))
    k2 = jnp.asarray(rng.integers(0, dom2, cap).astype(np.int32))
    kv1 = jnp.asarray(rng.random(cap) < 0.85)
    kv2 = jnp.asarray(rng.random(cap) < 0.9)
    dt = specs[0].dtype
    if isinstance(dt, t.DoubleType):
        d = rng.normal(size=cap)
        if specs[0].kind == G.MAX:
            d[rng.random(cap) < 0.02] = np.nan
        d = jnp.asarray(d)
    elif isinstance(dt, t.BooleanType):
        d = jnp.asarray(rng.random(cap) < 0.7)
    elif isinstance(dt, t.DecimalType):
        # about 2^48 a row: a bucket's total passes 2^53, where a float64
        # accumulator would round
        d = jnp.asarray(rng.integers(1 << 47, 1 << 49, cap)
                        .astype(np.int64) | 1)
    else:
        d = jnp.asarray(rng.integers(-50, 50, cap).astype(np.int64))
    dv = jnp.asarray(rng.random(cap) < 0.8)
    return [k1, k2], [kv1, kv2], [d], [dv], jnp.asarray(live)


def _assert_dense_matches_generic(specs, dom1, dom2, args, cap):
    info = [(t.IntegerType(), True, "int32")] * 2
    n_a, rows_a = _run(G.groupby_trace(info, specs, cap, cap), *args)
    n_b, rows_b = _run(G.dense_groupby_trace([dom1, dom2], specs, cap),
                       *args)
    assert n_a == n_b
    assert set(rows_a) == set(rows_b)
    for key in rows_a:
        for va, vb in zip(rows_a[key], rows_b[key]):
            if isinstance(va, float) and isinstance(vb, float):
                assert (np.isnan(va) and np.isnan(vb)) or \
                    abs(va - vb) <= 1e-9 * max(1.0, abs(va), abs(vb)), \
                    (key, va, vb)
            else:
                assert va == vb, (key, va, vb)
    return n_b, rows_b


@pytest.mark.parametrize("specs", SPEC_SETS)
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("dom1,dom2", DOMAINS)
def test_dense_matches_generic(specs, seed, dom1, dom2):
    """Both realisations against the sorted group-by: the domains lie on
    both sides of MASKED_DOMAIN_MAX by their sizes alone."""
    assert G.dense_is_masked((dom1, dom2)) == \
        ((dom1 + 1) * (dom2 + 1) <= G.MASKED_DOMAIN_MAX)
    cap = 4096
    args = _inputs(specs, seed, dom1, dom2, cap, np.arange(cap) < 3600)
    _assert_dense_matches_generic(specs, dom1, dom2, args, cap)


def test_the_threshold_is_between_the_parametrised_domains():
    assert [G.dense_is_masked(d) for d in DOMAINS] == [True, True, True,
                                                       False]
    assert G.dense_domain(DOMAINS[2]) == G.MASKED_DOMAIN_MAX
    assert G.dense_domain((3, 2)) == 12             # Q1's (3 + 1) x (2 + 1)


@pytest.mark.parametrize("specs", NEW_KIND_SETS)
def test_masked_kinds_match_generic(specs):
    cap = 4096
    live = np.random.default_rng(9).random(cap) < 0.9   # not a prefix
    args = _inputs(specs, 2, 3, 2, cap, live)
    _n, rows = _assert_dense_matches_generic(specs, 3, 2, args, cap)
    if isinstance(specs[0].dtype, t.DecimalType):
        assert max(v[0] for v in rows.values() if v[0]) > 1 << 53
        # and against Python's own integers, row by row
        k1, k2, kv1, kv2, d, dv, lv = (
            np.asarray(x) for x in jax.tree_util.tree_leaves(args))
        want = {}
        for i in np.flatnonzero(lv & dv):
            key = (int(k1[i]) if kv1[i] else None,
                   int(k2[i]) if kv2[i] else None)
            want[key] = want.get(key, 0) + int(d[i])
        assert {k: v[0] for k, v in rows.items() if v[0] is not None} \
            == want


@pytest.mark.parametrize("specs", MASKED_SPEC_SETS)
@pytest.mark.parametrize("shape", ["all_dead", "one_bucket", "null_keys"])
def test_masked_edge_batches(specs, shape):
    """No live row at all (`num_groups` 0), every live row in one
    bucket, and rows whose keys are all null (the null slots' bucket)."""
    cap = 1024
    live = np.zeros(cap, bool) if shape == "all_dead" \
        else np.arange(cap) % 3 != 0
    keys, kvalid, data, dvalid, live = _inputs(specs, 4, 3, 2, cap, live)
    if shape == "one_bucket":
        keys = [jnp.full((cap,), 2, jnp.int32), jnp.zeros((cap,), jnp.int32)]
        kvalid = [jnp.ones((cap,), bool)] * 2
    elif shape == "null_keys":
        kvalid = [jnp.zeros((cap,), bool)] * 2
    args = (keys, kvalid, data, dvalid, live)
    n, rows = _assert_dense_matches_generic(specs, 3, 2, args, cap)
    assert n == (0 if shape == "all_dead" else 1)
    if shape == "one_bucket":
        assert list(rows) == [(2, 0)]
    elif shape == "null_keys":
        assert list(rows) == [(None, None)]


@pytest.mark.parametrize("trace", ["dense_masked", "dense_scatter",
                                   "sorted", "sorted_scatter"])
def test_ignore_nulls_first_last_of_a_group_without_a_valid_row(
        trace, monkeypatch):
    """Group 1 holds only null values: its FIRST_NN / LAST_NN are null,
    not the row that an out-of-range pick clips onto (row 0 here, valid,
    of group 0)."""
    cap = 8
    specs = [G.AggSpec(G.LAST_NN, 0, t.LongType()),
             G.AggSpec(G.FIRST_NN, 0, t.LongType())]
    if trace.startswith("dense"):
        if trace == "dense_scatter":
            monkeypatch.setattr(G, "MASKED_DOMAIN_MAX", 0)
        fn = G.dense_groupby_trace([2], specs, cap)
    else:
        fn = G.groupby_trace([(t.IntegerType(), True, "int32")], specs, cap,
                             cap, scatter_free=trace == "sorted")
    n, rows = _run(fn, [jnp.asarray([0, 0, 1, 1, 0, 0, 0, 0], jnp.int32)],
                   [jnp.ones(cap, bool)],
                   [jnp.asarray([7, 8, 9, 10, 0, 0, 0, 0], jnp.int64)],
                   [jnp.asarray([1, 1, 0, 0, 0, 0, 0, 0], bool)],
                   jnp.arange(cap) < 4)
    assert (n, rows) == (2, {(0,): [8, 7], (1,): [None, None]})


def test_dense_domain_budget_gate():
    def col(n_dict):
        d = pa.array([f"v{i}" for i in range(n_dict)], pa.string())
        return DeviceColumn(jnp.zeros(8, jnp.int32), jnp.ones(8, bool),
                            t.STRING, d)
    # (size+1) must stay within the budget
    ok = _dense_domains([col(_DENSE_DOMAIN_MAX - 1)])
    assert ok == [_DENSE_DOMAIN_MAX - 1]
    assert _dense_domains([col(_DENSE_DOMAIN_MAX)]) is None
    # bool + small string mixes
    bool_col = DeviceColumn(jnp.zeros(8, jnp.int32), jnp.ones(8, bool),
                            t.BOOLEAN)
    assert _dense_domains([bool_col, col(10)]) == [2, 10]
    # unbounded (plain int) keys are ineligible
    int_col = DeviceColumn(jnp.zeros(8, jnp.int64), jnp.ones(8, bool),
                           t.LONG)
    assert _dense_domains([int_col]) is None


def test_fused_dense_falls_back_on_duplicate_dictionary():
    from spark_rapids_tpu.exec.aggregate import HashAggregate
    from spark_rapids_tpu.columnar.device import DeviceBatch
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.plan import expressions as E
    from spark_rapids_tpu.plan.aggregates import Count
    dup = pa.array(["a", "b", "a"], pa.string())
    col_ = DeviceColumn(jnp.zeros(8, jnp.int32), jnp.ones(8, bool),
                        t.STRING, dup)
    db = DeviceBatch([col_], 3, ["k"])
    schema = t.StructType([t.StructField("k", t.STRING)])
    agg = HashAggregate([E.ColumnRef("k").bind(schema)], ["k"],
                        [(Count(None).bind(schema), "n")], TpuConf())
    assert not agg.can_fuse_filter(db)     # dup dictionary -> no fuse
    assert agg.can_fuse_filter(None) is False
