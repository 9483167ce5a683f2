"""A DOUBLE built in integer arithmetic (ops/f64bits.py) is the IEEE-754
result bit for bit: Spark's cast of a decimal (the exact value rounded
once) and its division of two doubles, on random bit patterns across every
exponent, subnormals, zeros of both signs, infinities and NaN; and through
the engine, a projection's Cast and Divide leave with those bits."""
import decimal

import jax
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.ops.f64bits import (decimal_to_double_bits,
                                          divide_double_bits)
from spark_rapids_tpu.plan import expressions as E
from spark_rapids_tpu.session import TpuSession, col


def _patterns(rng, n):
    bits = rng.integers(-2**63, 2**63 - 1, n, dtype=np.int64)
    kind = rng.integers(0, 4, n)
    # a quarter subnormal, a quarter near 1.0, a quarter ordinary values
    sub = bits & np.uint64(0x800FFFFFFFFFFFFF).astype(np.int64)
    near = (bits & np.int64(-2**63)) | np.int64(0x3FF0000000000000) \
        | (bits & 0xFFFFF)
    plain = ((rng.random(n) - 0.5) * 1e6).view(np.int64)
    return np.select([kind == 0, kind == 1, kind == 2], [sub, near, plain],
                     bits)


def _same(got_bits, want):
    got = got_bits.view(np.float64)
    return ((got == want) & (np.signbit(got) == np.signbit(want))) | \
        (np.isnan(got) & np.isnan(want))


def test_divide_is_ieee_bit_for_bit():
    rng = np.random.default_rng(39)
    x, y = _patterns(rng, 50_000), _patterns(rng, 50_000)
    special = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -3.0,
                        5e-324, 1.7976931348623157e308]).view(np.int64)
    x = np.concatenate([x, np.repeat(special, len(special))])
    y = np.concatenate([y, np.tile(special, len(special))])
    bits, nonzero = (np.asarray(v) for v in
                     jax.jit(divide_double_bits)(x, y))
    with np.errstate(all="ignore"):
        want = x.view(np.float64) / y.view(np.float64)
    assert (nonzero == (y.view(np.float64) != 0)).all()
    assert _same(bits, want)[nonzero].all()
    assert (bits[~nonzero] == 0).all()


@pytest.mark.parametrize("scale", [0, 2, 7, 18])
def test_decimal_cast_is_the_exact_value_rounded_once(scale):
    rng = np.random.default_rng(scale)
    u = rng.integers(-2**63, 2**63 - 1, 20_000, dtype=np.int64)
    u >>= rng.integers(0, 63, len(u))
    u[:8] = [0, 1, -1, 2**53 + 1, -2**63, 2**63 - 1, 10**scale, 5]
    bits = np.asarray(jax.jit(
        lambda v: decimal_to_double_bits(v, scale))(u))
    want = np.array([int(v) / 10**scale for v in u.tolist()])
    assert (bits.view(np.float64) == want).all()


@pytest.mark.parametrize("engine", ["OFF", "ON"])
def test_cast_and_divide_of_decimal_sums_leave_with_ieee_bits(engine):
    """sum(p) / sum(s) as doubles: both casts rounded once and one
    division, as Python's int / int gives them, on both engines."""
    rng = np.random.default_rng(7)
    k = np.repeat(np.arange(60), 50)
    p = rng.integers(-2_000_000, 9_000_000, len(k))
    s = rng.integers(1, 9_000_000, len(k))
    dec = pa.decimal128(7, 2)
    table = pa.table({"k": k, "p": pa.array(
        [decimal.Decimal(int(v)).scaleb(-2) for v in p], dec),
        "s": pa.array([decimal.Decimal(int(v)).scaleb(-2) for v in s], dec)})
    from spark_rapids_tpu.plan.aggregates import Sum
    sess = TpuSession({"spark.rapids.tpu.sql.compile.wholePlan": engine})
    df = (sess.from_arrow(table).group_by("k")
          .agg((Sum(col("p")), "sp"), (Sum(col("s")), "ss")))
    df = df.select(col("k"), E.Divide(E.Cast(col("sp"), T.DOUBLE),
                                      E.Cast(col("ss"), T.DOUBLE)),
                   E.Cast(col("sp"), T.DOUBLE), names=["k", "m", "d"])
    out = df.sort("k").collect().to_pydict()
    sp = {g: int(p[k == g].sum()) for g in range(60)}
    ss = {g: int(s[k == g].sum()) for g in range(60)}
    assert out["d"] == [sp[g] / 100 for g in out["k"]]
    assert out["m"] == [(sp[g] / 100) / (ss[g] / 100) for g in out["k"]]
