"""Decimal kernels over int64 unscaled lanes.

Reference: decimalExpressions.scala + DecimalUtils JNI (128-bit).  TPU has
no int128; this engine's decimal story (columnar/device.py):

  * decimal(p<=18) — one int64 unscaled lane, exact.
  * wider results  — still ONE int64 lane on device: arithmetic whose
    *result type* exceeds precision 18 stays on device when the operand
    types fit int64, with overflow-to-null detection; the host boundary
    widens to arrow decimal128.  Values beyond int64's ~9.2e18 unscaled
    range null out where Spark's 128-bit math would succeed — a documented
    deviation (docs/compatibility.md analogue) the same spirit as the
    reference's float-ordering notes.  Such a result is consumable by the
    operators above it like any int64 lane: comparisons (`having sum(x) >
    300`: the literal cast to the common decimal, exact), arithmetic,
    casts, further aggregates.  Host columns that *arrive* wider than
    int64 (true 128-bit data: a scan, or the output of an operator the
    planner placed on the CPU) carry a second (hi) lane and are not
    computed on device (tagged, CPU fallback).  Which of the two a
    reference reads is decided from where the column comes from in the
    plan (plan/overrides.py `PlanMeta.wide_host_columns`, which marks the
    references through `mark_device_decimals`), never from the type; at
    run time the batch says the same (`DeviceColumn.data_hi`).

Spark result-type rules (DecimalPrecision, allowPrecisionLoss=true):
  add/sub: s = max(s1,s2);          p = max(p1-s1, p2-s2) + s + 1
  mul:     s = s1+s2;               p = p1 + p2 + 1
  div:     s = max(6, s1+p2+1);     p = p1 - s1 + s2 + s
capped at 38 with scale reduction (min scale 6) on overflow.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as t

MAX_PRECISION = 38
MIN_ADJUSTED_SCALE = 6

POW10 = np.array([10 ** i for i in range(19)], dtype=np.int64)

#: largest int64-exact unscaled magnitude per precision (p <= 18)
def max_unscaled(p: int) -> int:
    return 10 ** min(p, 18) - 1


def _adjust(p: int, s: int) -> t.DecimalType:
    """Spark DecimalType.adjustPrecisionScale (allowPrecisionLoss)."""
    if p <= MAX_PRECISION:
        return t.DecimalType(p, s)
    int_digits = p - s
    min_scale = min(s, MIN_ADJUSTED_SCALE)
    adj_scale = max(MAX_PRECISION - int_digits, min_scale)
    return t.DecimalType(MAX_PRECISION, adj_scale)


def add_result(a: t.DecimalType, b: t.DecimalType) -> t.DecimalType:
    s = max(a.scale, b.scale)
    p = max(a.precision - a.scale, b.precision - b.scale) + s + 1
    return _adjust(p, s)


def mul_result(a: t.DecimalType, b: t.DecimalType) -> t.DecimalType:
    return _adjust(a.precision + b.precision + 1, a.scale + b.scale)


def div_result(a: t.DecimalType, b: t.DecimalType) -> t.DecimalType:
    s = max(6, a.scale + b.precision + 1)
    p = a.precision - a.scale + b.scale + s
    return _adjust(p, s)


def integral_as_decimal(dt: t.DataType) -> t.DecimalType:
    return {t.ByteType: t.DecimalType(3, 0), t.ShortType: t.DecimalType(5, 0),
            t.IntegerType: t.DecimalType(10, 0),
            t.LongType: t.DecimalType(20, 0)}[type(dt)]


# ---------------------------------------------------------------------------
# Device kernels (traced)
# ---------------------------------------------------------------------------

def upscale(u: jax.Array, ds: int) -> Tuple[jax.Array, jax.Array]:
    """u * 10^ds with int64-overflow detection -> (value, ok)."""
    if ds == 0:
        return u, jnp.ones(u.shape, bool)
    f = POW10[ds]
    out = u * jnp.int64(f)
    ok = jnp.abs(u) <= (jnp.int64(2 ** 63 - 1) // jnp.int64(f))
    return out, ok


def downscale_half_up(u: jax.Array, ds: int) -> jax.Array:
    """u / 10^ds rounding half away from zero (Spark HALF_UP)."""
    if ds == 0:
        return u
    f = jnp.int64(POW10[ds])
    half = f // 2
    mag = (jnp.abs(u) + half) // f
    return jnp.where(u < 0, -mag, mag)


def rescale(u: jax.Array, from_scale: int, to_scale: int
            ) -> Tuple[jax.Array, jax.Array]:
    """(value, ok): change of scale with overflow/rounding per Spark."""
    if to_scale >= from_scale:
        return upscale(u, to_scale - from_scale)
    return downscale_half_up(u, from_scale - to_scale), \
        jnp.ones(u.shape, bool)


def fits_precision(u: jax.Array, p: int) -> jax.Array:
    """ok mask: |u| representable in precision p (int64-capped)."""
    if p >= 19:
        return jnp.ones(u.shape, bool)
    return jnp.abs(u) <= jnp.int64(max_unscaled(p))


def add_dev(ua, sa, ub, sb, out: t.DecimalType):
    """Aligned add -> (unscaled, ok)."""
    va, ok_a = rescale(ua, sa, out.scale)
    vb, ok_b = rescale(ub, sb, out.scale)
    r = va + vb
    # int64 add overflow: same sign in, different sign out
    ovf = ((va >= 0) == (vb >= 0)) & ((r >= 0) != (va >= 0))
    ok = ok_a & ok_b & ~ovf & fits_precision(r, out.precision)
    return r, ok


def sub_dev(ua, sa, ub, sb, out: t.DecimalType):
    return add_dev(ua, sa, -ub, sb, out)


def mul_dev(ua, sa, ub, sb, out: t.DecimalType):
    """Product at scale sa+sb, then rescale to out.scale."""
    prod = ua * ub
    # overflow estimate via f64 magnitudes (exact int64 check is awkward;
    # 2^62 guard leaves a safety margin over f64's 53-bit mantissa error)
    est = jnp.abs(ua.astype(jnp.float64)) * jnp.abs(ub.astype(jnp.float64))
    ok = est < jnp.float64(2 ** 62)
    r, ok2 = rescale(prod, sa + sb, out.scale)
    return r, ok & ok2 & fits_precision(r, out.precision)


def cast_to_integral(u: jax.Array, scale: int) -> jax.Array:
    """decimal -> integral: truncate toward zero."""
    if scale == 0:
        return u
    f = jnp.int64(POW10[scale])
    mag = jnp.abs(u) // f
    return jnp.where(u < 0, -mag, mag)


def to_double(u: jax.Array, scale: int) -> jax.Array:
    return u.astype(jnp.float64) / jnp.float64(10 ** scale)


def from_double(x: jax.Array, out: t.DecimalType):
    """double -> decimal(p, s) with HALF_UP, null on overflow/NaN."""
    scaled = x.astype(jnp.float64) * jnp.float64(10 ** out.scale)
    finite = jnp.isfinite(scaled)
    bounded = jnp.abs(scaled) < jnp.float64(2 ** 62)
    safe = jnp.where(finite & bounded, scaled, 0.0)
    mag = jnp.floor(jnp.abs(safe) + 0.5)
    u = jnp.where(safe < 0, -mag, mag).astype(jnp.int64)
    ok = finite & bounded & fits_precision(u, out.precision)
    return u, ok


def from_integral(v: jax.Array, out: t.DecimalType):
    u, ok = upscale(v.astype(jnp.int64), out.scale)
    return u, ok & fits_precision(u, out.precision)
