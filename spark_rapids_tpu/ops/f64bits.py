"""IEEE-754 binary64 results built in integer arithmetic, as bit patterns.

Spark casts a decimal to DOUBLE as its exact value rounded once, and
divides two doubles as IEEE-754 does.  Neither holds for f64 arithmetic on
the device: XLA rewrites a division by a constant (`u / 10**scale`) into a
multiplication by the constant's reciprocal, an ulp off for about one
value in seven on the CPU backend, and this TPU's f64 is a float32 pair
that keeps about 48 of the 53 bits (columnar/device.py).  These kernels
compute those two results from integer operations alone, round to nearest
even, and return the int64 bit pattern, which is DOUBLE's storage lane: the
same bits on every backend, on the host as `.view(np.float64)`.

Both reduce to one step, `_round_quotient`: the correctly rounded value of
num / den * 2**e2 for integers num and den normalized into [2**62, 2**63),
by restoring division (one quotient bit an iteration, 55 of them: the 53
of the significand, a guard bit and one more for the quotient's range),
the remainder as the sticky bit; subnormal results shift before they round.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_SIGN = np.int64(-2 ** 63)
_EXP_MASK = np.int64(0x7FF0000000000000)
_MANT_MASK = np.int64(0x000FFFFFFFFFFFFF)
_HIDDEN = np.int64(1 << 52)
_INF = np.int64(0x7FF0000000000000)
_NAN = np.int64(0x7FF8000000000000)
_QUOTIENT_BITS = 55


def _round_quotient(num: jax.Array, den: jax.Array, e2: jax.Array,
                    neg: jax.Array) -> jax.Array:
    """Bits of RN-even(num / den * 2**e2), negated where `neg`; `num` and
    `den` uint64 in [2**62, 2**63), so num / den lies in (1/2, 2)."""
    one = jnp.uint64(1)

    def step(_i, qr):
        q, r = qr
        ge = r >= den
        q = (q << one) | ge.astype(jnp.uint64)
        r = jnp.where(ge, r - den, r) << one      # < 2 * den < 2**64
        return q, r

    q, r = jax.lax.fori_loop(0, _QUOTIENT_BITS, step,
                             (jnp.zeros_like(num), num))
    sticky = r != 0
    # q = floor(num / den * 2**54) lies in [2**53, 2**55): keep 54 bits
    # (53 of significand, 1 guard), the bit below into the sticky
    wide = q >= (one << jnp.uint64(54))
    sticky = sticky | (wide & ((q & one) != 0))
    m54 = jnp.where(wide, q >> one, q)
    biased = e2.astype(jnp.int64) + jnp.where(wide, 1023, 1022)
    # a subnormal result: shift right to the exponent field's floor first
    shift = jnp.clip(1 - biased, 0, 63).astype(jnp.uint64)
    lost = m54 & ((one << shift) - one)
    m = m54 >> shift
    sticky = sticky | (lost != 0)
    mant = (m >> one).astype(jnp.int64)
    guard = (m & one) != 0
    up = guard & (sticky | ((mant & 1) != 0))
    mant = mant + up.astype(jnp.int64)
    # a carry out of the significand lands in the exponent field: right
    # for a normal (next binade) and a subnormal (smallest normal) alike
    bits = jnp.where(biased >= 1,
                     (jnp.maximum(biased, 1) << 52) + mant - _HIDDEN, mant)
    bits = jnp.where(biased >= 2047, _INF, jnp.minimum(bits, _INF))
    return jnp.where(neg, bits | _SIGN, bits)


def _clz64(x: jax.Array) -> jax.Array:
    return jax.lax.clz(x.astype(jnp.int64)).astype(jnp.int64)


def decimal_to_double_bits(unscaled: jax.Array, scale: int) -> jax.Array:
    """Bits of the double nearest unscaled / 10**scale (ties to even):
    Spark's cast of a decimal to DOUBLE, the exact value rounded once."""
    u = unscaled.astype(jnp.int64)
    neg = u < 0
    # |u| as uint64: -(-2**63) wraps to itself, whose uint64 is 2**63
    a = jnp.where(neg, -u, u).astype(jnp.uint64)
    lz = _clz64(a)
    num = jnp.where(lz == 0, a >> jnp.uint64(1),
                    a << jnp.clip(lz - 1, 0, 63).astype(jnp.uint64))
    d = 10 ** int(scale)
    d_lz = 64 - d.bit_length()
    den = jnp.uint64(d << (d_lz - 1))
    bits = _round_quotient(num, den, d_lz - lz, neg)
    return jnp.where(a == 0, jnp.int64(0), bits)


def _unpack(b: jax.Array):
    """-> (sign, normalized significand uint64 in [2**62, 2**63), exponent
    with value = significand / 2**62 * 2**exponent, is-zero, is-inf,
    is-nan) of finite-or-not f64 bit patterns."""
    neg = b < 0
    field = (b & _EXP_MASK) >> 52
    frac = b & _MANT_MASK
    zero = (field == 0) & (frac == 0)
    inf = (field == 2047) & (frac == 0)
    nan = (field == 2047) & (frac != 0)
    sig = jnp.where(field == 0, frac, frac | _HIDDEN)
    lz = _clz64(sig)
    norm = (sig << jnp.clip(lz - 1, 0, 63)).astype(jnp.uint64)
    exp = jnp.maximum(field, 1) - lz
    return neg, norm, exp, zero, inf, nan


def divide_double_bits(x: jax.Array, y: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """IEEE-754 x / y over f64 bit patterns -> (bits, y != 0).  Spark's
    Divide is null where the divisor is zero (either sign); those rows'
    bits are 0."""
    xs, xm, xe, xz, xi, xn = _unpack(x.astype(jnp.int64))
    ys, ym, ye, yz, yi, yn = _unpack(y.astype(jnp.int64))
    neg = xs ^ ys
    # both operands finite and nonzero: the rounded quotient
    finite = ~(xz | xi | xn | yz | yi | yn)
    safe_y = jnp.where(finite, ym, jnp.uint64(1 << 62))
    bits = _round_quotient(xm, safe_y, xe - ye, neg)
    signed_zero = jnp.where(neg, _SIGN, jnp.int64(0))
    signed_inf = jnp.where(neg, _INF | _SIGN, _INF)
    bits = jnp.where(xz | yi, signed_zero, bits)        # 0 / y, x / inf
    bits = jnp.where(xi, signed_inf, bits)              # inf / finite
    bits = jnp.where(xn | yn | (xi & yi), _NAN, bits)
    bits = jnp.where(yz, jnp.int64(0), bits)
    return bits, ~yz
