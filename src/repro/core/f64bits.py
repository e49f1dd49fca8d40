"""IEEE-754 binary64 values carried as int64 bit patterns.

XLA:TPU has no native f64: it stores and computes an f64 array as a
pair of f32, which keeps about 48 of the 53 significand bits (on a TPU
v5e, copying 4096 random f64 values to the chip and back changed 3318 of
them).  Its 64-bit integer types are emulated exactly.  The lattice
programs whose answers must equal host f64 bit for bit therefore take,
hold and return f64 values as int64 bit patterns, and do the few f64
operations they need here, with the host's rounding (round to nearest,
ties to even).

Order: for values >= +0 (the (min,+) costs, cardinalities and +inf)
the bit patterns order as the values do, so ``jnp.minimum``, ``<=`` and
``argmin`` apply to the bits directly.

``add`` covers every sign, zeros, subnormals and infinities; inf - inf
gives the canonical quiet NaN.  NaN inputs are not supported.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

INF = 0x7FF0000000000000           # +inf
NAN = 0x7FF8000000000000           # the canonical quiet NaN
_FRAC = (1 << 52) - 1
_HIDDEN = 1 << 52


def to_bits(x) -> np.ndarray:
    """Host f64 values -> int64 bit patterns."""
    return np.ascontiguousarray(x, np.float64).view(np.int64)


def from_bits(b) -> np.ndarray:
    """int64 bit patterns (host or device array) -> host f64 values."""
    return np.ascontiguousarray(np.asarray(b), np.int64).view(np.float64)


def neg(a):
    """-a: flip the sign bit."""
    return a ^ jnp.int64(np.iinfo(np.int64).min)


def abs_(a):
    """|a|: clear the sign bit."""
    return a & jnp.int64(np.iinfo(np.int64).max)


def add(a, b):
    """fl(a + b) for int64 bit patterns (broadcasting), exactly as IEEE
    binary64 addition rounds it.  Plain int64 throughout: magnitudes and
    significands are non-negative, so arithmetic shifts act as logical
    ones (and XLA:CPU constant-folds a bitcast to uint64 wrongly)."""
    i64 = jnp.int64
    a, b = jnp.broadcast_arrays(jnp.asarray(a, i64), jnp.asarray(b, i64))
    mag = i64(np.iinfo(np.int64).max)
    swap = (b & mag) > (a & mag)               # order so that |x| >= |y|
    x = jnp.where(swap, b, a)
    y = jnp.where(swap, a, b)
    sx, sy = x < 0, y < 0
    ex, ey = (x & mag) >> 52, (y & mag) >> 52
    fx, fy = x & i64(_FRAC), y & i64(_FRAC)
    # significands with the hidden bit, 3 guard bits below; subnormals
    # have exponent field 0 but scale as exponent 1
    mx = jnp.where(ex > 0, fx | i64(_HIDDEN), fx) << 3
    my = jnp.where(ey > 0, fy | i64(_HIDDEN), fy) << 3
    ex = jnp.maximum(ex, 1)
    ey = jnp.maximum(ey, 1)
    d = ex - ey                                    # >= 0
    dc = jnp.minimum(d, 62)
    lost = my & ((i64(1) << dc) - 1)
    my = jnp.where(d >= 57, (my != 0).astype(i64),
                   (my >> dc) | (lost != 0).astype(i64))
    same = sx == sy
    m = jnp.where(same, mx + my, mx - my)          # in [0, 2^57)
    e = ex
    # an add may carry into bit 56: shift right once, keeping sticky
    carry = m >= (i64(1) << 56)
    m = jnp.where(carry, (m >> 1) | (m & 1), m)
    e = jnp.where(carry, e + 1, e)
    # a subtract may cancel: shift left to put the leading bit at 55,
    # no further than the smallest exponent allows (subnormal results)
    lead = 63 - lax.clz(m)
    want = jnp.where(m > 0, 55 - jnp.minimum(lead, 55), 0)
    sh = jnp.minimum(want, e - 1)
    m = m << sh
    e = e - sh
    # round to nearest, ties to even, on the 3 guard bits
    g = m & 7
    m = m >> 3
    up = (g > 4) | ((g == 4) & ((m & 1) == 1))
    m = m + up.astype(i64)
    over = m >= (i64(1) << 53)                     # rounding carried out
    m = jnp.where(over, m >> 1, m)
    e = jnp.where(over, e + 1, e)
    e_field = jnp.where(m >= _HIDDEN, e, 0)
    sign = jnp.where(sx, i64(np.iinfo(np.int64).min), i64(0))
    out = sign | (e_field << 52) | (m & i64(_FRAC))
    out = jnp.where(e_field >= 0x7FF, sign | i64(INF), out)
    # exact cancellation rounds to +0 (and -0 + -0 stays -0)
    out = jnp.where((m == 0) & ~(same & sx), i64(0), out)
    # infinities: inf + finite = inf; inf - inf = NaN
    out = jnp.where(ex == 0x7FF,
                    jnp.where((ey == 0x7FF) & ~same, i64(NAN), x), out)
    return out
