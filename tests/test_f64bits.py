"""``f64bits.add`` against the host FPU, bit for bit.

The lattice programs' (min,+) passes add f64 values as int64 bit
patterns so that a TPU, whose own f64 keeps ~48 significand bits,
returns exactly the host's answers.  Every regime the rounding logic
branches on is covered: aligned and far-apart exponents, cancellation,
subnormals, signed zeros and infinities.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  (jax_enable_x64)
from repro.core import f64bits

N = 20000


def _finite_bits(rng, k):
    bits = rng.integers(0, 2 ** 63 - 1, k, dtype=np.int64)
    exp_all_ones = ((bits >> 52) & 0x7FF) == 0x7FF
    bits = np.where(exp_all_ones, bits & ~(np.int64(1) << 62), bits)
    sign = rng.integers(0, 2, k).astype(np.int64) << 63
    return (bits | sign).view(np.float64)


def _pairs(regime, rng):
    if regime == "random_bits":
        return _finite_bits(rng, N), _finite_bits(rng, N)
    if regime == "magnitudes":
        s = rng.choice([-1.0, 1.0], (2, N))
        v = np.exp(rng.uniform(-5, 40, (2, N)))
        return s[0] * v[0], s[1] * v[1]
    if regime == "cancellation":
        a = np.exp(rng.uniform(0, 20, N))
        return a, -(a * (1 + rng.integers(-50, 50, N) * 2.0 ** -52))
    if regime == "far_exponents":
        a = np.exp(rng.uniform(0, 20, N))
        return a, a * 2.0 ** rng.integers(-60, 5, N)
    if regime == "subnormals":
        a = rng.integers(0, 2 ** 52, N).astype(np.int64).view(np.float64)
        b = rng.integers(0, 2 ** 53, N).astype(np.int64).view(np.float64)
        return a, b * rng.choice([-1.0, 1.0], N)
    special = np.array([0.0, -0.0, np.inf, -np.inf, 1.0, -1.0, 5e-324,
                        -5e-324, 2.2250738585072014e-308,
                        1.7976931348623157e308, -1.7976931348623157e308])
    a, b = np.meshgrid(special, special)
    return a.ravel(), b.ravel()


@pytest.mark.parametrize("regime", ["random_bits", "magnitudes",
                                    "cancellation", "far_exponents",
                                    "subnormals", "specials"])
def test_add_matches_host_fpu(regime):
    a, b = _pairs(regime, np.random.default_rng(len(regime)))
    got = f64bits.from_bits(jax.jit(f64bits.add)(
        jnp.asarray(f64bits.to_bits(a)), jnp.asarray(f64bits.to_bits(b))))
    with np.errstate(all="ignore"):
        want = a + b
    same = (got.view(np.int64) == want.view(np.int64)) | (
        np.isnan(got) & np.isnan(want))
    assert same.all(), (a[~same][:3], b[~same][:3], got[~same][:3])


def test_add_of_folded_constants():
    """Inputs the compiler can fold (the first (min,+) layer reads only
    constants) must come out the same as computed ones."""
    inf = jnp.int64(f64bits.INF)

    def first_layer():
        dp = jnp.where(jnp.arange(8) % 3 == 0, jnp.int64(0), inf)
        return f64bits.add(dp[jnp.array([0, 1, 3, 6])],
                           dp[jnp.array([3, 0, 6, 2])])

    got = f64bits.from_bits(jax.jit(first_layer)())
    assert got.tolist() == [0.0, np.inf, 0.0, np.inf]


def test_bits_round_trip_and_sign_ops():
    x = np.array([0.0, -0.0, 1.5, -2.25, np.inf, 1e-310])
    b = f64bits.to_bits(x)
    assert np.array_equal(f64bits.from_bits(b).view(np.int64), b)
    assert np.array_equal(
        f64bits.from_bits(f64bits.neg(jnp.asarray(b))).view(np.int64),
        (-x).view(np.int64))
    assert np.array_equal(f64bits.from_bits(f64bits.abs_(jnp.asarray(b))),
                          np.abs(x))
