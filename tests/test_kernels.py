"""Pallas kernels vs pure-jnp oracles (interpret mode, CPU).

Sweeps lattice sizes and dtypes; integer inputs must match bit-exactly
inside the documented exactness envelopes.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import zeta_op, mobius_op, ranked_conv_op
from repro.kernels.ref import zeta_ref, mobius_ref, ranked_conv_ref
from repro.kernels.zeta_pallas import zeta_pallas, mobius_pallas


@pytest.mark.parametrize("n", [4, 8, 11, 12, 14])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_zeta_kernel_matches_ref(n, dtype):
    rng = np.random.default_rng(n)
    f = jnp.asarray(rng.integers(-5, 6, 1 << n), dtype)
    assert np.array_equal(np.asarray(zeta_op(f)),
                          np.asarray(zeta_ref(f)))
    assert np.array_equal(np.asarray(mobius_op(f)),
                          np.asarray(mobius_ref(f)))


@pytest.mark.parametrize("n", [11, 13])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_zeta_kernel_roundtrip(n, dtype):
    rng = np.random.default_rng(n)
    f = jnp.asarray(rng.integers(-100, 100, 1 << n), dtype)
    assert np.array_equal(np.asarray(mobius_op(zeta_op(f))),
                          np.asarray(f))


@pytest.mark.parametrize("row_block", [8, 16, 64])
def test_zeta_kernel_block_shapes(row_block):
    n = 13
    rng = np.random.default_rng(row_block)
    f = jnp.asarray(rng.integers(0, 9, 1 << n), jnp.float32)
    out = zeta_pallas(f, row_block=row_block, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(zeta_ref(f)))


@pytest.mark.parametrize("n,k", [(12, 2), (12, 5), (12, 12), (14, 7)])
def test_ranked_conv_kernel(n, k):
    rng = np.random.default_rng(n * 100 + k)
    Z = jnp.asarray(rng.integers(0, 50, (n + 1, 1 << n)), jnp.float32)
    a = ranked_conv_op(Z, k)
    b = ranked_conv_ref(Z, k)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(1, 10), st.integers(0, 2 ** 31),
       st.sampled_from([jnp.float32, jnp.int32]))
@settings(max_examples=20, deadline=None)
def test_zeta_kernel_property(n, seed, dtype):
    """Small lattices fall back to ref; larger go through the kernel —
    both must equal the oracle for any input."""
    rng = np.random.default_rng(seed)
    f = jnp.asarray(rng.integers(-8, 9, 1 << n), dtype)
    assert np.array_equal(np.asarray(zeta_op(f)), np.asarray(zeta_ref(f)))


def test_kernel_integrates_with_feasibility_counts():
    """The f32 kernel reproduces one exact layered feasibility conv for a
    small n (counts < 2^24 envelope)."""
    from repro.core.bitset import popcounts
    from repro.core.zeta import zeta as zeta_xla
    n = 10
    rng = np.random.default_rng(0)
    ind = (rng.random(1 << n) < 0.3).astype(np.float32)
    pc = popcounts(n)
    Z = np.zeros((n + 1, 1 << n), np.float32)
    for d in range(n + 1):
        Z[d] = np.asarray(zeta_op(jnp.asarray(
            np.where(pc == d, ind, 0).astype(np.float32))))
    k = 6
    got = np.asarray(ranked_conv_op(jnp.asarray(Z), k))
    ref = np.asarray(ranked_conv_ref(jnp.asarray(Z), k))
    assert np.array_equal(got, ref)


def test_interpret_mode_is_asked_for_never_inferred(monkeypatch):
    """Interpret mode comes from the call or the process variable only;
    the backend (a CPU here) never turns it on."""
    from repro.kernels import ops
    monkeypatch.delenv(ops.INTERPRET_ENV, raising=False)
    assert ops._resolve(None) is False
    assert ops._resolve(True) is True
    monkeypatch.setenv(ops.INTERPRET_ENV, "1")
    assert ops._resolve(None) is True
    assert ops._resolve(False) is False
