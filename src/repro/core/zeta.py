"""Zeta and Moebius transforms over the subset lattice (paper Sec. 4).

Three implementations, all exact:

1. ``zeta`` / ``mobius`` — Yates' butterfly (Lst. 1 of the paper), vectorized:
   pass ``j`` reshapes the lattice to (high, 2, low) and adds the bit-j=0
   hyperplane into the bit-j=1 hyperplane.  O(2^n n) adds, VPU-friendly.

2. ``zeta_matmul`` / ``mobius_matmul`` — the TPU-native kron form.  The zeta
   transform is multiplication by Z^{⊗n} with Z = [[1,0],[1,1]].  Viewing f as
   a (2^h, 2^l) matrix, ζf = Z^{⊗h} · F · (Z^{⊗l})^T: two dense matmuls that
   run on the MXU instead of n strided vector passes.  The Moebius transform
   uses the inverse factor Z^{-1} = [[1,0],[-1,1]].

   This is the hardware adaptation of the paper's C++ bit-loop (DESIGN.md):
   same O-count arithmetic re-blocked into systolic-friendly GEMMs.

3. The Pallas kernels (see ``repro.kernels``): the same butterflies inside
   VMEM tiles (lane and sublane rotates), then one pass per block bit.

All functions operate on the LAST axis of an arbitrarily-batched array, so
ranked tables (n+1, 2^n) transform in one call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _n_of(size: int) -> int:
    n = int(size).bit_length() - 1
    if (1 << n) != size:
        raise ValueError(f"lattice size {size} is not a power of two")
    return n


# ----------------------------------------------------------------- butterfly
def _butterfly(f: jnp.ndarray, sign: float) -> jnp.ndarray:
    size = f.shape[-1]
    n = _n_of(size)
    batch = f.shape[:-1]
    s = jnp.asarray(sign, f.dtype)       # integer tables stay integer
    for j in range(n):
        g = f.reshape(batch + (size // (2 << j), 2, 1 << j))
        g = g.at[..., 1, :].add(s * g[..., 0, :])
        f = g.reshape(batch + (size,))
    return f


@jax.jit
def zeta(f: jnp.ndarray) -> jnp.ndarray:
    """(ζf)(S) = Σ_{T ⊆ S} f(T), on the last axis."""
    return _butterfly(f, 1.0)


@jax.jit
def mobius(f: jnp.ndarray) -> jnp.ndarray:
    """(μf)(S) = Σ_{T ⊆ S} (-1)^{|S\\T|} f(T); inverse of ``zeta``."""
    return _butterfly(f, -1.0)


# -------------------------------------------------------------- kron matmul
@functools.lru_cache(maxsize=32)
def _kron_factor(bits: int, inverse: bool) -> np.ndarray:
    """Z^{⊗bits} (or its inverse) as a dense (2^bits, 2^bits) matrix.

    M[a, b] = 1 iff b ⊆ a (zeta);  inverse has sign (-1)^{|a\\b|}.
    """
    size = 1 << bits
    a = np.arange(size)[:, None]
    b = np.arange(size)[None, :]
    subset = (a & b) == b
    if not inverse:
        return subset.astype(np.float64)
    diff = a & ~b
    signs = (-1.0) ** np.vectorize(lambda x: bin(x).count("1"))(diff)
    return np.where(subset, signs, 0.0)


@functools.partial(jax.jit, static_argnames=("inverse", "split"))
def _kron_transform(f: jnp.ndarray, inverse: bool = False,
                    split: int | None = None) -> jnp.ndarray:
    size = f.shape[-1]
    n = _n_of(size)
    if split is None:
        split = n // 2
    lo_bits, hi_bits = split, n - split
    m_lo = jnp.asarray(_kron_factor(lo_bits, inverse), dtype=f.dtype)
    m_hi = jnp.asarray(_kron_factor(hi_bits, inverse), dtype=f.dtype)
    batch = f.shape[:-1]
    g = f.reshape(batch + (1 << hi_bits, 1 << lo_bits))
    # index S = hi * 2^lo + lo  ->  row-major (hi, lo)
    g = jnp.einsum("Hh,...hl->...Hl", m_hi, g)
    g = jnp.einsum("Ll,...hl->...hL", m_lo, g)
    return g.reshape(batch + (size,))


def zeta_matmul(f: jnp.ndarray, split: int | None = None) -> jnp.ndarray:
    """MXU-native zeta transform (two kron-factor GEMMs)."""
    return _kron_transform(f, inverse=False, split=split)


def mobius_matmul(f: jnp.ndarray, split: int | None = None) -> jnp.ndarray:
    """MXU-native Moebius transform."""
    return _kron_transform(f, inverse=True, split=split)


# ------------------------------------------------------------ numpy oracles
def zeta_np(f: np.ndarray) -> np.ndarray:
    """Reference O(3^n) definition — test oracle only (small n!)."""
    size = f.shape[-1]
    out = np.zeros_like(f)
    for s in range(size):
        t = s
        acc = f[..., 0] * 0
        while True:
            acc = acc + f[..., t]
            if t == 0:
                break
            t = (t - 1) & s
        out[..., s] = acc
    return out


def mobius_np(f: np.ndarray) -> np.ndarray:
    size = f.shape[-1]
    out = np.zeros_like(f)
    for s in range(size):
        t = s
        acc = f[..., 0] * 0
        while True:
            sign = -1.0 if bin(s & ~t).count("1") % 2 else 1.0
            acc = acc + sign * f[..., t]
            if t == 0:
                break
            t = (t - 1) & s
        out[..., s] = acc
    return out
