"""Fused layer-k ranked convolution kernel (paper Eq. 11 + Sec. 5.2).

The layered DP computes, per layer k,

    acc(S) = Σ_{d=1}^{k-1} Z[d](S) · Z[k-d](S)
           = 2 Σ_{d<k/2} Z[d](S) Z[k-d](S)  (+ Z[k/2]^2 if k even)

over the full 2^n lattice.  Evaluated naively this is k-1 separate
multiply-add passes over HBM; the kernel fuses them: each grid program
loads a lattice tile of ALL rank slices once into VMEM and accumulates the
banded product in registers — one HBM read of the (n+1, 2^n) table and one
write of (2^n,) per layer, instead of k reads.

VMEM budget: (n+1) · TILE · 4B; TILE = 8 rows × 256 lanes = 2048 floats
→ ≤ 27 · 8 KiB ≈ 216 KiB for n = 26.  MXU is not used — this stage is
memory-bound by design (roofline: bytes/flop = 2 per multiply-add).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.zeta_pallas import LANES, int32_index

SUBLANES = 8
TILE = SUBLANES * LANES


def _ranked_conv_kernel(z_ref, o_ref, *, k: int):
    acc = jnp.zeros(z_ref.shape[1:], z_ref.dtype)
    for d in range(1, (k - 1) // 2 + 1):
        acc = acc + z_ref[d] * z_ref[k - d]
    acc = acc * jnp.array(2, z_ref.dtype)
    if k % 2 == 0:
        acc = acc + z_ref[k // 2] * z_ref[k // 2]
    o_ref[...] = acc


def ranked_conv_pallas(Z: jnp.ndarray, k: int,
                       interpret: bool = False) -> jnp.ndarray:
    """Z: (n+1, ..., 2^n) ranked zeta table; returns the layer-k
    convolution (..., 2^n).

    Leading axes between the rank axis and the lattice axis are batch
    dimensions (the plan-serving batched solver stacks same-``n``
    queries; the (G+1)-ary probe strategy stacks gamma gates): the
    convolution is elementwise across lattice positions, so the whole
    batch folds into the kernel row dimension and shares one grid — true
    batching, not a host loop.  Falls back to the reference when the
    folded table is smaller than one tile (or not tileable).
    """
    nranks = Z.shape[0]
    size = Z.shape[-1]
    batch = Z.shape[1:-1]
    total = size
    for b in batch:
        total *= b
    if total < TILE or total % TILE:
        from repro.kernels.ref import ranked_conv_ref
        return ranked_conv_ref(Z, k)
    rows = total // LANES
    z3 = Z.reshape(nranks, rows, LANES)
    out = pl.pallas_call(
        functools.partial(_ranked_conv_kernel, k=k),
        grid=(rows // SUBLANES,),
        in_specs=[pl.BlockSpec((nranks, SUBLANES, LANES),
                               lambda i: int32_index(0, i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES),
                               lambda i: int32_index(i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), Z.dtype),
        interpret=interpret,
    )(z3)
    return out.reshape(batch + (size,))
