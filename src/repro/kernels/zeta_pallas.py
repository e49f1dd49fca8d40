"""Pallas TPU kernels for the subset-lattice zeta / Moebius transform.

TPU-native decomposition of Yates' O(2^n n) butterfly (DESIGN.md
§Hardware-adaptation):

  view f as (ROWS, LANES) with LANES = 256  (index S = row * LANES + col)

  * low  log2(LANES) bits — lane butterflies inside each (row_block,
    LANES) tile: a lane rotate (``pltpu.roll``) brings the bit-clear
    partner alongside, an iota select adds it where the bit is set.
  * middle bits (rows inside a block)  — the same rotate-and-select
    butterflies along the sublane axis.
  * high bits (across row-blocks)      — one pairing pass per bit: grid
    over block pairs, the bit-set block accumulates its bit-clear
    partner (out = own ± partner).

No scatter and no in-kernel reshape: the TPU lowering has neither.

Exactness envelope (documented, asserted by ops.py):
  float32 — exact while values stay < 2^24
  int32   — exact while values stay < 2^31
  beyond that the int64 XLA path in ``repro.core.zeta`` is used.

All kernels compile for the TPU (tests/test_tpu_compile.py) and are
validated with ``interpret=True`` on CPU against ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 256


def int32_index(*idx):
    """Block indices as int32 whatever ``jax_enable_x64`` says: Mosaic
    only legalizes an index map that returns 32-bit integers, and a bare
    Python int constant traces to int64 under x64."""
    return tuple(jnp.asarray(i, jnp.int32) for i in idx)


# --------------------------------------------------------------- kernel 1
def _butterflies(x, axis: int, bits: int, s):
    """In-register Yates butterflies over the low ``bits`` bits of
    ``axis``: every index with bit j set adds ``s`` times its bit-clear
    partner.  The partner sits 2^j positions lower, so a rotate (XLU)
    plus an iota select does it — no scatter, no reshape."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    zero = jnp.zeros_like(x)
    for j in range(bits):
        # partner[a] = x[a - 2^j]; an int32 shift, as Mosaic requires
        partner = pltpu.roll(x, jnp.int32(1 << j), axis)
        x = x + jnp.where(((pos >> j) & 1) == 1, partner * s, zero)
    return x


def _local_kernel(x_ref, o_ref, *, row_bits: int, sign: float):
    """Zeta/Moebius over the low log2(LANES) + row_bits bits of a block:
    lane butterflies, then sublane butterflies."""
    x = x_ref[...]                                   # (RB, LANES)
    s = jnp.array(sign, x.dtype)                     # ±1 in the array dtype
    x = _butterflies(x, 1, LANES.bit_length() - 1, s)
    o_ref[...] = _butterflies(x, 0, row_bits, s)


def _local_pass(f2d: jnp.ndarray, row_block: int, sign: float,
                interpret: bool) -> jnp.ndarray:
    rows = f2d.shape[0]
    return pl.pallas_call(
        functools.partial(_local_kernel,
                          row_bits=row_block.bit_length() - 1, sign=sign),
        grid=(rows // row_block,),
        in_specs=[pl.BlockSpec((row_block, LANES),
                               lambda i: int32_index(i, 0))],
        out_specs=pl.BlockSpec((row_block, LANES),
                               lambda i: int32_index(i, 0)),
        out_shape=jax.ShapeDtypeStruct(f2d.shape, f2d.dtype),
        interpret=interpret,
    )(f2d)


# --------------------------------------------------------------- kernel 2
def _pair_kernel(own_ref, partner_ref, o_ref, *, block_bit: int,
                 sign: float):
    i = pl.program_id(0)
    bit_set = ((i >> block_bit) & 1) == 1
    coeff = jnp.where(bit_set, jnp.array(sign, own_ref.dtype),
                      jnp.array(0, own_ref.dtype))
    o_ref[...] = own_ref[...] + partner_ref[...] * coeff


def _pair_pass(f2d: jnp.ndarray, row_block: int, block_bit: int,
               sign: float, interpret: bool) -> jnp.ndarray:
    """One butterfly pass over block-index bit ``block_bit``.

    Grid enumerates all blocks; bit-set blocks accumulate their bit-clear
    partner (out = own + sign * partner), bit-clear blocks copy through.
    Reads 2x / writes 1x the array — race-free without buffer aliasing.
    (On real hardware an input_output_aliased variant halves traffic; kept
    simple here, see DESIGN.md §Perf notes.)
    """
    rows = f2d.shape[0]
    nblocks = rows // row_block
    return pl.pallas_call(
        functools.partial(_pair_kernel, block_bit=block_bit, sign=sign),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((row_block, LANES),
                         lambda i: int32_index(i, 0)),
            pl.BlockSpec((row_block, LANES),
                         lambda i: int32_index(i ^ (1 << block_bit), 0)),
        ],
        out_specs=pl.BlockSpec((row_block, LANES),
                               lambda i: int32_index(i, 0)),
        out_shape=jax.ShapeDtypeStruct(f2d.shape, f2d.dtype),
        interpret=interpret,
    )(f2d, f2d)


# ------------------------------------------------------------ entry point
def zeta_pallas(f: jnp.ndarray, inverse: bool = False,
                row_block: int = 8, interpret: bool = False) -> jnp.ndarray:
    """Zeta (or Moebius, ``inverse=True``) transform over the LAST axis.

    Leading axes are a batch dimension (the plan-serving batched solver
    stacks same-``n`` queries): the batch is folded into the kernel row
    dimension, so one grid launch covers the whole stack.  This is exact
    batching, not a host loop — per-element lattices occupy disjoint,
    power-of-two-aligned row ranges, every local-pass block lies inside
    one element, and the pair-pass partner index ``i ^ (1 << bit)`` only
    touches bits below ``log2(rows_per_element / row_block)``, so butterflies
    never cross elements.

    Requires n >= log2(LANES) + log2(row_block); smaller inputs fall back
    to the reference path (they are latency-trivial anyway).
    """
    size = f.shape[-1]
    batch = f.shape[:-1]
    n = size.bit_length() - 1
    sign = -1.0 if inverse else 1.0
    min_bits = LANES.bit_length() - 1 + row_block.bit_length() - 1
    if n < min_bits:
        from repro.kernels.ref import zeta_ref, mobius_ref
        return mobius_ref(f) if inverse else zeta_ref(f)
    rows = size // LANES                       # rows per batch element
    nbatch = 1
    for b in batch:
        nbatch *= b
    f2d = f.reshape(nbatch * rows, LANES)
    f2d = _local_pass(f2d, row_block, sign, interpret)
    n_block_bits = (rows // row_block).bit_length() - 1
    for jb in range(n_block_bits):             # per-element bits only
        f2d = _pair_pass(f2d, row_block, jb, sign, interpret)
    return f2d.reshape(batch + (size,))


def mobius_pallas(f: jnp.ndarray, **kw) -> jnp.ndarray:
    return zeta_pallas(f, inverse=True, **kw)
