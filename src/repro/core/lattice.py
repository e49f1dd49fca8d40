"""The lattice-program layer: ONE implementation of the paper's layered
DP skeleton (Alg. 1), instantiated per cost function.

Before this module the repo had drifted into per-cost forks of the same
recursion: ``layered.py`` (host-loop feasibility reference),
``engine.py`` (fused scan-form feasibility), the ``gamma_batch`` probe
loop in ``dpconv_max.py``, and ``service.batch.pallas_dp_fn`` each
re-stated the layered recursion with small local differences.  This
module states it once, parameterized along four orthogonal axes:

========== =================================================================
axis        instances
========== =================================================================
semiring    *feasibility* — {0,1} counting in (+,·), thresholded per layer
            (Kosaraju's trick, Sec. 6): ``feasibility_layers``;
            *value* — (min,+) over f64 with a gamma gate (DPsub[out]'s
            recursion as a dense layer program): ``minplus_value_layers``;
            *connected value* — the same (min,+) sweep under per-subset
            valid-split masks (DPccp's csg/cmp search space as bitset
            tensors): ``minplus_connected_layers``
transforms  XLA int64 butterflies (exact counts to n = 26) or the batched
            Pallas int32 kernels (exact to n = 15) — ``transforms()``;
            optionally a fused ranked-convolution kernel
probe       binary search (G = 1) or (G+1)-ary ``gamma_batch`` probing —
            G gates ride a leading axis through the same layer program,
            shrinking rounds from ~log2 C to ~log_{G+1} C
extraction  Alg. 2 as an on-device masked scan over tree slots
            (``extract_scan``) — no host recursion, the host only
            assembles ``JoinTree`` objects from the returned split arrays
========== =================================================================

The layered recursion itself (direct small layers, ranked-convolution
middle layers, Moebius-at-V shortcut or full final butterfly) has exactly
one implementation, ``feasibility_layers``, which runs either *unrolled*
(the host-loop / jit-per-pass reference: ``layered.py`` is now a thin
wrapper) or *scan-form* (``lax.fori_loop`` body with masked convolution
slots, carried ranked-zeta buffer: the fused engine's mode).

``build_max_program`` / ``build_cap_program`` / ``build_out_program``
compose the axes into whole-solve programs — one dispatch per batched
solve — that ``repro.core.engine`` AOT-compiles and caches.  Each names
its device phases with ``jax.named_scope``: ``search`` (the gate builder
and the threshold search loop; the out program's (min,+) sweep, which
finds its optimum) and ``extract`` (the pass at the optimum that builds
the table extraction reads, and the extraction scan).  The scopes reach
the compiled instructions' ``op_name`` metadata, so a device trace's ops
can be told apart by phase; no instruction changes.  Exactness notes sit
next to each piece; every instantiation is bit-identical to its host
reference (asserted by tests/test_lattice_parity.py).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import f64bits
from repro.core.bitset import layer_indices, popcounts, submask_table

BACKENDS = ("xla", "pallas")


# ------------------------------------------------------------- transforms
@dataclasses.dataclass(frozen=True)
class Transforms:
    """The transform backend of a lattice program: zeta/Moebius pair, the
    DP dtype they are exact in, and (optionally) a fused ranked-conv
    kernel for the unrolled static-``k`` path."""
    name: str
    zeta: callable
    mobius: callable
    dtype: object
    ranked_conv: "callable | None" = None   # static-k fused kernel

    def __hash__(self):                      # jit static-arg friendly
        return hash((self.name, self.zeta, self.mobius))

    def __eq__(self, other):
        return (isinstance(other, Transforms)
                and (self.name, self.zeta, self.mobius)
                == (other.name, other.zeta, other.mobius))


def transforms(backend: str) -> Transforms:
    """The two shipped transform tiers (DESIGN.md §Hardware-adaptation).
    Both count in integers: int64 on the XLA tier (exact to n = 26, and
    exact on a TPU, whose f64 is a pair of f32), int32 on Pallas."""
    if backend == "xla":
        from repro.core.zeta import mobius, zeta
        return Transforms("xla", zeta, mobius, jnp.int64)
    if backend == "pallas":
        # int32 counting tier: exact while counts < 2^31 (n <= 15),
        # enforced by the caller (BatchPolicy.pallas_max_n)
        from repro.kernels.ops import (mobius_batch_op, ranked_conv_op,
                                       zeta_batch_op)
        return Transforms("pallas", zeta_batch_op, mobius_batch_op,
                          jnp.int32, ranked_conv=ranked_conv_op)
    raise ValueError(f"unknown lattice backend {backend!r}")


# ------------------------------------------------- static gather tables
@functools.lru_cache(maxsize=128)
def direct_layer_indices(n: int, k: int):
    """Static gather tables for direct evaluation of layer k.

    Returns (sets, subs, comps): sets (m,) int64 masks with |S| = k;
    subs/comps (m, 2^k) submask / complement-in-S tables.  Shared by the
    feasibility direct layers AND the (min,+) value layers — the rows
    T = 0 / T = S are neutralized by dp[∅] (0 for counting, +inf for
    min-plus), so one table serves both semirings.
    """
    sets = layer_indices(n)[k]
    subs = submask_table(sets, k).T          # (m, 2^k)
    comps = sets[:, None] & ~subs
    # NB: keep these as numpy — jnp constants created inside a jit trace
    # must not be cached across traces (tracer leak).
    return (sets, subs, comps)


# The sharded layer sweeps gather at most this many elements per batch
# row per chunk (rows_per_chunk = SHARD_CHUNK_ELEMS >> k), bounding the
# (..., rows, 2^k) working set on each device regardless of layer width.
SHARD_CHUNK_ELEMS = 1 << 21


@functools.lru_cache(maxsize=128)
def sharded_layer_indices(n: int, k: int, shards: int):
    """``direct_layer_indices`` padded so the sets axis splits into
    ``shards`` equal blocks (device d takes rows [d*blk, (d+1)*blk)).

    Pad rows point at index 0 (the empty set): pc[0] = 0 != k, so the
    per-layer ``pc == k`` select discards anything a pad row writes, and
    dp[∅] (0 for counting, +inf for min-plus) keeps the pad arithmetic
    NaN-free.  Returns (sets, subs, comps, blk) — numpy, same tracer-leak
    rule as ``direct_layer_indices``.
    """
    sets, subs, comps = direct_layer_indices(n, k)
    m = sets.shape[0]
    blk = -(-m // shards)
    pad = blk * shards - m
    if pad:
        sets = np.concatenate([sets, np.zeros(pad, sets.dtype)])
        subs = np.concatenate(
            [subs, np.zeros((pad, subs.shape[1]), subs.dtype)])
        comps = np.concatenate(
            [comps, np.zeros((pad, comps.shape[1]), comps.dtype)])
    return (sets, subs, comps, blk)


def _shard_block_tables(n: int, k: int, shards: int, axis: str,
                        chunk: int):
    """This device's row-chunks of the layer-k gather tables: yields
    ``(sets, subs, comps)`` slices of at most ``chunk >> k`` rows,
    starting at ``axis_index(axis) * blk``.  A static python loop — the
    chunk count is a compile-time constant, only the offset is traced."""
    sets, subs, comps, blk = sharded_layer_indices(n, k, shards)
    start = lax.axis_index(axis) * blk
    rows = max(1, min(blk, chunk >> k))
    for lo in range(0, blk, rows):
        r = min(rows, blk - lo)
        yield (lax.dynamic_slice_in_dim(sets, start + lo, r),
               lax.dynamic_slice_in_dim(subs, start + lo, r),
               lax.dynamic_slice_in_dim(comps, start + lo, r))


# ------------------------------------------------------ layer primitives
def direct_layer_full(dp, gate, n: int, k: int, pc, dtype):
    """Layer k by gather-based split enumeration (paper Sec. 6): full
    (..., 2^n) indicator of gated layer-k sets with a feasible split."""
    sets, subs, comps = direct_layer_indices(n, k)
    prod = dp[..., subs] * dp[..., comps]          # (..., m, 2^k)
    layer_ind = (jnp.sum(prod, axis=-1) > 0.5).astype(dtype)
    layer_full = jnp.zeros(dp.shape, dtype)
    layer_full = layer_full.at[..., sets].set(layer_ind) * gate
    return jnp.where(pc == k, layer_full, jnp.array(0, dtype))


def direct_layer_full_sharded(dp, gate, n: int, k: int, pc, dtype,
                              shards: int, axis: str,
                              chunk: int = SHARD_CHUNK_ELEMS):
    """``direct_layer_full`` under ``shard_map``: each device evaluates
    its block of layer-k sets (chunked gathers), scatters the {0,1}
    indicators into a zero lattice, and ONE ``psum`` merges the disjoint
    blocks.  Bit-identical to the unsharded form: each real set is
    written by exactly one device (zeros elsewhere, so the sum is the
    value itself, exact in both f64 and int32), and pad-row writes land
    on index 0 which the ``pc == k`` select drops."""
    part = jnp.zeros(dp.shape, dtype)
    for ss, sub, comp in _shard_block_tables(n, k, shards, axis, chunk):
        prod = dp[..., sub] * dp[..., comp]        # (..., rows, 2^k)
        ind = (jnp.sum(prod, axis=-1) > 0.5).astype(dtype)
        part = part.at[..., ss].set(ind)
    # {0,1} indicators: merge in int32, which every backend all-reduces
    layer_full = lax.psum(part.astype(jnp.int32), axis).astype(dtype) * gate
    return jnp.where(pc == k, layer_full, jnp.array(0, dtype))


def conv_fixed(Z, k: int, ranked_conv=None):
    """Symmetry-halved ranked convolution at a *static* layer k:
    conv_k = Σ_{d=1..k-1} Z[d] Z[k-d] = 2 Σ_{d<k/2} Z[d] Z[k-d]
    (+ Z[k/2]^2 if k even).  ``ranked_conv`` optionally routes to a fused
    kernel (one HBM read of the ranked table instead of k)."""
    if ranked_conv is not None:
        return ranked_conv(Z, k)
    acc = jnp.zeros_like(Z[0])
    for d in range(1, (k - 1) // 2 + 1):
        acc = acc + Z[d] * Z[k - d]
    acc = acc + acc        # *2, without promoting int32 to f64
    if k % 2 == 0:
        acc = acc + Z[k // 2] * Z[k // 2]
    return acc


def conv_masked(Z, k, n: int, dtype):
    """The same convolution for a *traced* k (scan-form middle layers):
    slots with d > k-d carry stale previous-round values and are masked
    by w = 0, trading arithmetic for uniformity (DESIGN.md
    §Hardware-adaptation)."""
    D = max(n // 2, 1)             # symmetry-halved convolution slots
    d = jnp.arange(1, D + 1)
    w = jnp.where(d < k - d, 2, jnp.where(d == k - d, 1, 0))
    Zhi = Z[jnp.clip(k - d, 1, n)]
    wb = w.astype(dtype).reshape((D,) + (1,) * (Z.ndim - 1))
    # sum in the DP dtype: under x64 an int32 sum would widen to int64,
    # which the Pallas tier's int32 kernels cannot take on a TPU
    return jnp.sum(wb * Z[1:D + 1] * Zhi, axis=0, dtype=dtype)


def moebius_at_v(acc, pc, n: int):
    """Moebius transform evaluated at the single point V: the signed
    O(2^n) sum Σ_T (-1)^{n-|T|} conv[T].  Signed partial sums exceed the
    count bound, so reduce in a wider type: int64 for integer tables
    (exact), f64 for the host loop's float tables."""
    wide = (jnp.float64 if jnp.issubdtype(acc.dtype, jnp.floating)
            else jnp.int64)
    sign = jnp.where((n - pc) % 2 == 0, 1, -1).astype(wide)
    return jnp.sum(acc.astype(wide) * sign, axis=-1, dtype=wide)


# --------------------------------------------- the feasibility recursion
def feasibility_layers(gate, n: int, direct_layers: int = 4,
                       tfm: "Transforms | None" = None,
                       final_shortcut: bool = True,
                       Z=None, scan_middle: bool = False,
                       shards: int = 1, shard_axis: "str | None" = None,
                       shard_chunk: int = SHARD_CHUNK_ELEMS,
                       seed_layers=None):
    """One full layered feasibility DP under ``gate`` — THE layered
    recursion (paper Sec. 5 + 6), shared by every solver in the repo.

    Returns ``(dp, Z, feas)``: the accumulated feasibility table, the
    ranked-zeta buffer, and the boolean feasibility of the full set V.
    With ``final_shortcut`` the final layer is evaluated only at V
    (Moebius-at-V) and ``dp`` carries no layer-n entries; otherwise the
    full final butterfly runs (the tree-extraction table).

    ``gate`` may carry any leading batch axes (..., 2^n): the serving
    batch axis, and the gamma-probe axis of (G+1)-ary search, both ride
    in front and every lattice op broadcasts.

    ``Z`` — pass the carried ``(n+1, ..., 2^n)`` ranked-zeta buffer to
    reuse it across rounds (the fused while-loop donates it); slot Z[1]
    (the singleton transform, round-invariant) must already be set and is
    never rewritten.  ``Z=None`` allocates fresh.

    ``scan_middle`` selects the middle-layer form: unrolled static-``k``
    layers (the host/jit-per-pass reference) or a ``lax.fori_loop`` with
    masked convolution slots (the fused engine; the final layer is then
    always convolution-form).  Both are exact — every intermediate is an
    exact {0,1} count in the transform dtype — so results are
    bit-identical across forms.

    ``shard_axis`` (inside ``shard_map``) partitions the *direct* layers'
    gather sweep across the mesh axis — one ``psum`` per layer merges the
    disjoint blocks.  The butterfly middle layers stay replicated (a
    zeta transform reads the whole lattice; DESIGN.md §Sharding).

    ``seed_layers`` — the incremental-planning warm start: a
    ``(k0, dp_seed)`` pair where ``dp_seed`` (broadcastable to
    ``gate``'s shape) is an already-accumulated feasibility table whose
    layer slices ``dp_seed * [pc == k]`` are *valid for this gate* for
    every ``k <= k0``.  Layers ``2..k0`` are then replayed from the seed
    (one select + zeta each) instead of re-enumerated — the gather-table
    split enumeration, the expensive part of a direct layer, is skipped
    entirely.  Correctness is the caller's contract: layer-``k``
    feasibility depends only on the gate over sets of size ``<= k``, so
    a seed transfers exactly when those gate values match the run that
    produced it (byte-identical cardinalities AND the same gamma
    threshold — e.g. the stored extraction table of a previous solve of
    the same canonical query, replayed at its cached optimum).  Seeded
    and cold runs are then bit-identical: the replayed slices equal what
    the enumeration would recompute, and zeta of equal inputs is equal.
    """
    tfm = tfm or transforms("xla")
    size = 1 << n
    pc = jnp.asarray(popcounts(n), dtype=jnp.int32)
    dtype = tfm.dtype
    gate = jnp.asarray(gate).astype(dtype)      # {0,1}: exact in any dtype
    batch = gate.shape[:-1]
    zero = jnp.array(0, dtype)

    singles = jnp.broadcast_to((pc == 1).astype(dtype), batch + (size,))
    dp = jnp.zeros(batch + (size,), dtype) + singles
    if Z is None:
        Z = jnp.zeros((n + 1,) + batch + (size,), dtype)
        Z = Z.at[1].set(tfm.zeta(singles))

    dl = min(direct_layers, n - 1) if scan_middle else min(direct_layers, n)
    start_k = 2
    if seed_layers is not None:                # warm-start solved prefix
        k0, dp_seed = seed_layers
        k0 = min(int(k0), n - 1)
        seed_t = jnp.asarray(dp_seed).astype(dtype)
        for k in range(2, k0 + 1):
            layer_full = jnp.where(pc == k,
                                   jnp.broadcast_to(seed_t, dp.shape),
                                   zero)
            dp = dp + layer_full
            if k < n:
                Z = Z.at[k].set(tfm.zeta(layer_full))
        start_k = max(2, k0 + 1)
    for k in range(start_k, dl + 1):           # direct small layers
        if shard_axis is not None:
            layer_full = direct_layer_full_sharded(
                dp, gate, n, k, pc, dtype, shards, shard_axis,
                shard_chunk)
        else:
            layer_full = direct_layer_full(dp, gate, n, k, pc, dtype)
        dp = dp + layer_full
        if k < n:
            Z = Z.at[k].set(tfm.zeta(layer_full))
    if dl >= n:                                # all-direct (host, small n)
        return dp, Z, dp[..., -1] > 0.5

    if scan_middle:
        def layer_body(k, carry):              # middle layers, scan-form
            dp, Z = carry
            h = tfm.mobius(conv_masked(Z, k, n, dtype))
            layer_full = jnp.where(
                pc == k, (h > 0.5).astype(dtype) * gate, zero)
            dp = dp + layer_full
            Z = lax.dynamic_update_index_in_dim(
                Z, tfm.zeta(layer_full), k, 0)
            return dp, Z

        first_conv = max(dl + 1, 2)   # layers start at 2: slot Z[1]
        if first_conv < n:            # holds the singleton transform
            dp, Z = lax.fori_loop(first_conv, n, layer_body, (dp, Z))
        acc = conv_masked(Z, n, n, dtype)
    else:
        for k in range(max(dl + 1, 2), n):     # middle layers, unrolled
            h = tfm.mobius(conv_fixed(Z, k, tfm.ranked_conv))
            layer_full = jnp.where(
                pc == k, (h > 0.5).astype(dtype) * gate, zero)
            dp = dp + layer_full
            Z = Z.at[k].set(tfm.zeta(layer_full))
        acc = conv_fixed(Z, n, tfm.ranked_conv)

    if final_shortcut:
        count_v = moebius_at_v(acc, pc, n)
        feas = (count_v > 0.5) & (gate[..., -1] > zero)
        return dp, Z, feas
    h = tfm.mobius(acc)
    layer_full = jnp.where(pc == n, (h > 0.5).astype(dtype) * gate, zero)
    dp = dp + layer_full
    return dp, Z, dp[..., -1] > 0.5


# ------------------------------------------------- the (min,+) semiring
def _merge_blocks(part, axis: str):
    """Merge per-device blocks of one (min,+) layer (f64 bit patterns).
    Every layer-k set is written by exactly one device and ``part`` is
    zero elsewhere, so summing the two 32-bit halves of each pattern
    passes it through exactly.  (A ``pmin`` over +inf fill would say
    the same, but XLA:TPU all-reduces 64-bit types by sum only.)"""
    lo = lax.psum((part & 0xFFFFFFFF).astype(jnp.int32), axis)
    hi = lax.psum((part >> 32).astype(jnp.int32), axis)
    return (hi.astype(jnp.int64) << 32) | (lo.astype(jnp.int64)
                                           & 0xFFFFFFFF)


def _minplus_sweep(card, set_ok, n: int, split_ok=None, shards: int = 1,
                   shard_axis: "str | None" = None,
                   shard_chunk: int = SHARD_CHUNK_ELEMS,
                   seed_vals=None, seed_ok=None):
    """The (min,+) layer sweep both value semirings share, on f64 bit
    patterns: ``dp[S] = (dp[T] + dp[S\\T]) + c(S)`` minimised over the
    splits (only those with ``split_ok`` on both halves, when given) for
    sets with ``set_ok``, +inf elsewhere; singletons cost 0.  Every
    value is >= +0, so the min runs on the bits."""
    pc = jnp.asarray(popcounts(n), dtype=jnp.int32)
    inf = jnp.int64(f64bits.INF)
    dp = jnp.broadcast_to(jnp.where(pc == 1, jnp.int64(0), inf), card.shape)

    def layer(dp, ss, sub, comp):
        combo = f64bits.add(dp[..., sub], dp[..., comp])   # (..., m, 2^k)
        if split_ok is not None:
            combo = jnp.where(split_ok[..., sub] & split_ok[..., comp],
                              combo, inf)
        val = f64bits.add(jnp.min(combo, axis=-1), card[..., ss])
        val = jnp.where(set_ok[..., ss], val, inf)
        if seed_vals is not None:
            val = jnp.where(seed_ok[..., ss], seed_vals[..., ss], val)
        return val

    for k in range(2, n + 1):
        if shard_axis is not None:
            part = jnp.zeros(dp.shape, jnp.int64)
            for ss, sub, comp in _shard_block_tables(
                    n, k, shards, shard_axis, shard_chunk):
                part = part.at[..., ss].set(layer(dp, ss, sub, comp))
            dp = jnp.where(pc == k, _merge_blocks(part, shard_axis), dp)
        else:
            sets, subs, comps = direct_layer_indices(n, k)
            dp = dp.at[..., sets].set(layer(dp, sets, subs, comps))
    return dp


def minplus_value_layers(card, gate_ok, n: int, shards: int = 1,
                         shard_axis: "str | None" = None,
                         shard_chunk: int = SHARD_CHUNK_ELEMS):
    """DPsub[out]'s recursion as a dense layer program — the C_cap
    pass-2 instantiation of the lattice skeleton.

    ``dp[S] = c(S) + min_T (dp[T] + dp[S\\T])`` for gated sets
    (``gate_ok``: c(S) <= gamma), +inf otherwise; singletons cost 0.
    There is no FSC shortcut in the (min,+) semiring (that hardness is
    the paper's point), so every layer runs the direct gather-table
    enumeration — the textbook O(3^n) operation count re-blocked into
    dense vector lanes, on device, batched, inside the same single
    dispatch as pass 1.  Bit-identical to ``baselines.dpsub(mode="out",
    prune_gamma=gamma)``: min is order-independent and the add
    association matches.

    ``card`` (..., 2^n) f64 as int64 bit patterns (``f64bits``; so is
    the returned table); ``gate_ok`` boolean, same shape.

    ``shard_axis`` (inside ``shard_map``) partitions each layer's sets
    axis across the mesh: every device computes its block of layer-k
    sets (the dominant ``C(n,k)·2^k`` combo tensor shrinks to 1/D), the
    blocks meet in ONE ``psum`` per layer, and a ``pc == k`` select
    folds the merged layer back into the carried table.  Bit-identical
    to the unsharded sweep: per set the full 2^k split axis stays on one
    device (same min order, same add association), and the psum just
    passes that device's value through the zeros everywhere else
    (``_merge_blocks``).
    """
    return _minplus_sweep(card, gate_ok, n, shards=shards,
                          shard_axis=shard_axis, shard_chunk=shard_chunk)


def minplus_connected_layers(card, conn, n: int, shards: int = 1,
                             shard_axis: "str | None" = None,
                             shard_chunk: int = SHARD_CHUNK_ELEMS,
                             seed_vals=None, seed_ok=None):
    """DPccp's recursion as a dense layer program — the connectivity-
    masked C_out instantiation of the lattice skeleton.

    ``dp[S] = c(S) + min_{(T, S\\T) valid} (dp[T] + dp[S\\T])`` where a
    split is *valid* iff both halves induce connected subgraphs — for a
    connected ``S`` a crossing join edge is then implied (any partition
    of a connected graph has one), so the valid splits are exactly the
    DPccp csg/cmp pairs and no cross product ever enters the search
    space.  Disconnected sets stay at +inf; singletons cost 0.

    The per-subset valid-split masks are materialized per layer from the
    connected-subset indicator by the same gather tables the (min,+)
    combination uses (``conn[subs] & conn[comps]``) — the DPccp search
    space as bitset tensors, see DESIGN.md §Lattice-programs for the
    memory accounting.  Bit-identical to ``dpccp.dpccp(q, card,
    mode="out")``: the valid pairs are the same multiset, min is
    order-independent, and the add association ``(dp[T] + dp[S\\T]) +
    c(S)`` matches the enumerator's.

    ``card`` (..., 2^n) f64 bit patterns, as in ``minplus_value_layers``;
    ``conn`` boolean, same shape (per-query
    connected-subset masks — each batch row may carry a different query
    graph).

    ``shard_axis`` partitions the sets axis exactly as in
    ``minplus_value_layers`` — the per-layer valid-split masks are then
    only ever materialized for this device's block, so the masks shrink
    1/D along with the combo tensor.

    ``seed_vals``/``seed_ok`` (same shape as ``card``; f64 bits / bool) are
    the incremental-planning value seeds: where ``seed_ok[S]`` the layer
    write takes ``seed_vals[S]`` instead of the freshly-computed value.
    ``dp[S]`` is a pure function of the sub-problem induced on ``S``
    (cardinalities + connectivity restricted to subsets of S), so a seed
    taken from a previous solve whose induced sub-problem on S is a
    byte-exact relabeling transfers bitwise — including the +inf of
    disconnected sets — and seeded sweeps stay bit-identical to cold
    ones.  Seeded entries still *feed* later layers through the same
    gather reads, so a correct prefix propagates exactly.
    """
    return _minplus_sweep(card, conn, n, split_ok=conn, shards=shards,
                          shard_axis=shard_axis, shard_chunk=shard_chunk,
                          seed_vals=seed_vals, seed_ok=seed_ok)


# ------------------------------------------------------ probe strategies
def probe_pivots(lo, hi, G: int):
    """(G,) interior pivots per query splitting [lo, hi] into G+1 parts:
    p_g = lo + (hi-lo)(g+1)/(G+1), all in [lo, hi-1] — every probe makes
    progress.  G = 1 reduces to the binary-search pivot (lo+hi)//2
    exactly, so the fused G = 1 path stays bit-aligned with the host
    loop's pivot sequence."""
    g = jnp.arange(1, G + 1, dtype=lo.dtype)
    return lo[None, :] + ((hi - lo)[None, :] * g[:, None]) // (G + 1)


def bracket_update(lo, hi, piv, ok, active):
    """Monotone (G+1)-ary bracket update: feasibility is monotone in
    gamma, so ``ok`` along the probe axis is [F..F, T..T]; the bracket
    collapses onto [largest infeasible + 1, smallest feasible]."""
    G = piv.shape[0]
    ntrue = jnp.sum(ok.astype(jnp.int32), axis=0)          # (B,)
    any_ok = ntrue > 0
    any_bad = ntrue < G
    first_ok = jnp.clip(G - ntrue, 0, G - 1)
    last_bad = jnp.clip(G - ntrue - 1, 0, G - 1)
    piv_ok = jnp.take_along_axis(piv, first_ok[None, :], axis=0)[0]
    piv_bad = jnp.take_along_axis(piv, last_bad[None, :], axis=0)[0]
    hi = jnp.where(active & any_ok, piv_ok, hi)
    lo = jnp.where(active & any_bad, piv_bad + 1, lo)
    return lo, hi


# ------------------------------------------- on-device tree extraction
LANE_BITS = 7                  # a TPU vector row: 2^7 = 128 lanes


def xor_permute(x, s, n: int):
    """``y[b, t] = x[b, t ^ s[b]]`` over the lattice axis of a (B, 2^n)
    table, with no per-element index: n static flips, bit i's kept or
    dropped per row by a ``where`` on bit i of ``s``.  A permutation, so
    exact for any dtype.

    The axis is viewed as (rows, lanes) with its low ``min(n, 7)`` bits
    on the 128 lanes.  A row bit's flip reverses one axis of a static
    reshape (whole lane rows trade places); a lane bit's swaps lanes
    ``k = 2^i`` apart, two rolls and a select on the lane's own bit —
    a lane-axis reshape would pad each piece out to 128 lanes.
    """
    B, size = x.shape
    lb = min(n, LANE_BITS)
    R, L = size >> lb, 1 << lb
    v = x.reshape(B, R, L)

    def flip_if(i, f, v):
        return jnp.where(((s >> i) & 1).astype(bool)[:, None, None], f, v)

    for j in range(n - lb):
        f = v.reshape(B, R >> (j + 1), 2, 1 << j, L)[:, :, ::-1]
        v = flip_if(lb + j, f.reshape(B, R, L), v)
    lane = jnp.arange(L, dtype=jnp.int32)
    for i in range(lb):
        k = 1 << i
        f = jnp.where((lane & k) == 0, jnp.roll(v, -k, axis=2),
                      jnp.roll(v, k, axis=2))
        v = flip_if(i, f, v)
    return v.reshape(B, size)


def extract_scan(dp, n: int, card=None):
    """Alg. 2 as a masked scan over tree slots — fully on device.

    The join tree over n relations has at most ``M = 2n-1`` nodes.  The
    scan walks a breadth-first slot array: slot r holds a set mask; an
    internal slot finds its witness split by one dense O(2^n) pass over
    all candidate submasks (valid-submask masking + argmin), writes its
    two children at the write head, and records the child slot index.
    Total O(2^n n) per query — Alg. 2's bound, with the per-node submask
    *enumeration* replaced by a full-lattice masked reduction (the same
    uniformity trade the rest of the engine makes).

    The complement half ``dp[S & ~T]`` is read as ``dp[T ^ S]`` through
    ``xor_permute`` — static flips of the lattice axis, no per-element
    gather.  Exact: on a submask T of S, ``S & ~T == S ^ T``, and every
    T that is not a submask is masked to the worst error regardless of
    what it read, so the errors that can win match the gather's bit for
    bit, and so do the witnesses.

    Witness rule — matched to the host extractors for bit-identical
    trees: the *largest* T minimizing the witness error, because the
    host's descending ``_submask_iter`` keeps the first (= largest)
    strict minimum.  ``card=None`` reads ``dp`` as a feasibility table
    (error 0 iff both sides feasible); with ``card`` it reads ``dp`` and
    ``card`` as f64 bit patterns of a C_out value table, with the host's
    f64 error |dp[T] + dp[S\\T] - (dp[S] - c(S))| computed bit for bit
    (``f64bits``).

    Returns ``(nodes, lidx)``: (B, M) int32 — slot masks and left-child
    slot indices (0 for leaves).  ``jointree.tree_from_split_arrays``
    assembles JoinTree objects from them without any host search.
    """
    B, size = dp.shape
    M = 2 * n - 1
    pc = jnp.asarray(popcounts(n), dtype=jnp.int32)
    T = jnp.arange(size, dtype=jnp.int32)
    ar = jnp.arange(B)

    def body(r, carry):
        nodes, lidx, w = carry
        S = nodes[:, r]                                    # (B,)
        internal = pc[S] >= 2
        valid = (((T[None, :] & ~S[:, None]) == 0)
                 & (T[None, :] != 0) & (T[None, :] != S[:, None]))
        dpC = xor_permute(dp, S, n)           # dp[S & ~T] where valid
        if card is None:
            err = 1 - ((dp > 0) & (dpC > 0)).astype(jnp.int32)
            worst = jnp.int32(2)
        else:                     # >= +0, so the bits order as values
            target = f64bits.add(
                jnp.take_along_axis(dp, S[:, None], axis=1),
                f64bits.neg(jnp.take_along_axis(card, S[:, None], axis=1)))
            err = f64bits.abs_(f64bits.add(f64bits.add(dp, dpC),
                                           f64bits.neg(target)))
            worst = jnp.int64(f64bits.INF)
        err = jnp.where(valid, err, worst)
        # largest T among the minima: argmin over the reversed axis
        twit = (size - 1 - jnp.argmin(err[:, ::-1], axis=1)) \
            .astype(jnp.int32)
        wc = jnp.minimum(w, M - 2)        # leaf slots don't advance w
        left = jnp.where(internal, twit, nodes[ar, wc])
        right = jnp.where(internal, S & ~twit, nodes[ar, wc + 1])
        nodes = nodes.at[ar, wc].set(left)
        nodes = nodes.at[ar, wc + 1].set(right)
        lidx = lidx.at[:, r].set(jnp.where(internal, wc, 0))
        w = w + 2 * internal.astype(jnp.int32)
        return nodes, lidx, w

    nodes0 = jnp.zeros((B, M), jnp.int32).at[:, 0].set(size - 1)
    lidx0 = jnp.zeros((B, M), jnp.int32)
    w0 = jnp.ones((B,), jnp.int32)
    nodes, lidx, _ = lax.fori_loop(0, M, body, (nodes0, lidx0, w0))
    return nodes, lidx


# --------------------------------------------- whole-solve programs
def _solve_axis(shards: int, mesh) -> "str | None":
    """The mesh axis a sharded program partitions over, or None for the
    single-device build.  ``shards`` and ``mesh`` travel together: the
    engine resolves ``shards -> make_solve_mesh(shards)`` and the
    builders just check consistency."""
    if shards <= 1 and mesh is None:
        return None
    if mesh is None:
        raise ValueError(f"shards={shards} needs a solve mesh")
    from repro.core.mesh import SOLVE_AXIS
    (axis,) = mesh.axis_names
    if axis != SOLVE_AXIS or mesh.devices.size != shards:
        raise ValueError(
            f"mesh {mesh.axis_names}/{mesh.devices.size} does not match "
            f"shards={shards}")
    return axis


def _search_state(cards, n: int, tfm: Transforms, G: int):
    """Initial (B,)-lockstep search state; the ranked-zeta buffer grows a
    leading probe axis for G > 1 (G gates per round, one dispatch)."""
    size = 1 << n
    pc = jnp.asarray(popcounts(n), dtype=jnp.int32)
    B = cards.shape[0]
    batch = (B,) if G == 1 else (G, B)
    singles = jnp.broadcast_to((pc == 1).astype(tfm.dtype),
                               batch + (size,))
    Z0 = jnp.zeros((n + 1,) + batch + (size,), tfm.dtype)
    return Z0.at[1].set(tfm.zeta(singles))


def _gate_builder(cards, pc, dtype):
    def gate_of(gamma):
        """gate(S) = [c(S) <= gamma] for |S| >= 2; singletons/empty pass.
        ``gamma`` (B,) or (G, B) — broadcasts to (..., B, 2^n)."""
        g = (cards <= gamma[..., None]).astype(dtype)
        return jnp.where(pc >= 2, g, jnp.array(1, dtype))
    return gate_of


def _fused_search(cards, cand, lo0, hi0, n, direct_layers, tfm, G,
                  gate_of, shards: int = 1,
                  shard_axis: "str | None" = None,
                  verify_seed: bool = False):
    """The whole-solve lockstep (G+1)-ary search: ONE while_loop whose
    body builds this round's G gates and runs the layered DP.  Returns
    (hi, Z, rounds) with the invariant cand[hi] feasible.

    ``lo0`` is the warm-start floor (cold solves pass zeros).  With
    ``verify_seed=True`` (the layer-cache program variant) a row whose
    ``lo0 = -(idx + 1)`` carries a cached-optimum *hypothesis* at
    candidate ``idx`` — NEVER trusted: one pre-loop dual probe checks
    feasibility at ``idx`` and ``idx - 1`` in a single gated feasibility
    pass.  A verified seed (feasible at ``idx``, infeasible below)
    collapses the bracket so the while_loop exits with zero further
    rounds; a stale seed merely shrinks the bracket monotonically
    (feasible below ⇒ search [0, idx-1]; infeasible at ``idx`` ⇒ search
    [idx+1, hi0]) and the search proceeds to the true optimum —
    correctness never depends on the cache, it only prices rounds.  The
    extraction pass then rebuilds every Z slot >= 2 at the optimum's
    gate, so the result stays bit-identical to the cold search (slot 1
    is the round-invariant singleton transform).  The invariant a
    caller must keep: cand[hi0] is feasible and no candidate below
    ``max(lo0, 0)`` is.

    Under ``shard_axis`` the direct layers inside every round shard
    their gather sweep; the bracket state stays replicated (all inputs
    replicated + per-layer combines ⇒ identical brackets on every
    device, so the while_loop trip count agrees across the mesh)."""
    dl = min(direct_layers, n - 1)
    Z0 = _search_state(cards, n, tfm, G)

    pre_rounds = 0
    if verify_seed:
        has = lo0 < 0
        idx = jnp.where(has, -lo0 - 1, 0)
        lo0 = jnp.maximum(lo0, 0)
        piv = jnp.stack([jnp.maximum(idx - 1, 0), idx])       # (2, B)
        piv = jnp.where(has[None, :], piv, hi0[None, :])
        gamma = jnp.take_along_axis(cand, piv.T, axis=1).T
        Zv = _search_state(cards, n, tfm, 2)
        _, _, ok = feasibility_layers(gate_of(gamma), n, dl, tfm, True,
                                      Z=Zv, scan_middle=True,
                                      shards=shards,
                                      shard_axis=shard_axis)
        lo0, hi0 = bracket_update(lo0, hi0, piv, ok, has)
        pre_rounds = 1                   # the verification sweep is paid

    def cond(state):
        lo, hi, _, _ = state
        return jnp.any(lo < hi)

    def body(state):
        lo, hi, Z, r = state
        active = lo < hi
        if G == 1:
            mid = jnp.where(active, (lo + hi) // 2, hi)
            gamma = jnp.take_along_axis(cand, mid[:, None], axis=1)[:, 0]
            _, Z, ok = feasibility_layers(gate_of(gamma), n, dl, tfm,
                                          True, Z=Z, scan_middle=True,
                                          shards=shards,
                                          shard_axis=shard_axis)
            hi = jnp.where(active & ok, mid, hi)
            lo = jnp.where(active & ~ok, mid + 1, lo)
        else:
            piv = probe_pivots(lo, hi, G)                  # (G, B)
            piv = jnp.where(active[None, :], piv, hi[None, :])
            gamma = jnp.take_along_axis(cand, piv.T, axis=1).T
            _, Z, ok = feasibility_layers(gate_of(gamma), n, dl, tfm,
                                          True, Z=Z, scan_middle=True,
                                          shards=shards,
                                          shard_axis=shard_axis)
            lo, hi = bracket_update(lo, hi, piv, ok, active)
        return lo, hi, Z, r + 1

    lo, hi, Z, rounds = lax.while_loop(
        cond, body, (lo0, hi0, Z0, jnp.int32(0)))
    return hi, Z, rounds + pre_rounds


def _shard_wrap(fn, mesh):
    """Wrap a whole-solve program in ``shard_map`` over the 1-D solve
    mesh.  Every input and output is replicated (``P()``): the sharding
    lives *inside* the program — per-layer subset blocks picked by
    ``axis_index`` — so callers hand in ordinary host arrays and get
    full-lattice results back, and the AOT shapes match the unsharded
    builders exactly.  ``check_vma=False``: the replication checker
    can't see through the scatter/while_loop combines, but every output
    is replicated by construction (each layer ends in a mesh-wide
    ``psum``)."""
    from jax.sharding import PartitionSpec
    P = PartitionSpec()
    return jax.shard_map(fn, mesh=mesh, in_specs=P, out_specs=P,
                         check_vma=False)


def build_max_program(n: int, direct_layers: int, backend: str,
                      extract: bool, gamma_batch: int = 1,
                      shards: int = 1, mesh=None, seeded: bool = False):
    """The whole-solve DPconv[max] program:
    ``(cards, cand, lo0, hi0) -> (opt[, dp, nodes, lidx], rounds)``.

    Shapes bind at compile time: cards (B, 2^n) and cand (B, C) as f64
    bit patterns (int64, ``f64bits``: the gates compare bits, and the
    optimum comes back as the candidate's exact bits), lo0/hi0 (B,)
    int32 — the initial search bracket (cold solves pass
    lo0 = 0; with ``seeded=True`` — a separate compile-time variant, the
    cold program's AOT signature never changes — the layer cache passes
    ``lo0 = -(idx + 1)`` and the search VERIFIES the cached-optimum
    hypothesis with one dual probe before collapsing the bracket, see
    ``_fused_search``).  Search, gate
    construction, layered DP, the extraction table AND the Alg. 2 split
    scan all run on device; the only host transfer is the result tuple.

    ``shards > 1`` runs the program under ``shard_map`` over ``mesh``
    (a ``core.mesh.make_solve_mesh`` 1-D mesh of ``shards`` devices):
    the direct-layer sweeps partition their sets axis per device with
    one collective combine per layer.  Inputs/outputs stay replicated —
    same shapes, bit-identical results.
    """
    pc_np = popcounts(n)
    tfm = transforms(backend)
    dl = min(direct_layers, n - 1)
    G = gamma_batch
    axis = _solve_axis(shards, mesh)

    def fn(cards, cand, lo0, hi0):
        pc = jnp.asarray(pc_np, dtype=jnp.int32)
        with jax.named_scope("search"):
            gate_of = _gate_builder(cards, pc, tfm.dtype)
            hi, Z, rounds = _fused_search(cards, cand, lo0, hi0, n,
                                          direct_layers, tfm, G, gate_of,
                                          shards=shards, shard_axis=axis,
                                          verify_seed=seeded)
            opt = jnp.take_along_axis(cand, hi[:, None], axis=1)[:, 0]
        if not extract:
            return opt, rounds
        # extraction pass: full final layer at the optimum's gate.  For
        # G > 1 the probe axis is dropped — slice 0 of the carried buffer
        # keeps the (round-invariant) singleton transform in slot 1, and
        # every slot >= 2 is rewritten before the recursion reads it.
        with jax.named_scope("extract"):
            Zx = Z if G == 1 else Z[:, 0]
            dp, _, _ = feasibility_layers(gate_of(opt), n, dl, tfm, False,
                                          Z=Zx, scan_middle=True,
                                          shards=shards, shard_axis=axis)
            dp = dp.astype(jnp.int32)              # {0,1}
            nodes, lidx = extract_scan(dp, n)
        return opt, dp, nodes, lidx, rounds

    return _shard_wrap(fn, mesh) if axis is not None else fn


def build_out_program(n: int, extract: bool, shards: int = 1,
                      mesh=None, seeded: bool = False):
    """The whole-solve connected C_out program (DPccp semantics):
    ``(cards, conn) -> (cout[, dp, nodes, lidx])`` — or, with
    ``seeded=True``, ``(cards, conn, seed_vals, seed_ok) -> ...``: the
    incremental-planning variant whose (min,+) sweep replays cached
    sub-table values where ``seed_ok`` (see
    ``minplus_connected_layers``).  A separate compile-time variant
    keeps the cold program's AOT signature untouched.

    Shapes bind at compile time: cards (B, 2^n) f64 bit patterns (int64,
    as are the returned values), conn (B, 2^n) bool — the per-query
    connected-subset masks, precomputed on the host from
    each query graph (``dpccp.connectivity_masks``).  The (min,+) layer
    sweep runs under per-subset valid-split masks derived from ``conn``
    (the DPccp csg/cmp search space as bitset tensors), and the Alg. 2
    masked-scan extraction reads the same value table — disconnected
    witnesses carry +inf error, so the extracted tree is restricted to
    connected csg/cmp pairs by construction.  There is no search loop:
    C_out needs no gamma probing, so the program is a straight-line
    layer sweep and the whole batched solve is trivially ONE dispatch.

    Bit-identical optima, DP tables and trees to ``dpccp_with_tree``
    (tests/test_out_parity.py's property harness is the machine check).
    """
    axis = _solve_axis(shards, mesh)

    def body(cards, conn, seed_vals=None, seed_ok=None):
        with jax.named_scope("search"):
            dpv = minplus_connected_layers(cards, conn, n, shards=shards,
                                           shard_axis=axis,
                                           seed_vals=seed_vals,
                                           seed_ok=seed_ok)
        cout = dpv[..., -1]
        if not extract:
            return (cout,)
        with jax.named_scope("extract"):
            nodes, lidx = extract_scan(dpv, n, card=cards)
        return cout, dpv, nodes, lidx

    if seeded:                          # fixed arity for shard_map specs
        fn = lambda cards, conn, sv, so: body(cards, conn, sv, so)
    else:
        fn = lambda cards, conn: body(cards, conn)
    return _shard_wrap(fn, mesh) if axis is not None else fn


def build_cap_program(n: int, direct_layers: int, backend: str,
                      extract: bool, gamma_batch: int = 1,
                      connected: bool = False, shards: int = 1,
                      mesh=None, seeded: bool = False):
    """The whole-solve C_cap program (paper Sec. 8, both passes fused):
    ``(cards, cand, lo0, hi0, caps) ->
    (gamma, cout[, nodes, lidx], rounds)``, every f64 as int64 bits.

    Pass 1 is the same lockstep feasibility search as DPconv[max]
    (gamma* = optimal C_max); pass 2 runs the (min,+) value program under
    the gamma-slack gate; pass 3 extracts the C_out witness tree — all
    inside one dispatch.  ``caps`` (B, C) holds, per candidate, the cap
    its C_max optimum implies — ``cand * slack`` with the Sec. 11
    resource-aware knob, multiplied on the host so the device multiplies
    no f64; gamma = caps[optimum index].

    ``connected=True`` is the no-cross-products cap: the program grows a
    ``conn`` input (the per-query connected-subset masks
    ``build_out_program`` consumes) and pass 2 runs the *connected*
    (min,+) sweep under the combined ``gamma-gate & connected`` mask —
    the DPccp search space pruned by the cap.  Bit-identical to the host
    pipeline ``dpconv_max`` + ``dpccp(prune_gamma=gamma)``: a split half
    over gamma carries dp = +inf in both forms, so masking splits by the
    combined gate adds no pair the enumerator would score differently.
    NB: the cap is still the *full-lattice* C_max optimum (matching the
    host pipeline), which a cross-product-free plan may not attain —
    ``cout`` is then +inf, exactly like the host's pruned enumeration.
    """
    pc_np = popcounts(n)
    tfm = transforms(backend)
    G = gamma_batch
    axis = _solve_axis(shards, mesh)

    def fn(cards, cand, lo0, hi0, caps, conn=None):
        pc = jnp.asarray(pc_np, dtype=jnp.int32)
        with jax.named_scope("search"):
            gate_of = _gate_builder(cards, pc, tfm.dtype)
            hi, _, rounds = _fused_search(cards, cand, lo0, hi0, n,
                                          direct_layers, tfm, G, gate_of,
                                          shards=shards, shard_axis=axis,
                                          verify_seed=seeded)
            gamma = jnp.take_along_axis(caps, hi[:, None], axis=1)[:, 0]
        with jax.named_scope("extract"):
            gate_ok = (cards <= gamma[:, None]) | (pc < 2)
            if connected:
                dpv = minplus_connected_layers(cards, gate_ok & conn, n,
                                               shards=shards,
                                               shard_axis=axis)
            else:
                dpv = minplus_value_layers(cards, gate_ok, n,
                                           shards=shards, shard_axis=axis)
            cout = dpv[..., -1]
            if not extract:
                return gamma, cout, rounds
            nodes, lidx = extract_scan(dpv, n, card=cards)
        return gamma, cout, nodes, lidx, rounds

    if axis is None:
        return fn
    if connected:                       # fixed arity for shard_map specs
        return _shard_wrap(
            lambda c, d, l, h, s, cn: fn(c, d, l, h, s, cn), mesh)
    return _shard_wrap(lambda c, d, l, h, s: fn(c, d, l, h, s), mesh)


def program_card(n: int, cost: str, backend: str = "xla",
                 gamma_batch: int = 1, extract: bool = True,
                 shards: int = 1) -> dict:
    """Static description of one whole-solve lattice program.

    Consumed by the engine's per-dispatch profiling records
    (``engine.DispatchRecord`` meta): the structural facts an operator
    wants next to a slow dispatch — which semiring passes run, how many
    DP layers, the subset-lattice width, the search arity — without
    re-deriving them from the program builders.
    """
    semirings = {
        "max": ["feasibility(count)"],
        "max_seeded": ["feasibility(count), verified warm start"],
        "cap": ["feasibility(count)", "(min,+)"],
        "cap_seeded": ["feasibility(count), verified warm start",
                       "(min,+)"],
        "cap_conn": ["feasibility(count)", "(min,+) connected"],
        "cap_conn_seeded": ["feasibility(count), verified warm start",
                            "(min,+) connected"],
        "out": ["(min,+) connected"],
        "out_seeded": ["(min,+) connected, seeded"],
    }
    if cost not in semirings:
        raise ValueError(f"unknown fused cost {cost!r}")
    searched = cost not in ("out", "out_seeded")
    card = {
        "cost": cost,
        "backend": backend if searched else "xla",
        "semirings": semirings[cost],
        "layers": n - 1,                # DP layers per value sweep
        "subset_lattice": 1 << n,       # cells per query per layer
        "search": (f"lockstep {gamma_batch + 1}-ary" if searched
                   else "none"),
        "extract": bool(extract),
        "shards": int(shards),
    }
    card["dtype"] = (str(np.dtype(transforms(backend).dtype))
                     if searched else "float64 bits")
    return card
