"""The 1-D solve mesh for sharded lattice solves.

Functions, not module constants: importing this module never touches jax
device state.

The mesh axis is ``Auto``: the solve programs run under ``shard_map``,
written for the partitioner to propagate shardings (``jax.make_mesh``
defaults to ``Explicit`` axes).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


SOLVE_AXIS = "solve"


def make_solve_mesh(shards: "int | None" = None):
    """1-D mesh for sharded lattice solves: the (min,+)/zeta layer
    sweeps partition their per-layer subset blocks over this axis
    (``repro.core.lattice`` under ``shard_map``), one ``psum``
    combine per layer.  ``shards=None`` takes every visible device; on
    CPU force more with ``XLA_FLAGS=--xla_force_host_platform_device_
    count=8`` (tests and CI do).
    """
    n = len(jax.devices())
    d = n if shards is None else int(shards)
    if not 1 <= d <= n:
        raise ValueError(f"solve mesh wants {d} devices, have {n}")
    return _auto_mesh((d,), (SOLVE_AXIS,))


def mesh_fingerprint(mesh) -> tuple:
    """Stable identity of a mesh's device assignment — extends the
    engine's AOT-cache keys so executables compiled for different
    meshes (or device counts) never alias, and profiling records say
    which devices a dispatch ran on."""
    devs = list(mesh.devices.flat)
    return (devs[0].platform, tuple(int(d.id) for d in devs))
