"""Production mesh builders.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the 'pod'
axis crosses the DCN boundary; FSDP spans (pod, data), TP stays inside a
pod on 'model' (ICI).

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).

Every mesh has ``Auto`` axes: the model code places arrays with
``with_sharding_constraint`` and the solve programs with ``shard_map``,
both written for the partitioner to propagate shardings (``jax.make_mesh``
defaults to ``Explicit`` axes).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by tests
    and the CPU train example."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"))


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
