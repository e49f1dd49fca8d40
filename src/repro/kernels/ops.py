"""Jit'd public wrappers around the Pallas kernels.

The kernels compile for the TPU.  Interpret mode — the Pallas HLO
interpreter, which is how the CPU tests run them — is asked for
explicitly, either per call (``interpret=True``) or for the process with
``REPRO_PALLAS_INTERPRET=1`` (``tests/conftest.py`` sets it).  It is
never inferred from the backend: on a CPU backend without it a kernel
fails to lower instead of running in a slow stand-in.  The wrappers
also enforce the documented exactness envelopes for counting workloads.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels.zeta_pallas import zeta_pallas
from repro.kernels.ranked_conv import ranked_conv_pallas

INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def interpret_requested() -> bool:
    """Whether the process asked for interpret mode (``INTERPRET_ENV``)."""
    return os.environ.get(INTERPRET_ENV, "") == "1"


def _resolve(interpret: "bool | None") -> bool:
    return interpret_requested() if interpret is None else bool(interpret)


# exactness envelopes (see zeta_pallas docstring / DESIGN.md)
F32_EXACT_LIMIT = float(1 << 24)
I32_EXACT_LIMIT = float(1 << 31)


@functools.partial(jax.jit, static_argnames=("inverse", "interpret"))
def _zeta_jit(f: jnp.ndarray, inverse: bool, interpret: bool):
    return zeta_pallas(f, inverse=inverse, interpret=interpret)


def zeta_op(f: jnp.ndarray, inverse: bool = False,
            interpret: bool | None = None) -> jnp.ndarray:
    return _zeta_jit(f, inverse, _resolve(interpret))


def mobius_op(f: jnp.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    return zeta_op(f, inverse=True, interpret=interpret)


# ------------------------------------------------------- batched wrappers
# The plan-serving batched solver (repro.service.batch) stacks B same-n
# feasibility tables as (B, 2^n) and transforms them in ONE kernel launch:
# zeta_pallas folds leading axes into the kernel row dimension, so the
# whole batch shares a grid instead of paying B launches.  Counting
# workloads must use the int32 path (exact < 2^31, i.e. n <= 15 for the
# 2^{2n} feasibility counts); f32 is for value workloads within the
# 2^24 envelope.
def zeta_batch_op(f: jnp.ndarray, inverse: bool = False,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Batched zeta/Moebius over the last axis of a (..., 2^n) stack."""
    if f.ndim < 2:
        raise ValueError("zeta_batch_op expects a leading batch axis; "
                         "use zeta_op for flat tables")
    return zeta_op(f, inverse=inverse, interpret=interpret)


def mobius_batch_op(f: jnp.ndarray,
                    interpret: bool | None = None) -> jnp.ndarray:
    return zeta_batch_op(f, inverse=True, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _ranked_conv_jit(Z: jnp.ndarray, k: int, interpret: bool):
    return ranked_conv_pallas(Z, k, interpret=interpret)


def ranked_conv_op(Z: jnp.ndarray, k: int,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Fused layer-k ranked convolution of a (n+1, ..., 2^n) ranked zeta
    table; leading axes are batch dimensions folded into the kernel grid
    (one launch for the whole stack).  The lattice layer's host-loop
    instantiation routes its middle-layer convolutions here on the
    Pallas tier (``lattice.Transforms.ranked_conv``)."""
    return _ranked_conv_jit(Z, k, _resolve(interpret))
