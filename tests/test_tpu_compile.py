"""Compile-only tests for a described TPU v5e chip.

The TPU compiler is installed here but no chip is attached: a described
``v5e:2x2`` topology lets ``jit(...).lower(...).compile()`` run Mosaic
and XLA:TPU exactly as a chip run would, without running anything.
This catches what interpret mode cannot — primitives the TPU lowering
lacks (scatter), index maps that return int64 under ``jax_enable_x64``,
and int64 operands reaching an int32 kernel.

The topology is described inside the fixture, never at import: only one
process may hold the TPU library, and collection must be identical on
every test worker.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (jax_enable_x64, as in production)
from repro.core import engine, lattice
from repro.kernels.ops import INTERPRET_ENV
from repro.kernels.ranked_conv import ranked_conv_pallas
from repro.kernels.zeta_pallas import _pair_pass, mobius_pallas, zeta_pallas


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [13, 15])
def test_zeta_int32_compiles(one_chip, n):
    c = _compile(lambda f: zeta_pallas(f), one_chip,
                 ((4, 1 << n), jnp.int32))
    assert _has_kernel(c)


def test_mobius_compiles(one_chip):
    c = _compile(lambda f: mobius_pallas(f), one_chip,
                 ((4, 1 << 13), jnp.int32))
    assert _has_kernel(c)


def test_pair_pass_compiles(one_chip):
    c = _compile(lambda f: _pair_pass(f, 8, 2, -1.0, False), one_chip,
                 ((512, 256), jnp.int32))
    assert _has_kernel(c)


def test_ranked_conv_compiles(one_chip):
    n = 15
    c = _compile(lambda z: ranked_conv_pallas(z, 7), one_chip,
                 ((n + 1, 4, 1 << n), jnp.int32))
    assert _has_kernel(c)


def test_fused_max_program_on_pallas_compiles(one_chip, monkeypatch):
    """The whole-solve max program with its transforms on the Pallas
    tier, as the server runs it on a chip (kernels compiled, not
    interpreted)."""
    monkeypatch.setenv(INTERPRET_ENV, "0")
    n, B = 12, 4
    fn = lattice.build_max_program(n, 4, "pallas", True)
    C = engine.candidate_bucket(n)
    c = _compile(fn, one_chip,                  # f64 inputs as int64 bits
                 ((B, 1 << n), jnp.int64), ((B, C), jnp.int64),
                 ((B,), jnp.int32), ((B,), jnp.int32))
    assert _has_kernel(c)
    assert c.memory_analysis() is not None
