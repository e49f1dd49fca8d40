"""Batched serving solver: parity with single-query optimize, bit-exact."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.querygraph import (chain, clique, cycle, grid,
                                   make_cardinalities, random_sparse, star)
from repro.core.dpconv import optimize, optimize_batch
from repro.core.dpconv_max import dpconv_max, dpconv_max_batch
from repro.kernels.ops import mobius_batch_op, zeta_batch_op
from repro.kernels.ref import zeta_ref
from repro.service.batch import BatchedSolver, BatchPolicy, pallas_dp_fn


def _mixed_batch(n, seeds):
    makers = [clique, chain, star, cycle,
              lambda k: random_sparse(k, 2, seed=7)]
    qs, cards = [], []
    for i, seed in enumerate(seeds):
        q = makers[i % len(makers)](n)
        qs.append(q)
        cards.append(make_cardinalities(q, seed=seed))
    return qs, cards


@pytest.mark.parametrize("n", [5, 6, 7])
def test_batched_dpconv_max_bit_identical(n):
    qs, cards = _mixed_batch(n, seeds=[0, 1, 2, 3])
    batched = dpconv_max_batch(np.stack(cards), n)
    for q, card, res in zip(qs, cards, batched):
        single = dpconv_max(q, card)
        assert res.optimum == single.optimum        # bit-identical
        assert res.tree.validate()
        assert res.tree.cost_max(card) == res.optimum


def test_batched_facade_matches_optimize():
    qs, cards = _mixed_batch(6, seeds=[5, 6, 7])
    rs = optimize_batch(qs, cards, cost="max")
    assert all(r.meta.get("batched") for r in rs)
    for q, card, r in zip(qs, cards, rs):
        assert r.cost == optimize(q, card, cost="max").cost


def test_batched_facade_mixed_n_falls_back():
    q1, c1 = clique(5), make_cardinalities(clique(5), seed=0)
    q2, c2 = chain(6), make_cardinalities(chain(6), seed=0)
    rs = optimize_batch([q1, q2], [c1, c2], cost="max")
    assert not any(r.meta.get("batched") for r in rs)
    assert rs[0].cost == optimize(q1, c1, cost="max").cost
    assert rs[1].cost == optimize(q2, c2, cost="max").cost


def test_pallas_tier_bit_identical():
    """The int32 Pallas transform backend must agree with the f64 XLA
    path exactly (feasibility is exact integer counting in both)."""
    n = 6
    qs, cards = _mixed_batch(n, seeds=[11, 12])
    ref = dpconv_max_batch(np.stack(cards), n)
    pal = dpconv_max_batch(np.stack(cards), n, dp_fn=pallas_dp_fn(n))
    for r, p in zip(ref, pal):
        assert p.optimum == r.optimum
        assert p.tree.validate()


@pytest.mark.parametrize("n", [11])
def test_pallas_tier_kernel_path(n):
    """n above the kernel threshold exercises the real (non-fallback)
    Pallas grid, batched over the stacked queries (interpret mode)."""
    qs, cards = _mixed_batch(n, seeds=[0, 1])
    ref = dpconv_max_batch(np.stack(cards), n, extract_tree=False)
    pal = dpconv_max_batch(np.stack(cards), n, extract_tree=False,
                           dp_fn=pallas_dp_fn(n))
    assert [p.optimum for p in pal] == [r.optimum for r in ref]


def test_batched_zeta_kernel_parity():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 4, size=(3, 1 << 11)).astype(np.int32))
    z = zeta_batch_op(x)
    assert bool(jnp.all(z == zeta_ref(x)))
    assert bool(jnp.all(mobius_batch_op(z) == x))
    with pytest.raises(ValueError):
        zeta_batch_op(x[0])


def test_batched_solver_orders_and_groups():
    """Mixed-n micro-batch: results come back in request order."""
    items = []
    refs = []
    for n, seed in [(6, 0), (5, 1), (6, 2), (7, 3), (5, 4)]:
        q = clique(n)
        card = make_cardinalities(q, seed=seed)
        items.append((q, card))
        refs.append(optimize(q, card, cost="max").cost)
    solver = BatchedSolver(BatchPolicy(max_batch=8))
    out = solver.solve(items)
    assert [r.cost for r in out] == refs
    assert solver.queries_batched >= 4      # the two pairs went batched
    for (q, card), r in zip(items, out):
        assert r.tree is not None and r.tree.validate()


def test_grid_topology_plans():
    q = grid(2, 3)
    card = make_cardinalities(q, seed=9)
    res = optimize(q, card, cost="max")
    assert res.tree.validate()
    assert res.tree.cost_max(card) == res.cost


def test_solve_mesh_wider_than_devices_is_refused():
    """The server lifts its cap/out ceilings for the policy's mesh width,
    so a width the visible devices cannot hold is an error at
    construction, never a silent single-device fallback."""
    import jax
    from repro.service import PlanServer
    too_wide = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="visible"):
        PlanServer(batch_policy=BatchPolicy(solve_shards=too_wide))
