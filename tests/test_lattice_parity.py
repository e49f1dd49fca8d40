"""Cross-engine parity over the lattice-program layer: TREES (not just
optima) bit-identical between the host loop, the fused binary-probe
path, the fused gamma-probe path, fused on-device extraction, and the
fused C_cap pass — over random + clique + chain + star graphs, against
the O(3^n) oracles."""
import numpy as np
import pytest

from repro.core import engine, f64bits, jointree, lattice
from repro.core.baselines import dpsub
from repro.core.bitset import popcounts
from repro.core.ccap import ccap, ccap_batch
from repro.core.dpconv import optimize_batch
from repro.core.dpconv_max import dpconv_max, dpconv_max_batch, \
    dpconv_max_ref
from repro.core.querygraph import (chain, clique, cycle,
                                   make_cardinalities, random_sparse,
                                   star)

MAKERS = [clique, chain, star, lambda k: random_sparse(k, 2, seed=5)]


def _instances(n, seeds):
    qs, cards = [], []
    for i, seed in enumerate(seeds):
        q = MAKERS[i % len(MAKERS)](n)
        qs.append(q)
        cards.append(make_cardinalities(q, seed=seed))
    return qs, cards


# ------------------------------------------------------ C_max tree parity
@pytest.mark.parametrize("n", [4, 6, 7])
def test_trees_identical_across_all_max_paths(n):
    """host loop == fused binary == fused gamma == host re-extraction of
    the fused table, tree for tree."""
    qs, cards = _instances(n, seeds=[0, 1, 2, 3])
    stacked = np.stack(cards)
    host = dpconv_max_batch(stacked, n, engine="host")
    fused = engine.fused_dpconv_max(stacked, n)
    gamma = engine.fused_dpconv_max(stacked, n, gamma_batch=3)
    assert fused.dispatches == 1 and gamma.dispatches == 1
    for b, card in enumerate(cards):
        ref = dpconv_max_ref(card, n)
        assert fused.optima[b] == ref == gamma.optima[b]
        t_host = repr(host[b].tree)
        # device extraction scan == host Alg. 2 recursion, same witness
        assert repr(fused.trees[b]) == t_host
        assert repr(gamma.trees[b]) == t_host
        re_host = jointree.extract_tree_feasibility(fused.dp[b], card, n)
        assert repr(re_host) == t_host


def test_gamma_probe_reduces_rounds_at_equal_answers():
    n = 8
    qs, cards = _instances(n, seeds=[0, 1, 2, 3])
    stacked = np.stack(cards)
    binary = engine.fused_dpconv_max(stacked, n)
    probed = engine.fused_dpconv_max(stacked, n, gamma_batch=3)
    assert list(binary.optima) == list(probed.optima)
    assert [repr(t) for t in binary.trees] == \
        [repr(t) for t in probed.trees]
    assert probed.rounds < binary.rounds


def test_single_query_gamma_auto_routes_fused():
    q = clique(7)
    card = make_cardinalities(q, seed=4)
    r = dpconv_max(q, card, gamma_batch=4)
    assert r.engine == "fused" and r.dispatches == 1
    assert r.optimum == dpconv_max_ref(card, 7)


# ------------------------------------------------------- C_cap parity
@pytest.mark.parametrize("n", [4, 6, 7])
def test_fused_cap_bit_identical_to_host_pipeline(n):
    qs, cards = _instances(n, seeds=[7, 8, 9, 10])
    fc = ccap_batch(qs, np.stack(cards), n)
    assert all(r.engine == "fused" and r.dispatches == 1 for r in fc)
    for b, (q, card) in enumerate(zip(qs, cards)):
        host = ccap(q, card, engine="host")
        assert fc[b].gamma == host.gamma          # bit-identical cap
        assert fc[b].cout == host.cout            # bit-identical C_out
        assert repr(fc[b].tree) == repr(host.tree)
        # and against the raw oracle tables
        gmax = dpconv_max(q, card, engine="host",
                          extract_tree=False).optimum
        dp2 = dpsub(card, n, mode="out", prune_gamma=gmax)
        assert fc[b].gamma == gmax and fc[b].cout == dp2[-1]


def test_fused_cap_slack_matches_host():
    q = clique(6)
    card = make_cardinalities(q, seed=2)
    for slack in (1.0, 1.5, 4.0):
        f = ccap(q, card, gamma_slack=slack)
        h = ccap(q, card, gamma_slack=slack, engine="host")
        assert f.engine == "fused" and h.engine == "host"
        assert (f.gamma, f.cout) == (h.gamma, h.cout)
        assert repr(f.tree) == repr(h.tree)


def test_fused_cap_rejects_non_dpsub_pass2():
    q = clique(5)
    card = make_cardinalities(q, seed=0)
    with pytest.raises(ValueError):
        ccap(q, card, engine_pass2="dpccp", engine="fused")
    # auto quietly takes the host pipeline for the dpccp pass
    r = ccap(q, card, engine_pass2="dpccp")
    assert r.engine == "host"


@pytest.mark.parametrize("n", [5, 6, 7])
def test_fused_connected_cap_matches_host_dpccp_pipeline(n):
    """The cap-lane connectivity gate: pass 2 under the connected-split
    masks is bit-identical to the host dpconv_max + dpccp(prune_gamma)
    pipeline — gamma, C_out AND tree — including the cap-infeasible
    case (no cross-product-free plan attains the full-lattice gamma*),
    where both sides report +inf."""
    from repro.core.dpccp import dpccp
    qs = [chain(n), star(n), cycle(n), random_sparse(n, 2, seed=5)]
    cards = [make_cardinalities(q, seed=20 + i)
             for i, q in enumerate(qs)]
    fc = engine.fused_ccap(np.stack(cards), n, qs=qs)
    assert fc.dispatches == 1
    for b, (q, card) in enumerate(zip(qs, cards)):
        gamma = dpconv_max(q, card, engine="host",
                           extract_tree=False).optimum
        assert fc.gammas[b] == gamma
        dp, _ = dpccp(q, card, mode="out", prune_gamma=gamma)
        if np.isfinite(dp[-1]):
            assert fc.couts[b] == dp[-1]
            host_tree = jointree.extract_tree_out(dp, card, n)
            assert repr(fc.trees[b]) == repr(host_tree)
            assert all(q.is_connected(m)
                       for m in fc.trees[b].internal_masks())
        else:
            assert not np.isfinite(fc.couts[b])


def test_ccap_connected_host_and_fused_agree():
    q = chain(6)
    card = make_cardinalities(q, seed=3)
    # guard the instance choice: the connected cap must be feasible for
    # the ccap entry (its assertion fires otherwise) — slack 2 makes the
    # DPccp space comfortably admissible on this seed
    f = ccap(q, card, connected=True, gamma_slack=2.0)
    h = ccap(q, card, connected=True, engine="host", gamma_slack=2.0)
    assert f.engine == "fused" and f.dispatches == 1
    assert h.engine == "host"
    assert (f.gamma, f.cout) == (h.gamma, h.cout)
    assert repr(f.tree) == repr(h.tree)
    # more search space never hurts: the full-lattice cap C_out is a
    # lower bound on the cross-product-free one
    full = ccap(q, card, gamma_slack=2.0)
    assert full.cout <= f.cout
    # the fused route refuses what DPccp semantics cannot express
    with pytest.raises(ValueError):
        ccap(q, card, connected=True, engine="fused",
             engine_pass1="dpsub")


def test_optimize_batch_cap_lane():
    qs, cards = _instances(6, seeds=[3, 4, 5])
    rs = optimize_batch(qs, cards, cost="cap")
    assert all(r.meta.get("batched") and r.meta["engine"] == "fused"
               for r in rs)
    for q, card, r in zip(qs, cards, rs):
        h = ccap(q, card, engine="host")
        assert float(r.cost) == h.cout
        assert r.meta["gamma"] == h.gamma


# --------------------------------------------- lattice-layer primitives
def test_minplus_value_layers_bitwise_vs_dpsub():
    n = 6
    _, cards = _instances(n, seeds=[0, 1])
    pc = popcounts(n)
    for card in cards:
        for gamma in (np.inf, float(np.sort(card)[-3])):
            gate_ok = (card <= gamma) | (pc < 2)
            dev = f64bits.from_bits(lattice.minplus_value_layers(
                f64bits.to_bits(card)[None, :], gate_ok[None, :], n))[0]
            ref = dpsub(card, n, mode="out",
                        prune_gamma=None if np.isinf(gamma) else gamma)
            assert np.array_equal(dev, ref)


def test_extract_scan_matches_host_witness_rule():
    n = 6
    rng = np.random.default_rng(0)
    from repro.core.layered import feasibility_dp_ref
    pc = popcounts(n)
    for seed in range(4):
        card = rng.integers(1, 50, 1 << n).astype(np.float64)
        gamma = dpconv_max_ref(card, n)
        gate = np.where(pc >= 2, (card <= gamma).astype(float), 1.0)
        dp = feasibility_dp_ref(gate, n)
        nodes, lidx = lattice.extract_scan(np.asarray(dp)[None, :], n)
        dev = jointree.tree_from_split_arrays(np.asarray(nodes)[0],
                                              np.asarray(lidx)[0])
        host = jointree.extract_tree_feasibility(dp, card, n)
        assert repr(dev) == repr(host)
        assert dev.validate() and dev.cost_max(card) == gamma


def test_feasibility_layers_forms_agree():
    """Unrolled (host) and scan-form (fused) middle layers produce the
    same table — the single-implementation guarantee."""
    import jax.numpy as jnp
    n = 7
    q = clique(n)
    card = make_cardinalities(q, seed=6)
    pc = popcounts(n)
    gamma = float(np.median(card))
    gate = jnp.asarray(
        np.where(pc >= 2, (card <= gamma).astype(float), 1.0))
    tfm = lattice.transforms("xla")
    for shortcut in (False, True):
        dp_u, _, feas_u = lattice.feasibility_layers(
            gate[None, :], n, 4, tfm, shortcut, scan_middle=False)
        dp_s, _, feas_s = lattice.feasibility_layers(
            gate[None, :], n, 4, tfm, shortcut, scan_middle=True)
        assert bool(feas_u[0]) == bool(feas_s[0])
        if not shortcut:
            assert np.array_equal(np.asarray(dp_u), np.asarray(dp_s))


# ------------------------------------------------------------- prewarm
def test_prewarm_covers_serving_buckets():
    from repro.service import PlanServer, WorkloadSpec, make_workload
    from repro.service.batch import BatchPolicy
    engine.clear_executable_cache()
    reqs = make_workload(WorkloadSpec(n_requests=24, seed=5,
                                      n_range=(5, 7)))
    srv = PlanServer(max_batch=4,
                     batch_policy=BatchPolicy(max_batch=4))
    pw = srv.prewarm(sorted({r.q.n for r in reqs}))
    assert pw["compiled"] > 0
    engine.reset_stats()
    srv.serve(list(reqs), closed_loop=True)
    st = engine.stats()
    assert st.exec_cache_misses == 0          # no cold buckets survive
    assert st.dispatches == st.solves
    assert st.host_extractions == 0


# ------------------------------------------------------- replay lane
def test_einsum_replay_workload_parity():
    from repro.core.dpconv import optimize
    from repro.service import (PlanServer, WorkloadSpec,
                               make_einsum_workload)
    reqs = make_einsum_workload(WorkloadSpec(n_requests=24, seed=2))
    assert {r.q.n for r in reqs} and all(r.q.n >= 2 for r in reqs)
    srv = PlanServer(max_batch=8)
    resps, _ = srv.serve(list(reqs), closed_loop=True)
    for req, resp in zip(reqs, resps):
        if resp.route.method in ("goo", "approx"):
            continue
        if req.cost == "cap":
            ref = optimize(req.q, req.card, cost="cap", engine="host")
        else:
            kw = dict(resp.route.kw())
            if resp.route.method == "dpconv" and req.cost == "max":
                kw["engine"] = "host"
            ref = optimize(req.q, req.card, cost=req.cost,
                           method=resp.route.method, **kw)
        assert float(resp.cost) == float(ref.cost)
