"""Fused DPconv[max] engine: bit-exact parity with the host loop and the
O(3^n) oracles, executable-cache behavior, and non-regression of the
host-only variants (gamma_batch, early_exit) it must leave intact."""
import numpy as np
import pytest

from repro.core import engine
from repro.core.bitset import popcounts
from repro.core.dpconv import optimize, optimize_batch
from repro.core.dpconv_max import dpconv_max, dpconv_max_batch, \
    dpconv_max_ref
from repro.core.layered import feasibility_dp_ref
from repro.core.querygraph import (chain, clique, cycle, grid,
                                   make_cardinalities, random_sparse, star)


def _instances(n, seeds, makers=None):
    makers = makers or [clique, chain, star, cycle,
                        lambda k: random_sparse(k, 2, seed=5)]
    qs, cards = [], []
    for i, seed in enumerate(seeds):
        q = makers[i % len(makers)](n)
        qs.append(q)
        cards.append(make_cardinalities(q, seed=seed))
    return qs, cards


# ------------------------------------------------------------- bit parity
@pytest.mark.parametrize("n", [3, 5, 6, 7, 9])
def test_fused_matches_host_and_oracle(n):
    qs, cards = _instances(n, seeds=[0, 1, 2, 3])
    fs = engine.fused_dpconv_max(np.stack(cards), n)
    host = dpconv_max_batch(np.stack(cards), n, engine="host")
    assert fs.dispatches == 1
    for b, (q, card) in enumerate(zip(qs, cards)):
        ref = dpconv_max_ref(card, n)
        assert fs.optima[b] == ref                     # bit-identical
        assert fs.optima[b] == host[b].optimum
        assert fs.trees[b].validate()
        assert fs.trees[b].cost_max(card) == fs.optima[b]
        # identical extraction table -> identical tree
        assert repr(fs.trees[b]) == repr(host[b].tree)
    # host passes = fused rounds + extraction (same pivot sequence)
    assert fs.passes == host[0].feasibility_passes


def test_fused_grid_topologies():
    for q in (grid(2, 3), grid(2, 4), grid(3, 3)):
        card = make_cardinalities(q, seed=13)
        res = dpconv_max(q, card)              # default engine = fused
        assert res.engine == "fused" and res.dispatches == 1
        assert res.optimum == dpconv_max_ref(card, q.n)
        assert res.tree.validate()
        assert res.tree.cost_max(card) == res.optimum


def test_fused_random_cardinalities_property():
    """Arbitrary positive tables (no submultiplicativity), n = 6."""
    n = 6
    q = clique(n)
    for seed in range(8):
        rng = np.random.default_rng(seed)
        card = rng.integers(1, 1000, 1 << n).astype(np.float64)
        fs = engine.fused_dpconv_max(card[None], n)
        assert fs.optima[0] == dpconv_max_ref(card, n)
        assert fs.trees[0].cost_max(card) == fs.optima[0]


def test_fused_n12_extraction_free():
    """One larger lattice (2^12) against the host loop."""
    n = 12
    q = grid(3, 4)
    cards = np.stack([make_cardinalities(q, seed=s) for s in (0, 1)])
    fs = engine.fused_dpconv_max(cards, n, extract_tree=False)
    host = dpconv_max_batch(cards, n, engine="host", extract_tree=False)
    assert list(fs.optima) == [h.optimum for h in host]
    assert fs.trees == [None, None]


def test_fused_dp_table_matches_feasibility_ref():
    """The extraction table is the reference feasibility DP at the
    optimum's gate."""
    n = 6
    qs, cards = _instances(n, seeds=[7, 8])
    fs = engine.fused_dpconv_max(np.stack(cards), n)
    pc = popcounts(n)
    for b, card in enumerate(cards):
        gate = np.where(pc >= 2, (card <= fs.optima[b]).astype(float), 1.0)
        assert np.array_equal(fs.dp[b], feasibility_dp_ref(gate, n))


def test_fused_direct_layer_sweep():
    q = clique(8)
    card = make_cardinalities(q, seed=3)
    ref = dpconv_max_ref(card, 8)
    for dl in (0, 2, 4, 8):
        fs = engine.fused_dpconv_max(card[None], 8, direct_layers=dl)
        assert fs.optima[0] == ref


def test_fused_pallas_backend_bit_identical():
    n = 6
    qs, cards = _instances(n, seeds=[11, 12, 13])
    xla = engine.fused_dpconv_max(np.stack(cards), n, backend="xla")
    pal = engine.fused_dpconv_max(np.stack(cards), n, backend="pallas")
    assert list(pal.optima) == list(xla.optima)
    for t in pal.trees:
        assert t.validate()


def test_fused_odd_batch_padding():
    """B = 5 pads to the 8-bucket; results cover only the real rows."""
    n = 5
    qs, cards = _instances(n, seeds=[0, 1, 2, 3, 4])
    fs = engine.fused_dpconv_max(np.stack(cards), n)
    assert len(fs.optima) == 5 and len(fs.trees) == 5
    for b, card in enumerate(cards):
        assert fs.optima[b] == dpconv_max_ref(card, n)


# ----------------------------------------------------- facade & host paths
def test_dpconv_max_defaults_to_fused_engine():
    q = clique(6)
    card = make_cardinalities(q, seed=0)
    res = dpconv_max(q, card)
    assert res.engine == "fused" and res.dispatches == 1
    host = dpconv_max(q, card, engine="host")
    assert host.engine == "host"
    assert host.dispatches == host.feasibility_passes > 1
    assert res.optimum == host.optimum


@pytest.mark.parametrize("gamma_batch", [2, 4])
def test_gamma_batch_runs_fused(gamma_batch):
    """(G+1)-ary probing is folded into the fused while loop: same
    optimum and tree, fewer rounds, still one dispatch.  The host loop
    keeps its own gamma_batch implementation as the parity reference;
    the host BATCH loop is binary-only and refuses the knob."""
    q = clique(7)
    card = make_cardinalities(q, seed=3)
    res = dpconv_max(q, card, gamma_batch=gamma_batch)
    assert res.engine == "fused" and res.dispatches == 1
    assert res.optimum == dpconv_max_ref(card, 7)
    assert res.tree.cost_max(card) == res.optimum
    binary = dpconv_max(q, card)
    assert res.optimum == binary.optimum
    assert res.feasibility_passes <= binary.feasibility_passes
    host = dpconv_max(q, card, gamma_batch=gamma_batch, engine="host",
                      extract_tree=False)
    assert host.engine == "host" and host.optimum == res.optimum
    with pytest.raises(ValueError):
        dpconv_max_batch(np.stack([card, card]), 7, engine="host",
                         gamma_batch=gamma_batch)


def test_early_exit_still_host_path():
    q = clique(7)
    card = make_cardinalities(q, seed=1)
    res = dpconv_max(q, card, early_exit=True, extract_tree=False)
    assert res.engine == "host"
    assert res.optimum == dpconv_max_ref(card, 7)
    with pytest.raises(ValueError):
        dpconv_max(q, card, early_exit=True, engine="fused")


def test_dp_fn_override_still_host_path():
    from repro.service.batch import pallas_dp_fn
    n = 6
    _, cards = _instances(n, seeds=[1, 2])
    rs = dpconv_max_batch(np.stack(cards), n, dp_fn=pallas_dp_fn(n))
    assert all(r.engine == "host" for r in rs)
    with pytest.raises(ValueError):
        dpconv_max_batch(np.stack(cards), n, dp_fn=pallas_dp_fn(n),
                         engine="fused")
    with pytest.raises(ValueError):
        dpconv_max_batch(np.stack(cards), n, engine="warp")


def test_optimize_facade_reports_engine():
    q = chain(6)
    card = make_cardinalities(q, seed=2)
    r = optimize(q, card, cost="max")
    assert r.meta["engine"] == "fused" and r.meta["dispatches"] == 1
    rh = optimize(q, card, cost="max", engine="host")
    assert rh.meta["engine"] == "host"
    assert r.cost == rh.cost
    rs = optimize_batch([q, q], [card, card], cost="max")
    assert all(x.meta["engine"] == "fused" for x in rs)


# -------------------------------------------------------- executable cache
def test_executable_cache_steady_state():
    n = 6
    _, cards = _instances(n, seeds=[21, 22, 23, 24])
    stacked = np.stack(cards)
    engine.fused_dpconv_max(stacked, n)       # populate (trace+compile)
    engine.reset_stats()
    for _ in range(3):
        engine.fused_dpconv_max(stacked, n)
    st = engine.stats()
    assert st.solves == 3 and st.dispatches == 3
    assert st.exec_cache_misses == 0          # steady state: no re-trace
    assert st.exec_cache_hits == 3
    assert st.queries == 12


def test_executable_cache_keys_on_shape_buckets():
    n = 5
    _, cards = _instances(n, seeds=[1, 2])
    engine.clear_executable_cache()
    engine.reset_stats()
    engine.fused_dpconv_max(np.stack(cards), n)
    misses0 = engine.stats().exec_cache_misses
    assert misses0 == 1
    # same shape bucket -> executable reused
    engine.fused_dpconv_max(np.stack(cards), n)
    assert engine.stats().exec_cache_misses == misses0
    assert engine.stats().exec_cache_hits == 1
    # doubled B -> a new (B_bucket,) key, exactly one more compile
    engine.fused_dpconv_max(np.stack(cards + cards), n)
    assert engine.stats().exec_cache_misses == misses0 + 1


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_placement(tmp_path, monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    without it the cache goes to one fixed path under the given root.
    ``jax.config.update`` is captured, so the test turns no cache on."""
    set_calls = []
    monkeypatch.setattr(engine.jax.config, "update",
                        lambda k, v: set_calls.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(tmp_path / ".jax_cache")
        assert engine.use_compile_cache(str(tmp_path)) == want
        assert set_calls == [("jax_compilation_cache_dir", want)]
    else:
        where = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", where)
        assert engine.use_compile_cache(str(tmp_path)) == where
        assert set_calls == []
