"""Canonicalization + plan cache: key invariance and relabeled reuse."""
import functools
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.core.querygraph import (QueryGraph, chain, clique, cycle, grid,
                                   make_cardinalities, permute_card,
                                   random_sparse, relabel, star)
from repro.core.dpconv import optimize
from repro.service.cache import CachedPlan, PlanCache
from repro.service.canon import (canonical_perm, canonicalize, relabel_tree,
                                 subset_signature, topology_signature)
from repro.service.server import PlanServer


# --------------------------------------------------------- canonical keys
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_cache_key_invariant_under_relabeling_random(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 9))
    q = random_sparse(n, extra_edges=int(rng.integers(0, n)), seed=seed)
    card = make_cardinalities(q, seed=seed)
    base = canonicalize(q, card)
    for _ in range(6):
        perm = rng.permutation(n)
        f = canonicalize(relabel(q, perm), permute_card(card, n, perm))
        assert f.key == base.key
        assert f.signature == base.signature
        # canonical forms are literally byte-identical
        assert f.q.edges == base.q.edges
        assert np.array_equal(f.card, base.card)


@pytest.mark.parametrize("maker", [chain, star, cycle, clique])
def test_cache_key_invariant_on_symmetric_topologies(maker):
    """Symmetric graphs exercise the individualization branch (WL alone
    cannot break automorphic ties)."""
    n = 6
    q = maker(n)
    card = make_cardinalities(q, seed=42)
    base = canonicalize(q, card)
    rng = np.random.default_rng(0)
    for _ in range(4):
        perm = rng.permutation(n)
        f = canonicalize(relabel(q, perm), permute_card(card, n, perm))
        assert f.key == base.key


def test_different_queries_different_keys():
    q1 = chain(6)
    card1 = make_cardinalities(q1, seed=0)
    assert canonicalize(q1, card1).key != \
        canonicalize(q1, make_cardinalities(q1, seed=1)).key
    assert canonicalize(q1, card1).key != \
        canonicalize(star(6), card1).key


def test_canonical_form_roundtrips_to_request_labels():
    q = random_sparse(7, 3, seed=5)
    card = make_cardinalities(q, seed=5)
    f = canonicalize(q, card)
    assert sorted(f.perm) == list(range(7))
    # permuting the request by perm gives exactly the canonical form
    assert relabel(q, f.perm).edges == f.q.edges
    assert np.array_equal(permute_card(card, 7, f.perm), f.card)
    # inverse_perm really inverts
    inv = f.inverse_perm
    assert [inv[f.perm[i]] for i in range(7)] == list(range(7))


def test_topology_signature_classes():
    assert topology_signature(chain(6)).endswith("chain")
    assert topology_signature(star(6)).endswith("star")
    assert topology_signature(cycle(6)).endswith("cycle")
    assert topology_signature(clique(6)).endswith("clique")
    assert topology_signature(grid(2, 3)).endswith("sparse")
    tree = QueryGraph(5, ((0, 1), (0, 2), (1, 3), (1, 4)))
    assert topology_signature(tree).endswith("tree")


# --------------------------------------------------------------- LRU cache
def test_lru_eviction_and_stats():
    c = PlanCache(capacity=2)
    p = CachedPlan(cost=1.0, tree=None, meta={})
    c.insert(("a",), p)
    c.insert(("b",), p)
    assert c.lookup(("a",)) is not None        # refreshes 'a'
    c.insert(("c",), p)                        # evicts 'b' (LRU)
    assert c.lookup(("b",)) is None
    assert c.lookup(("a",)) is not None
    assert c.lookup(("c",)) is not None
    s = c.stats
    assert (s.hits, s.misses, s.evictions) == (3, 1, 1)
    assert len(c) == 2


def test_relabeled_request_reuses_cached_plan():
    q = random_sparse(7, 2, seed=3)
    card = make_cardinalities(q, seed=3)
    srv = PlanServer()
    first = srv.plan_one(q, card, cost="max")
    assert not first.cache_hit
    rng = np.random.default_rng(0)
    for _ in range(3):
        perm = rng.permutation(7)
        q2 = relabel(q, perm)
        card2 = permute_card(card, 7, perm)
        resp = srv.plan_one(q2, card2, cost="max")
        assert resp.cache_hit
        # the replayed plan is a valid plan FOR THE RELABELED request
        assert resp.tree.validate()
        assert resp.tree.mask == q2.full_mask
        assert resp.tree.cost_max(card2) == resp.cost
        # and matches a from-scratch solve bit-for-bit
        assert resp.cost == optimize(q2, card2, cost="max").cost
    assert srv.cache.stats.relabel_hits >= 1


def test_cache_disabled_never_hits():
    q = chain(6)
    card = make_cardinalities(q, seed=0)
    srv = PlanServer(enable_cache=False)
    srv.plan_one(q, card, cost="max")
    resp = srv.plan_one(q, card, cost="max")
    assert not resp.cache_hit
    assert srv.cache.stats.lookups == 0


# ------------------------------------------------- pinned canonical keys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "tests", "fixtures", "canon_keys.json")) as fh:
    PINNED = json.load(fh)


@functools.lru_cache(maxsize=None)
def _pin_script():
    """``scripts/pin_canon_keys.py``, which builds the fixture's queries."""
    spec = importlib.util.spec_from_file_location(
        "pin_canon_keys", os.path.join(ROOT, "scripts", "pin_canon_keys.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _pinned_cases() -> dict:
    return {name: (q, card) for name, q, card in _pin_script().cases()}


@pytest.mark.parametrize("entry", PINNED["queries"],
                         ids=[e["name"] for e in PINNED["queries"]])
def test_canonical_form_matches_pinned_fixture(entry):
    """Key, perm and canonical table are bit-identical to the fixture
    written before each leaf's table was built once."""
    q, card = _pinned_cases()[entry["name"]]
    f = canonicalize(q, card)
    assert f.key == entry["key"]
    assert list(f.perm) == entry["perm"]
    assert canonical_perm(q, card) == f.perm
    assert _pin_script().card_sha256(f.card) == entry["card_sha256"]
    assert f.card.dtype == np.float64
    # the form's own graph and table are the request relabelled by perm
    assert f.q == relabel(q, f.perm)
    assert f.card.tobytes() == permute_card(card, q.n, f.perm).tobytes()


@pytest.mark.parametrize("entry", PINNED["fragments"],
                         ids=[e["name"] for e in PINNED["fragments"]])
def test_subset_signature_matches_pinned_fixture(entry):
    q, card = _pinned_cases()[entry["case"]]
    s = subset_signature(q, card, entry["mask"])
    assert s.key == entry["key"]
    assert list(s.rels) == entry["rels"]
    assert list(s.perm) == entry["perm"]


def test_canonical_leaves_count_tables_built():
    cases = _pinned_cases()
    # random cardinalities refine to discrete colours: one leaf
    for name in ("paper_clique15_s0", "paper_star15_s0", "sparse13",
                 "hyper8"):
        assert canonicalize(*cases[name]).leaves == 1, name
    # every relation of the uniform clique ties: the search branches to
    # its cap, and a lower cap builds fewer tables for the same key
    q, card = cases["uniform_clique8"]
    assert canonicalize(q, card).leaves == 64
    capped = canonicalize(q, card, branch_cap=4)
    assert capped.leaves == 4
    assert capped.key == canonicalize(q, card).key
