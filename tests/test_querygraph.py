"""Query graphs, connectivity, and the cardinality model invariants."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.querygraph import (QueryGraph, clique, chain, star, cycle,
                                   random_sparse, make_cardinalities,
                                   paper_clique_instance, permute_card)
from repro.core.bitset import popcounts, bits_of, popcount_int


def test_connectivity_basics():
    q = chain(4)               # 0-1-2-3
    assert q.is_connected(0b0011)
    assert q.is_connected(0b1111)
    assert not q.is_connected(0b0101)       # {0, 2} not adjacent
    assert not q.is_connected(0)


def test_connected_mask_matches_pointwise():
    for maker in (chain, star, cycle, clique):
        q = maker(6)
        mask = q.connected_mask()
        for s in range(1, 1 << 6):
            assert mask[s] == q.is_connected(s), (maker.__name__, s)


def test_hyperedge_connectivity():
    # 0-1 edge; hyperedge ({0,1}, {2,3}) connects the pairs as groups
    q = QueryGraph(4, ((0, 1), (2, 3)), hyperedges=((0b0011, 0b1100),))
    assert q.is_connected(0b1111)
    assert not q.is_connected(0b0101)       # hyperedge needs both sides
    assert q.can_join(0b0011, 0b1100)
    assert not q.can_join(0b0001, 0b1100)


@given(st.integers(0, 10 ** 6))
@settings(max_examples=20, deadline=None)
def test_cardinality_submultiplicative(seed):
    """The paper's evaluation constraint: c(S) <= c(S1) c(S2)."""
    n = 6
    q = random_sparse(n, 3, seed=seed % 100)
    card = make_cardinalities(q, seed=seed)
    size = 1 << n
    rng = np.random.default_rng(seed)
    for _ in range(200):
        s = int(rng.integers(1, size))
        bits = bits_of(s)
        if len(bits) < 2:
            continue
        k = int(rng.integers(1, len(bits)))
        s1 = sum(1 << b for b in bits[:k])
        s2 = s & ~s1
        assert card[s] <= card[s1] * card[s2] * (1 + 1e-9)


def test_cardinality_range_and_cap():
    q, card = paper_clique_instance(8, seed=0)
    assert card.min() >= 1.0
    assert card.max() <= 1e8 * (1 + 1e-12)


def test_bitset_utils():
    assert bits_of(0b1010) == [1, 3]
    assert popcount_int(0b1011) == 3
    pc = popcounts(5)
    for s in range(32):
        assert pc[s] == bin(s).count("1")


def _shift_or_permute_card(card, n, perm):
    """permute_card as n full-width shift-or passes over the lattice."""
    S = np.arange(1 << n, dtype=np.int64)
    Sp = np.zeros(1 << n, dtype=np.int64)
    for i in range(n):
        Sp |= ((S >> i) & 1) << int(perm[i])
    out = np.empty_like(np.asarray(card))
    out[Sp] = np.asarray(card)
    return out


@pytest.mark.parametrize("n", [0, 1, 7, 8, 9, 15, 16])
def test_permute_card_bytewise_index_matches_shift_or(n):
    rng = np.random.default_rng(1000 + n)
    card = rng.uniform(1.0, 1e8, 1 << n)
    for _ in range(3):
        perm = [int(p) for p in rng.permutation(n)]
        got = permute_card(card, n, perm)
        assert got.dtype == card.dtype
        assert got.tobytes() == _shift_or_permute_card(card, n, perm).tobytes()
        inv = [0] * n
        for i, p in enumerate(perm):
            inv[p] = i
        assert permute_card(got, n, inv).tobytes() == card.tobytes()
    # dtype follows the input
    ints = np.arange(1 << n, dtype=np.int32)
    assert permute_card(ints, n, list(range(n))).dtype == np.int32
