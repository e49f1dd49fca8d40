"""The lattice the benchmark's plain references are built from.

Written from the cost models' definitions and sharing nothing with the
program.  Each cost function's reference, ``bench/costs/<cost>.py``,
states its dynamic program over submask splits with these pieces: the
split pairs of every set, one popcount layer at a time; the relations
adjacent to a set and which sets are connected; the layered sweep

    DP[{i}] = 0
    DP[S] = close(min_{A+B=S, keep(A, B)} pair(DP[A], DP[B]), c(S))

in the precision it is given (float64 is the configurations' own;
float32 is the control, the step below it, which the comparison must
refuse); the extraction of a tree whose every split realises its DP
value; and the walk that checks a tree covers every relation once.
"""
from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=4)
def split_pairs(n: int) -> tuple:
    """Every (S, A) with A a proper non-empty submask of S that holds S's
    lowest bit (so each unordered split appears once), sorted by
    popcount(S) then S.  Returns (S, A, layer_start) where rows of layer
    k are ``layer_start[k]:layer_start[k + 1]``."""
    size = 1 << n
    S = np.arange(size, dtype=np.int64)
    pc = popcounts(n)
    S = S[pc >= 2]
    low = S & -S
    rest = S ^ low
    out_s, out_a = [], []
    sub = rest.copy()                    # walk the submasks of rest
    live = np.ones(len(S), bool)
    while live.any():
        idx = np.nonzero(live)[0]
        s, r, lo_, b = S[idx], rest[idx], low[idx], sub[idx]
        keep = b != r                    # A = low|b must leave B non-empty
        out_s.append(s[keep])
        out_a.append((lo_ | b)[keep])
        done = b == 0
        sub[idx] = (b - 1) & r
        live[idx[done]] = False
    s_all = np.concatenate(out_s)
    a_all = np.concatenate(out_a)
    order = np.lexsort((s_all, pc[s_all]))
    s_all, a_all = s_all[order], a_all[order]
    starts = np.searchsorted(pc[s_all], np.arange(n + 2))
    return s_all, a_all, starts


@functools.lru_cache(maxsize=4)
def popcounts(n: int) -> np.ndarray:
    pc = np.zeros(1 << n, dtype=np.int64)
    for j in range(n):
        pc[1 << j:2 << j] = pc[:1 << j] + 1
    return pc


@functools.lru_cache(maxsize=512)      # repeated templates share graphs
def adjacency(n: int, edges: tuple) -> np.ndarray:
    """nbr[S]: the relations adjacent to some member of S (read-only)."""
    adj = np.zeros(n, dtype=np.int64)
    for u, v in edges:
        adj[u] |= 1 << v
        adj[v] |= 1 << u
    nbr = np.zeros(1 << n, dtype=np.int64)
    for j in range(n):
        nbr[1 << j:2 << j] = nbr[:1 << j] | adj[j]
    return nbr


@functools.lru_cache(maxsize=512)
def connected(n: int, edges: tuple) -> np.ndarray:
    """conn[S]: S is non-empty and induces a connected subgraph
    (read-only)."""
    nbr = adjacency(n, edges)
    S = np.arange(1 << n, dtype=np.int64)
    reach = S & -S
    for _ in range(n):
        reach = reach | (nbr[reach] & S)
    out = reach == S
    out[0] = False
    return out


def layered(n: int, card: np.ndarray, dtype, pair, close,
            keep=None) -> np.ndarray:
    """The DP table of ``DP[S] = close(min over the splits A+B=S that
    ``keep(A, B)`` holds of pair(DP[A], DP[B]), c(S))``, singletons 0, a
    set without a kept split +inf; vectorized one popcount layer at a
    time, in ``dtype``."""
    s_all, a_all, starts = split_pairs(n)
    c = np.asarray(card, dtype)
    dp = np.full(1 << n, np.inf, dtype)
    dp[1 << np.arange(n)] = 0
    for k in range(2, n + 1):
        lo, hi = starts[k], starts[k + 1]
        s, a = s_all[lo:hi], a_all[lo:hi]
        b = s ^ a
        if keep is not None:
            ok = keep(a, b)
            s, a, b = s[ok], a[ok], b[ok]
        if len(s) == 0:
            continue
        heads = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
        best = np.minimum.reduceat(pair(dp[a], dp[b]), heads)
        sets = s[heads]
        dp[sets] = close(best, c[sets])
    return dp


def extract(n: int, realises) -> tuple:
    """A tree over all n relations whose every join splits its set S
    into A and B with ``realises(S, A, B)``, as nested (left, right)
    tuples of relation masks (a leaf is its mask).  Splits are tried in
    submask order, the first that realises wins."""

    def build(s: int):
        if s & (s - 1) == 0:
            return s
        low = s & -s
        rest = s ^ low
        sub = rest
        while True:
            if sub != rest:
                a, bb = low | sub, rest ^ sub
                if realises(s, a, bb):
                    return (build(a), build(bb))
            if sub == 0:
                break
            sub = (sub - 1) & rest
        raise ValueError(f"no split of {s:#x} realises its DP value")

    return build((1 << n) - 1)


def intermediates(tree) -> list:
    """The relation set of every join of a (left, right) tuple tree, in
    post-order."""
    sets = []

    def walk(t):
        if isinstance(t, tuple):
            m = walk(t[0]) | walk(t[1])
            sets.append(m)
            return m
        return int(t)

    walk(tree)
    return sets


def tree_problems(tree, n: int, join=None) -> list:
    """Why ``tree`` is not a join tree over all n relations (an empty
    list if it is).  ``join(a, b)``, where given, names what is wrong
    with joining inputs a and b, or returns None."""
    bad = []

    def walk(t) -> int:
        if isinstance(t, tuple):
            a, b = walk(t[0]), walk(t[1])
            if a & b:
                bad.append(f"inputs {a:#x} and {b:#x} overlap")
            problem = join(a, b) if join is not None else None
            if problem:
                bad.append(problem)
            return a | b
        t = int(t)
        if t <= 0 or t & (t - 1):
            bad.append(f"leaf {t:#x} is not one relation")
        return t

    if walk(tree) != (1 << n) - 1:
        bad.append("the tree does not cover every relation")
    return bad
