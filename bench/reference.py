"""The plain reference the benchmark holds every answer to.

Written from the cost models' definitions and sharing nothing with the
program: the optimal C_max over the full subset lattice (bushy trees,
cross products allowed, as DPsub computes it) and the optimal C_out over
connected subgraphs joined along an edge (no cross products, as DPccp
computes it), both as O(3^n) dynamic programs over submask splits,
vectorized one popcount layer at a time.

    C_max:  DP[S] = max(c(S), min_{A+B=S} max(DP[A], DP[B]))
    C_out:  DP[S] = min_{A+B=S, A~B} (DP[A] + DP[B]) + c(S)
    DP[{i}] = 0

``dtype`` is the precision the DP runs in: float64 is the configuration's
own; float32 is the control, the step below it, which the comparison
must refuse.
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


def solve(n: int, edges, card: np.ndarray, cost: str,
          dtype=np.float64) -> tuple:
    """The optimal value and the DP table of one query."""
    s_all, a_all, starts = split_pairs(n)
    c = np.asarray(card, dtype)
    dp = np.full(1 << n, np.inf, dtype)
    dp[1 << np.arange(n)] = 0
    if cost == "out":
        conn = connected(n, edges)
        nbr = adjacency(n, edges)
    for k in range(2, n + 1):
        lo, hi = starts[k], starts[k + 1]
        s, a = s_all[lo:hi], a_all[lo:hi]
        b = s ^ a
        if cost == "max":
            vals = np.maximum(dp[a], dp[b])
        elif cost == "out":
            ok = conn[a] & conn[b] & ((nbr[a] & b) != 0)
            s, a, b = s[ok], a[ok], b[ok]
            vals = dp[a] + dp[b]
        else:
            raise ValueError(f"unknown cost {cost!r}")
        if len(s) == 0:
            continue
        heads = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
        best = np.minimum.reduceat(vals, heads)
        sets = s[heads]
        dp[sets] = (np.maximum(best, c[sets]) if cost == "max"
                    else best + c[sets])
    return dp[-1], dp


def extract(n: int, edges, card: np.ndarray, cost: str, dp: np.ndarray
            ) -> tuple:
    """An optimal tree from a DP table, as nested (left, right) tuples of
    relation masks (a leaf is its mask)."""
    c = np.asarray(card, dp.dtype)
    conn = connected(n, edges) if cost == "out" else None
    nbr = adjacency(n, edges) if cost == "out" else None

    def build(s: int):
        if s & (s - 1) == 0:
            return s
        low = s & -s
        rest = s ^ low
        sub = rest
        while True:
            if sub != rest:
                a, bb = low | sub, rest ^ sub
                if cost == "max":
                    v = max(dp[a], dp[bb])
                    ok = max(v, c[s]) == dp[s]
                else:
                    ok = (conn[a] and conn[bb] and (nbr[a] & bb) != 0
                          and dp[a] + dp[bb] + c[s] == dp[s])
                if ok:
                    return (build(a), build(bb))
            if sub == 0:
                break
            sub = (sub - 1) & rest
        raise ValueError(f"no split of {s:#x} realises its DP value")

    return build((1 << n) - 1)


def tree_cost(tree, card: np.ndarray, cost: str) -> float:
    """C_max or C_out of a (left, right) tuple tree."""
    vals = []

    def walk(t):
        if isinstance(t, tuple):
            m = walk(t[0]) | walk(t[1])
            vals.append(float(card[m]))
            return m
        return int(t)

    walk(tree)
    if cost == "max":
        return max(vals)
    total = 0.0
    for v in vals:
        total += v
    return total


def tree_problems(tree, n: int, edges, cost: str) -> list:
    """Why ``tree`` is not a join tree over all n relations (an empty
    list if it is).  C_out trees join connected inputs along an edge."""
    bad = []
    conn = connected(n, edges) if cost == "out" else None
    nbr = adjacency(n, edges) if cost == "out" else None

    def walk(t) -> int:
        if isinstance(t, tuple):
            a, b = walk(t[0]), walk(t[1])
            if a & b:
                bad.append(f"inputs {a:#x} and {b:#x} overlap")
            if cost == "out" and not (conn[a] and conn[b]
                                      and nbr[a] & b):
                bad.append(f"join of {a:#x} and {b:#x} is a cross product")
            return a | b
        t = int(t)
        if t <= 0 or t & (t - 1):
            bad.append(f"leaf {t:#x} is not one relation")
        return t

    if walk(tree) != (1 << n) - 1:
        bad.append("the tree does not cover every relation")
    return bad
