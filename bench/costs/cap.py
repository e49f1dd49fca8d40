"""C_cap: the least C_out over bushy join trees, cross products allowed,
whose every intermediate result is no larger than the least C_max
(arXiv 2409.08013, Sec. 8):

    pass 1:  gamma* = the C_max optimum over the full lattice,
             DP1[S] = max(c(S), min_{A+B=S} max(DP1[A], DP1[B]))
    pass 2:  DP[S] = min_{A+B=S} (DP[A] + DP[B]) + c(S)   if c(S) <= gamma*
             DP[S] = +inf                                 if c(S) > gamma*
             DP[{i}] = 0

An answer reports its cap as ``meta["gamma"]``.  Every answer's tree
keeps each intermediate at or under the cap it reports (an answer that
reports none is not a C_cap plan); a sampled answer's cap must be
gamma*, exactly.  The connected (no-cross-products) variant of the
second pass is not stated here.
"""
import numpy as np

from bench import reference


def solve(q, dtype=np.float64) -> tuple:
    """The capped optimum, its pass-2 DP table in ``dtype``, and the cap
    an answer must report: ``{"gamma": gamma*}``."""
    gamma = reference.layered(q.n, q.card, dtype, np.maximum,
                              np.maximum)[-1]

    def close(best, c):
        v = best + c
        v[c > gamma] = np.inf
        return v
    dp = reference.layered(q.n, q.card, dtype, np.add, close)
    return dp[-1], dp, {"gamma": float(gamma)}


def extract(q, dp: np.ndarray) -> tuple:
    c = np.asarray(q.card, dp.dtype)
    return reference.extract(
        q.n, lambda s, a, b: dp[a] + dp[b] + c[s] == dp[s])


def tree_problems(tree, q, meta: dict) -> list:
    gamma = meta.get("gamma")
    if gamma is None:
        return ["the answer reports no cap"]

    def join(a, b):
        c = float(q.card[a | b])
        if not c <= gamma:
            return (f"join of {a:#x} and {b:#x} is {c!r}, above the cap "
                    f"{gamma!r}")
        return None
    return reference.tree_problems(tree, q.n, join)


def tree_cost(tree, q) -> float:
    total = 0.0                  # C_out, in post-order, one at a time
    for m in reference.intermediates(tree):
        total += float(q.card[m])
    return total
