"""C_max: the largest intermediate result of a bushy join tree, cross
products allowed, as DPsub searches it (arXiv 2409.08013, Sec. 3):

    DP[S] = max(c(S), min_{A+B=S} max(DP[A], DP[B])),  DP[{i}] = 0

An answer reports nothing beside its value.
"""
import numpy as np

from bench import reference


def solve(q, dtype=np.float64) -> tuple:
    """The optimum, its DP table in ``dtype``, and the numbers an answer
    reports beside its value (none)."""
    dp = reference.layered(q.n, q.card, dtype, np.maximum, np.maximum)
    return dp[-1], dp, {}


def extract(q, dp: np.ndarray) -> tuple:
    c = np.asarray(q.card, dp.dtype)
    return reference.extract(
        q.n, lambda s, a, b: max(max(dp[a], dp[b]), c[s]) == dp[s])


def tree_problems(tree, q, meta: dict) -> list:
    return reference.tree_problems(tree, q.n)


def tree_cost(tree, q) -> float:
    return max(float(q.card[m]) for m in reference.intermediates(tree))
