"""C_out: the sum of the intermediate results of a bushy join tree
without cross products, as DPccp searches it: connected inputs joined
along an edge (Moerkotte & Neumann, VLDB 2006):

    DP[S] = min_{A+B=S, A~B} (DP[A] + DP[B]) + c(S),  DP[{i}] = 0

An answer reports nothing beside its value.
"""
import numpy as np

from bench import reference


def _graph(q) -> tuple:
    return (reference.connected(q.n, q.edges),
            reference.adjacency(q.n, q.edges))


def solve(q, dtype=np.float64) -> tuple:
    """The optimum, its DP table in ``dtype``, and the numbers an answer
    reports beside its value (none)."""
    conn, nbr = _graph(q)
    dp = reference.layered(
        q.n, q.card, dtype, np.add, np.add,
        keep=lambda a, b: conn[a] & conn[b] & ((nbr[a] & b) != 0))
    return dp[-1], dp, {}


def extract(q, dp: np.ndarray) -> tuple:
    c = np.asarray(q.card, dp.dtype)
    conn, nbr = _graph(q)
    return reference.extract(
        q.n, lambda s, a, b: (conn[a] and conn[b] and (nbr[a] & b) != 0
                              and dp[a] + dp[b] + c[s] == dp[s]))


def tree_problems(tree, q, meta: dict) -> list:
    conn, nbr = _graph(q)

    def join(a, b):
        if not (conn[a] and conn[b] and nbr[a] & b):
            return f"join of {a:#x} and {b:#x} is a cross product"
        return None
    return reference.tree_problems(tree, q.n, join)


def tree_cost(tree, q) -> float:
    total = 0.0                  # in post-order, one addition at a time
    for m in reference.intermediates(tree):
        total += float(q.card[m])
    return total
