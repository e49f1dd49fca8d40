"""Quickstart: optimal join ordering with DPconv.

    PYTHONPATH=src python examples/quickstart.py

Builds a 12-relation clique query with random (submultiplicative)
cardinalities — the paper's worst case — and optimizes it under every
supported cost function, printing the optimal bushy join trees.
"""
import os
import time

import numpy as np

from repro.core import engine
from repro.core.querygraph import clique, random_sparse, \
    make_cardinalities
from repro.core.dpconv import optimize

engine.use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))

n = 12
q = clique(n)
card = make_cardinalities(q, seed=42)
print(f"query: clique of {n} relations, "
      f"cardinalities in [{card.min():.0f}, {card.max():.0f}]\n")

for cost, method in [("max", "dpconv"), ("out", "dpsub"),
                     ("cap", "dpconv"), ("smj", "dpsub")]:
    t0 = time.perf_counter()
    res = optimize(q, card, cost=cost, method=method,
                   extract_tree=(cost != "smj"))
    dt = time.perf_counter() - t0
    print(f"C_{cost:3s} [{method:6s}]  optimum = {res.cost:14,.0f}   "
          f"({dt:.2f}s)")
    if res.tree is not None:
        print(f"   plan: {res.tree}")
        print(f"   peak intermediate = {res.tree.cost_max(card):,.0f}, "
              f"total = {res.tree.cost_out(card):,.0f}\n")

# approximate C_out: (1+eps) guarantee, W-independent running time
for eps in (0.5, 0.1):
    t0 = time.perf_counter()
    res = optimize(q, card, cost="out", method="approx", eps=eps)
    exact = optimize(q, card, cost="out", method="dpsub",
                     extract_tree=False).cost
    print(f"C_out approx eps={eps}: {res.cost:,.0f} "
          f"(ratio {res.cost / exact:.4f}, {time.perf_counter()-t0:.2f}s)")

# sparse (JOB-like) graph: DPccp enumerates only connected pairs
qs = random_sparse(14, 4, seed=1)
cs = make_cardinalities(qs, seed=1)
res = optimize(qs, cs, cost="out", method="dpccp")
print(f"\nsparse 14-relation query via DPccp: optimum {res.cost:,.0f} "
      f"({res.meta['ccp']} ccp pairs vs 3^14={3**14:,} subset pairs)")
