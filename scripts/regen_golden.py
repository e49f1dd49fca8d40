#!/usr/bin/env python
"""Regenerate the golden-plan fixtures under ``tests/fixtures/``.

    PYTHONPATH=src python scripts/regen_golden.py

``golden_plans.json`` freezes optima (bit-exact, ``float.hex``) and
serialized join trees (the compact s-expr ``repr``) for a deterministic
instance set — the canned einsum contraction-log replay trace, JOB-like
chain/star workloads, the chain/star/cycle/sparse/clique families at
n = 10, 12 and 13 (13 is the single-device fused ceiling of the cap and
out lanes), two grids, and the model-trace contractions of the replay
pool — computed on the **host reference pipelines**
(host-loop DPconv[max], the DPccp enumerator, the host two-pass C_cap).
``tests/test_golden_plans.py`` diffs the **live serving-default
solvers** (the fused engines) against it, so the fixture is both a
cross-PR regression anchor (any drift in optima or witness rules shows
up as a diff) and a host-vs-fused cross-engine check that runs without
recomputing the references.

``einsum_replay_pool.json`` pins the replay pool
(``service.workload.einsum_replay_pool``) contraction by contraction,
so a change to how the model traces are derived shows up as a diff.

Regenerate ONLY when an intentional change moves the frozen values
(e.g. a new witness tie-break rule), and say why in the commit.  New
instances go at the end, so existing entries stay byte-identical.
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "golden_plans.json")
POOL_FIXTURE = os.path.join(REPO, "tests", "fixtures",
                            "einsum_replay_pool.json")

sys.path.insert(0, os.path.join(REPO, "src"))


def golden_instances():
    """The deterministic (name, q, card, costs) instance set shared by
    the regenerator and the regression test — the single source of
    truth for what the fixture covers."""
    from repro.core.querygraph import (chain, cycle, grid,
                                       make_cardinalities,
                                       paper_clique_instance, random_sparse,
                                       star)
    from repro.planner.einsum_path import (builtin_trace, cardinalities,
                                           query_graph)
    from repro.service.workload import einsum_replay_pool

    all_costs = ["max", "out", "cap"]
    out = []

    def einsum_entries(prefix, contractions):
        for i, c in enumerate(contractions):
            q = query_graph(c)
            costs = ["max", "cap"]
            # the DPccp lane is defined for connected simple-edge graphs
            if q.is_connected(q.full_mask) and not q.hyperedges:
                costs.append("out")
            out.append((f"{prefix}/{i}/n={q.n}", q, cardinalities(c), costs))

    builtin = builtin_trace()
    einsum_entries("einsum", [c for c in builtin if c.n >= 4])
    for name, maker, seed in (("job_chain8", chain, 0),
                              ("job_star8", star, 1)):
        q = maker(8)
        card = make_cardinalities(q, seed=seed)
        out.append((name, q, card, all_costs))
    for n in (10, 12, 13):
        for name, q in (("chain", chain(n)), ("star", star(n)),
                        ("cycle", cycle(n)),
                        ("sparse", random_sparse(n, n // 4, seed=n))):
            out.append((f"{name}{n}", q, make_cardinalities(q, seed=n),
                        all_costs))
        q, card = paper_clique_instance(n, seed=n)
        out.append((f"clique{n}", q, card, all_costs))
    for rows, cols in ((2, 5), (3, 4)):
        q = grid(rows, cols)
        out.append((f"grid{rows}x{cols}", q,
                    make_cardinalities(q, seed=rows * cols), all_costs))
    # the model-trace contractions the replay pool adds to the canned trace
    extra = []
    for c in einsum_replay_pool():
        if c.n >= 4 and c not in builtin and c not in extra:
            extra.append(c)
    einsum_entries("model_trace", extra)
    return out


def host_reference(q, card, cost):
    """The frozen-truth pipelines: host engines only."""
    from repro.core.ccap import ccap
    from repro.core.dpconv import optimize

    if cost == "max":
        r = optimize(q, card, cost="max", engine="host")
        return float(r.cost), r.tree
    if cost == "out":
        r = optimize(q, card, cost="out", method="dpccp", engine="host")
        return float(r.cost), r.tree
    if cost == "cap":
        r = ccap(q, card, engine="host")
        return float(r.cout), r.tree
    raise ValueError(cost)


def main() -> int:
    entries = []
    for name, q, card, costs in golden_instances():
        for cost in costs:
            opt, tree = host_reference(q, card, cost)
            entries.append({
                "name": name,
                "cost": cost,
                "n": q.n,
                "optimum": opt,                 # human-readable
                "optimum_hex": float(opt).hex(),  # the bit-exact anchor
                "tree": repr(tree),
            })
            print(f"  {name} cost={cost}: {opt:.6g}  {repr(tree)[:60]}")
    os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
    with open(FIXTURE, "w") as f:
        json.dump({"comment": "regenerate with scripts/regen_golden.py; "
                              "see its docstring before touching",
                   "entries": entries}, f, indent=1)
        f.write("\n")
    print(f"wrote {FIXTURE} ({len(entries)} entries)")

    from repro.service.workload import einsum_replay_pool
    pool = [{"operands": list(c.operands), "output": c.output,
             "sizes": c.sizes} for c in einsum_replay_pool()]
    with open(POOL_FIXTURE, "w") as f:   # one contraction per line
        f.write("[\n" + ",\n".join(json.dumps(r) for r in pool) + "\n]\n")
    print(f"wrote {POOL_FIXTURE} ({len(pool)} contractions)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
