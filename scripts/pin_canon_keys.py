#!/usr/bin/env python
"""Write the pinned canonical-key fixture, ``tests/fixtures/canon_keys.json``.

    PYTHONPATH=src python scripts/pin_canon_keys.py

For a fixed set of queries the fixture records what
``service.canon.canonicalize`` returns: the plan-cache ``key``, the
canonical ``perm``, and the SHA-256 of the canonical ``card`` table's
float64 bytes; and, for a few relation subsets, the layer cache's
``subset_signature`` key, member relations and fragment ``perm``.

The queries: paper-regime clique(15) and star(15) (arXiv 2409.08013
Sec. 7 ranges, clipped at 1e8), chain, cycle, grid and sparse graphs, a
hyperedge query, relabelled copies of several of them (whose keys must
equal their originals'), and a clique(8), a star(8) and a chain(9) whose
cardinality depends only on the subset's size, so relations tie and the
individualisation search branches (the clique's up to ``branch_cap``).

The fixture was written on the commit before canonicalisation built each
refinement leaf's permuted table once and ``permute_card`` took a
byte-wise index, and ``tests/test_service_cache.py`` holds the program to
it bit for bit.  Regenerate it only for an intended change of the
canonical form, and say why in the commit.
"""
from __future__ import annotations

import hashlib
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "canon_keys.json")

sys.path.insert(0, os.path.join(REPO, "src"))

PAPER = {"base_range": (1e2, 1e6), "selectivity_range": (1e-4, 1.0),
         "cap": 1e8}


def _relabelled(q, card, seed: int):
    from repro.core.querygraph import permute_card, relabel
    perm = np.random.default_rng(seed).permutation(q.n)
    return relabel(q, perm), permute_card(card, q.n, perm)


def cases() -> list:
    """``[(name, q, card)]``, deterministic."""
    from repro.core.querygraph import (QueryGraph, chain, clique, cycle, grid,
                                       make_cardinalities, random_sparse,
                                       star)
    out = []
    for s in range(3):
        q = clique(15)
        out.append((f"paper_clique15_s{s}", q,
                    make_cardinalities(q, seed=100 + s, **PAPER)))
    for s in range(3):
        q = star(15)
        out.append((f"paper_star15_s{s}", q,
                    make_cardinalities(q, seed=200 + s, **PAPER)))
    for name, q in (("chain12", chain(12)), ("cycle11", cycle(11)),
                    ("grid3x4", grid(3, 4)),
                    ("sparse13", random_sparse(13, 4, seed=3)),
                    ("sparse10", random_sparse(10, 2, seed=8))):
        out.append((name, q, make_cardinalities(q, seed=300 + len(out))))
    hyper = QueryGraph(8, ((0, 1), (1, 2), (2, 3), (4, 5), (5, 6), (6, 7)),
                       ((0b00001111, 0b11110000),))
    out.append(("hyper8", hyper, make_cardinalities(hyper, seed=400)))
    # symmetric graphs with random cardinalities, and relabelled copies
    for name, q in (("star6", star(6)), ("cycle6", cycle(6)),
                    ("clique6", clique(6))):
        out.append((name, q, make_cardinalities(q, seed=42)))
    base = {name: (q, card) for name, q, card in out}
    for name, seed in (("paper_clique15_s0", 1), ("paper_star15_s0", 2),
                       ("chain12", 3), ("sparse13", 4), ("hyper8", 5),
                       ("cycle6", 6), ("clique6", 7)):
        q, card = base[name]
        out.append((f"{name}_relabelled", *_relabelled(q, card, seed)))
    # every relation ties: card depends on |S| alone
    q = clique(8)
    sizes = np.array([bin(s).count("1") for s in range(1 << 8)])
    uniform = np.power(10.0, sizes)
    out.append(("uniform_clique8", q, uniform))
    out.append(("uniform_clique8_relabelled", *_relabelled(q, uniform, 9)))
    for name, q in (("uniform_star8", star(8)), ("uniform_chain9", chain(9))):
        sizes = np.array([bin(s).count("1") for s in range(1 << q.n)])
        out.append((name, q, np.power(10.0, sizes)))
    return out


def fragments() -> list:
    """``[(name, case name, mask)]``: subsets for ``subset_signature``."""
    return [("paper_clique15_s0_low7", "paper_clique15_s0", 0b1111111),
            ("paper_star15_s0_hub5", "paper_star15_s0", 0b100011001000001),
            ("sparse13_mid6", "sparse13", 0b0011111100000),
            ("hyper8_all", "hyper8", 0xFF),
            ("uniform_clique8_odd", "uniform_clique8", 0b01010101),
            ("uniform_star8_hub3", "uniform_star8", 0b00001011)]


def card_sha256(card) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(card, np.float64).tobytes()).hexdigest()


def compute() -> dict:
    from repro.service.canon import canonicalize, subset_signature
    table = cases()
    out = {"queries": [], "fragments": []}
    for name, q, card in table:
        f = canonicalize(q, card)
        out["queries"].append({"name": name, "n": q.n, "key": f.key,
                               "perm": list(f.perm),
                               "card_sha256": card_sha256(f.card)})
    by_name = {name: (q, card) for name, q, card in table}
    for name, case, mask in fragments():
        q, card = by_name[case]
        s = subset_signature(q, card, mask)
        out["fragments"].append({"name": name, "case": case, "mask": mask,
                                 "key": s.key, "rels": list(s.rels),
                                 "perm": list(s.perm)})
    return out


def main() -> None:
    data = compute()
    with open(FIXTURE, "w") as fh:
        json.dump(data, fh, indent=1)
        fh.write("\n")
    print(f"wrote {len(data['queries'])} queries and "
          f"{len(data['fragments'])} fragments to {FIXTURE}")


if __name__ == "__main__":
    main()
