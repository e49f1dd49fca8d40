"""Planner integration: einsum contraction ordering + data-pipeline join
planning via DPconv (the paper's technique as a framework feature)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.planner.einsum_path import (Contraction, cardinalities,
                                       query_graph, plan_contraction,
                                       greedy_plan, execute_plan)
from repro.planner.datajoin import Table, JoinSpec, build_graph, \
    plan_joins, execute
from repro.core.baselines import dpsub_max, dpsub_out


CHAIN = Contraction(("ab", "bc", "cd", "de"), "ae",
                    {"a": 4, "b": 32, "c": 3, "d": 32, "e": 4})


def test_einsum_cardinalities():
    card = cardinalities(CHAIN)
    # contracting {0,1} = ab,bc -> ac : 4*3 = 12
    assert card[0b0011] == 12
    # single operand: its own size
    assert card[0b0001] == 4 * 32


def test_einsum_plan_optimal_peak():
    res = plan_contraction(CHAIN, cost="max")
    card = cardinalities(CHAIN)
    ref = dpsub_max(card, CHAIN.n)[-1]
    assert res.cost == ref
    assert res.tree.cost_max(card) == res.cost


def test_einsum_plan_beats_or_ties_greedy():
    rng = np.random.default_rng(0)
    for trial in range(10):
        n = 5
        idx = "abcdefg"
        ops, sizes = [], {}
        for i in range(n):
            a, b = idx[i], idx[i + 1]
            ops.append(a + b)
            sizes[a] = int(rng.integers(2, 64))
            sizes[b] = int(rng.integers(2, 64))
        c = Contraction(tuple(ops), idx[0] + idx[n], sizes)
        res = plan_contraction(c, cost="max")
        _, gpeak, _ = greedy_plan(c)
        assert res.cost <= gpeak + 1e-9


def test_einsum_execution_correct():
    rng = np.random.default_rng(1)
    tensors = [jnp.asarray(rng.normal(size=(CHAIN.sizes[i1],
                                             CHAIN.sizes[i2])))
               for i1, i2 in CHAIN.operands]
    for cost in ("max", "cap"):
        res = plan_contraction(CHAIN, cost=cost)
        out = execute_plan(CHAIN, res.tree, tensors)
        ref = jnp.einsum("ab,bc,cd,de->ae", *tensors)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-8)


@given(st.integers(0, 10 ** 6))
@settings(max_examples=10, deadline=None)
def test_einsum_cap_dominates_property(seed):
    """C_cap plan: peak == optimal C_max; total >= optimal C_out."""
    rng = np.random.default_rng(seed)
    idx = "abcdef"
    ops = tuple(idx[i] + idx[i + 1] for i in range(4))
    sizes = {c: int(rng.integers(2, 40)) for c in idx[:5]}
    c = Contraction(ops, idx[0] + idx[4], sizes)
    card = cardinalities(c)
    res = plan_contraction(c, cost="cap")
    assert np.isclose(res.tree.cost_max(card),
                      dpsub_max(card, c.n)[-1])
    assert res.cost >= dpsub_out(card, c.n)[-1] - 1e-9


# ------------------------------------------------------------- data joins
def _pipeline():
    tables = [Table("examples", ("doc",), 1000),
              Table("docs", ("doc", "src"), 300),
              Table("sources", ("src",), 20),
              Table("quality", ("doc",), 280)]
    joins = [JoinSpec(0, 1, "doc", 1 / 300),
             JoinSpec(1, 2, "src", 1 / 20),
             JoinSpec(1, 3, "doc", 1 / 290)]
    return tables, joins


def test_datajoin_graph_and_plan():
    tables, joins = _pipeline()
    q, card = build_graph(tables, joins)
    assert q.n == 4 and len(q.edges) == 3
    plan, _ = plan_joins(tables, joins, cost="cap")
    assert plan.tree.validate()
    assert plan.meta["gamma"] == dpsub_max(card, 4)[-1]


def test_datajoin_execute_matches_plan_order_invariance():
    """Row multiset of the joined result is independent of join order."""
    rng = np.random.default_rng(0)
    tables, joins = _pipeline()
    ex = np.zeros(100, dtype=[("doc", "i8"), ("w", "f8")])
    ex["doc"] = rng.integers(0, 30, 100)
    dc = np.zeros(30, dtype=[("doc", "i8"), ("src", "i8")])
    dc["doc"] = np.arange(30)
    dc["src"] = rng.integers(0, 5, 30)
    sr = np.zeros(5, dtype=[("src", "i8"), ("lic", "i8")])
    sr["src"] = np.arange(5)
    qu = np.zeros(28, dtype=[("doc", "i8"), ("q", "f8")])
    qu["doc"] = np.arange(28)
    data = [ex, dc, sr, qu]
    outs = []
    for cost in ("max", "cap"):
        plan, _ = plan_joins(tables, joins, cost=cost)
        res = execute(data, joins, plan.tree)
        rows = sorted(tuple(r[k] for k in sorted(res.dtype.names))
                      for r in res)
        outs.append(rows)
    assert outs[0] == outs[1]
    # expected row count: examples with doc < 28 (those have quality rows)
    assert len(outs[0]) == int((ex["doc"] < 28).sum())


def test_einsum_replay_pool_matches_pinned_fixture():
    """The replay pool (canned trace + the model traces derived from
    each replay shape) is pinned contraction by contraction, in order:
    a change to how traces are derived shows up here as a diff."""
    import json
    import os

    from repro.service.workload import einsum_replay_pool

    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "einsum_replay_pool.json")
    with open(path) as f:
        pinned = json.load(f)
    got = [{"operands": list(c.operands), "output": c.output,
            "sizes": c.sizes} for c in einsum_replay_pool()]
    assert got == pinned


def test_hypergraph_plan_respects_connectivity():
    """Non-inner-join hyperedge: plans must not join either side of the
    hyperedge before the side is complete."""
    from repro.core.querygraph import QueryGraph, make_cardinalities
    from repro.core.jointree import extract_tree_out
    # relations 0-1 joined; 2-3 joined; a hyperedge ({0,1},{2,3})
    q = QueryGraph(4, ((0, 1), (2, 3)), hyperedges=((0b0011, 0b1100),))
    card = make_cardinalities(q, seed=0)
    conn = q.connected_mask()
    dp = dpsub_out(card, 4, connected=conn)
    assert np.isfinite(dp[-1])
    tree = extract_tree_out(dp, card, 4)
    # every internal node must be a connected set under hypergraph rules
    for m in tree.internal_masks():
        assert q.is_connected(m), bin(m)
    # sets mixing one side of the hyperedge with part of the other are
    # not connected and must be absent
    assert not q.is_connected(0b0101)
    assert np.isinf(dp[0b0101])
