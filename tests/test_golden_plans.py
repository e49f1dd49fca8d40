"""Golden-fixture regression: live serving-default solvers vs the frozen
host-reference plans in ``tests/fixtures/golden_plans.json``.

The fixture (regenerated only deliberately, by
``scripts/regen_golden.py``) freezes bit-exact optima and serialized
trees for the canned einsum replay trace, JOB-like chain/star
workloads, the chain/star/cycle/sparse/clique families at n = 10-13,
two grids and the replay pool's model-trace contractions, computed on
the host pipelines.  This test recomputes every
entry with the **fused engines the serving tier defaults to** and diffs:
a mismatch means either an unintended optimum/witness drift or a fused/
host divergence — both must fail loudly, not skew silently.
"""
import functools
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "tests", "fixtures", "golden_plans.json")


@functools.lru_cache(maxsize=1)
def _regen_module():
    spec = importlib.util.spec_from_file_location(
        "regen_golden", os.path.join(ROOT, "scripts", "regen_golden.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=1)
def _instances_by_name():
    # built once per session: the instance set is deterministic and the
    # parametrized cases below would otherwise rebuild every einsum
    # trace + cardinality table per entry
    return {name: (q, card, costs)
            for name, q, card, costs in _regen_module().golden_instances()}


def live_solve(q, card, cost):
    """The live defaults a served request actually runs: fused engines."""
    from repro.core.ccap import ccap
    from repro.core.dpconv import optimize

    if cost == "max":
        r = optimize(q, card, cost="max")          # engine="auto": fused
        return float(r.cost), r.tree, r.meta.get("engine")
    if cost == "out":
        r = optimize(q, card, cost="out", method="dpccp", engine="fused")
        return float(r.cost), r.tree, r.meta.get("engine")
    if cost == "cap":
        r = ccap(q, card)                          # engine="auto": fused
        return float(r.cout), r.tree, r.engine
    raise ValueError(cost)


def _cases():
    with open(FIXTURE) as f:
        fixture = json.load(f)
    return fixture["entries"]


def test_fixture_covers_instance_set():
    """Every (instance, cost) the generator defines has a frozen entry —
    a stale fixture after an instance-set change fails here, pointing at
    scripts/regen_golden.py."""
    want = {(name, cost)
            for name, (_q, _c, costs) in _instances_by_name().items()
            for cost in costs}
    have = {(e["name"], e["cost"]) for e in _cases()}
    assert want == have


@pytest.mark.parametrize("entry", _cases(),
                         ids=lambda e: f"{e['name']}/{e['cost']}")
def test_live_solver_matches_golden(entry):
    q, card, _costs = _instances_by_name()[entry["name"]]
    opt, tree, engine = live_solve(q, card, entry["cost"])
    assert engine == "fused"
    assert opt == float.fromhex(entry["optimum_hex"])
    assert repr(tree) == entry["tree"]
