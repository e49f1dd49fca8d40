"""The cost functions' references, ``bench/costs/<cost>.py``, on the CPU:
the harness finds a cost by its file alone and refuses a cost without
one; C_max's and C_out's checks read what they read before the
references were split by cost (answers recorded from rehearsal windows,
with the numbers the single reference module gave them); and C_cap's
reference agrees bit for bit with the program's host and fused C_cap."""
import dataclasses
import json
import os
import types

import numpy as np
import pytest

from bench import harness
from bench.traffic import gen

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
SEED = 2 ** 33 + 77


# ------------------------------------------------------- C_max and C_out
@pytest.fixture(scope="module")
def pinned():
    """Per rehearsal variant (``closed``: cliques under C_max; ``out``
    and ``acyclic``: stars under C_out): its traffic, every answer of
    its window, the same answers with planted faults (one lost, costs
    off by 1e-9, trees replaced), and the numbers each set was given
    before the split."""
    with open(os.path.join(FIXTURES, "pinned_compare.json")) as f:
        return json.load(f)


def _tree(t):
    return (_tree(t[0]), _tree(t[1])) if isinstance(t, list) else t


def _node(t):
    """A nested-tuple tree as the program's tree type reads to
    ``harness.answer_tree``."""
    if isinstance(t, tuple):
        left, right = _node(t[0]), _node(t[1])
        return types.SimpleNamespace(mask=left.mask | right.mask,
                                     left=left, right=right)
    return types.SimpleNamespace(mask=t, left=None, right=None)


def _recs(v: dict, answers: list) -> list:
    """The window's requests again, the queries from the stream."""
    stream = gen.Stream(gen.Mix.from_dict(v["mix"]), v["seed"])
    recs = []
    for a in answers:
        rec = harness.Rec(stream.next(), 0.0, done=1.0)
        if a is not None:
            value, tree, status, engine = a
            rec.resp = types.SimpleNamespace(
                cost=float.fromhex(value), tree=_node(_tree(tree)),
                status=status, meta={"engine": engine})
        recs.append(rec)
    return recs


def _checks(v: dict, kind: str) -> dict:
    mix = gen.Mix.from_dict(v["mix"])
    seed = v["seed"]
    recs = _recs(v, v["answers"])
    if kind == "window":
        return harness.window_checks(harness.Window(0.0, recs), mix, seed)
    if kind == "full":
        return harness.compare(recs, mix.cost, len(recs), seed)
    if kind == "perturbed":
        recs = _recs(v, v["perturbed"])
        return harness.compare(recs, mix.cost, 6, seed, answer=lambda r: (
            r.resp.cost, harness.answer_tree(r.resp.tree), r.resp.meta))
    sample = harness.sample(recs, mix.check_sample, seed)
    return harness.compare(sample, mix.cost, len(sample), seed,
                           answer=harness.control_answer(mix.cost))


@pytest.mark.parametrize("kind", ["window", "full", "perturbed",
                                  "control"])
@pytest.mark.parametrize("variant", ["closed", "out", "acyclic"])
def test_max_and_out_checks_are_unchanged(pinned, variant, kind):
    v = pinned[variant]
    got = json.loads(json.dumps(_checks(v, kind)))
    assert got == v["checks"][kind]


# ------------------------------------------------------------- the lookup
JOINS = '''"""A cost planted for a test: the number of joins."""
import numpy as np

from bench import reference


def solve(q, dtype=np.float64):
    return dtype(q.n - 1), None, {"joins": q.n - 1}


def extract(q, table):
    t = 1
    for i in range(1, q.n):
        t = (t, 1 << i)
    return t


def tree_problems(tree, q, meta):
    return reference.tree_problems(tree, q.n)


def tree_cost(tree, q):
    return float(len(reference.intermediates(tree)))
'''


def test_a_cost_is_found_by_its_file(tmp_path, monkeypatch):
    (tmp_path / "joins.py").write_text(JOINS)
    monkeypatch.setattr(harness, "COSTS", str(tmp_path))
    rng = np.random.default_rng(SEED)
    recs = [harness.Rec(gen.make_query(rng, n, "star", ("paper",)), 0.0)
            for n in (4, 5, 6)]
    for r in recs:
        r.resp = object()
    answer = harness.control_answer("joins")
    assert harness.correct(harness.compare(recs, "joins", 3, SEED,
                                           answer=answer))
    off = harness.compare(recs, "joins", 3, SEED, answer=lambda r: (
        r.query.n, answer(r)[1], {"joins": r.query.n - 1}))
    assert off["tree_gap"]["value"] > 0 and off["opt_gap"]["value"] > 0
    # a number the reference reports beside the value must match exactly
    meta = harness.compare(recs, "joins", 3, SEED, answer=lambda r: (
        r.query.n - 1, answer(r)[1], {"joins": r.query.n}))
    assert meta["tree_gap"]["value"] == 0
    assert meta["opt_gap"]["value"] > meta["opt_gap"]["limit"]
    # only the directory it was pointed at holds references now
    with pytest.raises(SystemExit, match="max.py"):
        harness.load_cost("max")


def test_a_cost_without_its_file_is_refused(tmp_path):
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "c", "file": "c.json"}],
        "workloads": [{"name": "w", "config": "c", "traffic": "t",
                       "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    (tmp_path / "c.json").write_text(json.dumps({"name": "c",
                                                 "cost": "nosuch"}))
    (tmp_path / "bench" / "traffic" / "t.json").write_text(json.dumps({
        "loop": "closed", "clients": 1, "cost": "nosuch",
        "n_values": [6], "topologies": ["clique"], "regimes": ["paper"]}))
    with pytest.raises(SystemExit, match="nosuch.py"):
        harness.load_cell("w", root=str(tmp_path))


# ------------------------------------------------------------------ C_cap
def _bound_query():
    """The first query of the cap rehearsal's stream (cliques at n =
    7-9) whose unpruned C_out optimum breaks the cap: on a clique every
    set is connected, so ``out.py`` is unpruned there."""
    cap, out = harness.load_cost("cap"), harness.load_cost("out")
    mix = dataclasses.replace(harness.load_cell("clique.n15").mix,
                              cost="cap", n_values=(7, 8, 9))
    stream = gen.Stream(mix, SEED)
    for _ in range(200):
        q = stream.next()
        if out.solve(q)[0] < cap.solve(q)[0]:
            return q
    raise AssertionError("no query of the stream meets the cap")


@pytest.mark.parametrize("topology,n", [("clique", n) for n in range(4, 10)]
                         + [("star", n) for n in range(4, 9)]
                         + [("bound", None)])
def test_cap_reference_is_the_programs_cap(topology, n):
    """The cap and the capped C_out of ``bench/costs/cap.py`` are the
    program's, bit for bit, from its host pipeline and its fused
    program; every tree of the three passes the reference's check.
    ``bound`` is a clique whose cap changes the optimum."""
    from repro.core import engine
    from repro.core.ccap import ccap
    cap = harness.load_cost("cap")
    if topology == "bound":
        q = _bound_query()
    else:
        rng = np.random.default_rng([SEED, n, topology == "star"])
        q = gen.make_query(rng, n, topology, ("paper",))
    opt, dp, want = cap.solve(q)
    host = ccap(harness.program_query(q), q.card, engine="host")
    fused = engine.fused_ccap(q.card[None, :], q.n)
    answers = [(float(opt), cap.extract(q, dp)),
               (host.cout, harness.answer_tree(host.tree)),
               (float(fused.couts[0]), harness.answer_tree(fused.trees[0]))]
    assert [host.gamma, float(fused.gammas[0])] == [want["gamma"]] * 2
    for value, tree in answers:
        assert value == float(opt)
        assert cap.tree_problems(tree, q, want) == []
        assert harness._gap(cap.tree_cost(tree, q), value) \
            <= harness.LIMITS["tree_gap"]


def _cap_answers(case: str):
    """The reference's own answer, with one thing wrong where ``case``
    says so."""
    cap = harness.load_cost("cap")

    def answer(r):
        opt, dp, meta = cap.solve(r.query)
        tree = cap.extract(r.query, dp)
        if case == "no cap":
            meta = {}
        elif case == "cap one step high":
            meta = {"gamma": float(np.nextafter(meta["gamma"], np.inf))}
        elif case == "cap one step low":
            meta = {"gamma": float(np.nextafter(meta["gamma"], 0))}
        return float(opt), tree, meta
    return answer


@pytest.mark.parametrize("case,failing", [
    ("sound", None),
    ("no cap", "bad_trees"),
    # no tree beats the C_max optimum, so some intermediate of a C_cap
    # tree sits at the cap, and one step under it breaks the cap
    ("cap one step low", "bad_trees"),
    ("cap one step high", "opt_gap")])
def test_cap_answer_checks(case, failing):
    rng = np.random.default_rng([SEED, 9])
    recs = [harness.Rec(gen.make_query(rng, n, "clique", ("paper",)), 0.0)
            for n in (5, 6, 7, 8)]
    for r in recs:
        r.resp = object()
    checks = harness.compare(recs, "cap", len(recs), SEED,
                             answer=_cap_answers(case))
    bad = sorted(k for k, c in checks.items() if c["value"] > c["limit"])
    assert bad == ([failing] if failing else []), checks
