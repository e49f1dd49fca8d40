"""Whole benchmark runs rehearsed on the CPU at tiny sizes.

The cell's own configuration and traffic, with fewer relations and a
window of about a second, driven through the harness's window and
comparison exactly as ``bench/run.py`` drives them, with the Pallas
kernels in interpret mode where the clique lane runs them.  Beside the
cell's closed loop, the same configuration is rehearsed under the
generator's other loops (an open loop at a rate, a template pool with
relabelled repeats), under C_out on stars and under C_cap on cliques,
and the four-chip cell's own traffic file runs on the one device a test
has, so that the harness's window and each cost's reference are tried
on every path a traffic file can ask for.  A clean run must pass; a run
answered by the failure ladder's host rung must count those answers as
failed, and is not correct; and runs whose timed path is broken
underneath (answers dropped from a batch, answers altered where they are
produced, C_cap's gate left out) or whose answers come from the control
must come out not correct.  The command itself still refuses to measure
without a TPU."""
import dataclasses
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.traffic import gen

PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2 ** 33 + 77                # larger than 32 bits, as the driver's are
CELL = "clique.n15"

# the cell at a size a test can hold: the clique at n = 11, the smallest n
# at which the Pallas kernels run rather than their fallback
SMALL = {"n_values": (11,), "check_sample": 6}
VARIANTS = {
    "closed": {},
    "open": {"loop": "open", "rate": 30.0},
    "templates": {"loop": "open", "rate": 60.0, "pool_size": 6,
                  "relabel_frac": 0.5, "fresh_frac": 0.2},
    # C_out on stars: the fused connected-C_out lane and its reference
    "out": {"cost": "out", "n_values": (6, 7), "topologies": ("star",),
            "regimes": ("warehouse", "selective"), "check_sample": 8},
    # the four-chip cell's own traffic file (stars under C_out, the
    # paper regime) at n = 6-7, on the one device a test has
    "acyclic": {"n_values": (6, 7), "check_sample": 8},
    # C_cap on the cell's cliques: the fused two-pass cap program
    "cap": {"cost": "cap", "n_values": (7, 8, 9), "check_sample": 8},
}
# the cell a variant starts from, where it is not CELL
BASE = {"acyclic": "acyclic_out.mesh4"}


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")


def small_cell(variant: str = "closed", **changes) -> harness.Cell:
    c = harness.load_cell(BASE.get(variant, CELL))
    config = dict(c.config)
    policy = {k: v for k, v in config.get("batch_policy", {}).items()
              if k != "solve_shards"}  # a mesh needs more devices
    config["batch_policy"] = {**policy, "backend": "pallas"}
    mix = {**SMALL, **VARIANTS[variant], **changes}
    return dataclasses.replace(c, config=config,
                               mix=dataclasses.replace(c.mix, **mix))


def window(cell: harness.Cell, seconds: float = 1.0, warm: bool = True,
           broken=lambda: None, runtime: "dict | None" = None):
    """Set up a server for the cell and run one window on it; ``broken``
    breaks the timed path once set-up is done; ``runtime`` overrides
    fields of the async runtime's ``RuntimeConfig``."""
    srv = harness.build_server(cell.config)
    for k, v in (runtime or {}).items():
        setattr(srv.async_runtime().config, k, v)
    if warm:
        harness.set_up(srv, cell.mix, SEED)
    broken()
    try:
        return harness.run_window(srv, cell.mix, gen.Stream(cell.mix, SEED),
                                  seconds)
    finally:
        srv.async_runtime().close()


@pytest.mark.parametrize("variant,trace", [("closed", False),
                                           ("closed", True),
                                           ("open", False),
                                           ("templates", True),
                                           ("acyclic", False),
                                           ("acyclic", True),
                                           ("cap", False)])
def test_clean_run_passes(variant, trace, tmp_path):
    cell = small_cell(variant)
    lines = []
    res = harness.measure(cell, SEED, 1.0, trace, time.perf_counter(),
                          PEAKS, log=lines.append,
                          trace_dir=str(tmp_path / "trace"))
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = cell.per_layer if trace else cell.end_to_end
    got = set(res["metrics"])
    assert got <= {m["name"] for m in want}
    if not trace:
        assert got == {m["name"] for m in want}
    else:
        assert res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"platform", "kind", "count"} <= set(res["device"])
    # nothing compiled inside the window
    (line,) = [s for s in lines if s.startswith("compiles:")]
    assert "window {'compile_requests': 0," in line
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def _drop_half(solve):
    """Answers for the second half of every batch of two or more are
    left out: those requests are never answered."""
    def broken(self, items, **kw):
        res = solve(self, items, **kw)
        return res[:(len(res) + 1) // 2] if len(res) > 1 else res
    return broken


def _left_deep(solve):
    """Each answer is replaced where it is produced by the left-deep
    tree over relations 0, 1, ... n-1 with that tree's own cost, so the
    program's own plan-cost recheck passes it."""
    from repro.core.jointree import JoinTree

    def broken(self, items, **kw):
        res = solve(self, items, **kw)
        for r, item in zip(res, items):
            q, card, cost = item[0], item[1], item[2]
            tree = JoinTree(1)
            for i in range(1, q.n):
                tree = JoinTree(tree.mask | 1 << i, tree, JoinTree(1 << i))
            r.tree = tree
            r.cost = tree.cost_max(card) if cost == "max" \
                else tree.cost_out(card)
        return res
    return broken


@pytest.mark.parametrize("fault", [_drop_half, _left_deep])
@pytest.mark.parametrize("variant", ["closed", "out", "acyclic", "cap"])
def test_broken_timed_path_is_not_correct(variant, fault, monkeypatch):
    from repro.service.batch import BatchedSolver
    # four clients keep batches of several requests forming
    cell = small_cell(variant, loop="closed", clients=4)
    monkeypatch.setattr(harness, "GRACE_S", 1.0)
    win = window(cell, broken=lambda: monkeypatch.setattr(
        BatchedSolver, "solve", fault(BatchedSolver.solve)))
    checks = harness.compare(win.recs, cell.mix.cost, cell.mix.check_sample,
                             SEED)
    assert not harness.correct(checks), checks


def _gate_left_out(solve):
    """Each answer is replaced where it is produced by the unpruned C_out
    plan, with that plan's own cost: the cap's gate left out of the
    second pass."""
    from repro.core import baselines, jointree

    def broken(self, items, **kw):
        res = solve(self, items, **kw)
        for r, item in zip(res, items):
            q, card = item[0], item[1]
            dp = baselines.dpsub(card, q.n, mode="out")
            r.tree = jointree.extract_tree_out(dp, card, q.n)
            r.cost = r.tree.cost_out(card)
        return res
    return broken


def test_cap_gate_left_out_is_not_correct(monkeypatch):
    from repro.service.batch import BatchedSolver
    cell = small_cell("cap", loop="closed", clients=4)
    # the window is long enough to reach queries whose unpruned optimum
    # breaks the cap: about one in fifty of the cell's cliques at n = 7-9
    win = window(cell, seconds=3.0, broken=lambda: monkeypatch.setattr(
        BatchedSolver, "solve", _gate_left_out(BatchedSolver.solve)))
    cap, out = harness.load_cost("cap"), harness.load_cost("out")
    # on a clique every set is connected, so C_out is unpruned there
    assert any(out.solve(r.query)[0] < cap.solve(r.query)[0]
               for r in win.recs)
    checks = harness.compare(win.recs, cell.mix.cost, cell.mix.check_sample,
                             SEED)
    assert not harness.correct(checks), checks
    assert checks["bad_trees"]["value"] > 0


@pytest.mark.parametrize("variant", ["closed", "out", "acyclic", "cap"])
def test_control_is_not_correct(variant):
    """The control, the reference computed in float32 (the precision
    below the configuration's float64), answering in the program's
    place over the same requests."""
    cell = small_cell(variant)
    win = window(cell)
    assert harness.correct(harness.compare(
        win.recs, cell.mix.cost, cell.mix.check_sample, SEED))
    sample = harness.sample(win.recs, cell.mix.check_sample, SEED)
    checks = harness.compare(sample, cell.mix.cost, len(sample), SEED,
                             answer=harness.control_answer(cell.mix.cost))
    assert not harness.correct(checks), checks
    assert checks["opt_gap"]["value"] > checks["opt_gap"]["limit"]


def test_no_tpu_no_result():
    root = harness.ROOT
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "no TPU" in r.stderr


def test_host_failover_counts_as_failed():
    """Every fused compile fails, so the failure ladder answers each
    request from the host engine: exact, correct, and failed."""
    from repro.core import engine
    from repro.service import FaultInjector, FaultPlan, FaultSpec
    cell = small_cell()
    engine.clear_executable_cache()
    plan = FaultPlan(seed=0, specs=(FaultSpec("compile", "raise", 1.0),))
    engine.set_compile_fault_hook(FaultInjector(plan).compile_fault)
    try:
        # the watchdog off: on a loaded host the host rung's first solve
        # (which compiles) can outlast its 2 s floor, and a host solve
        # declared hung would send the request on to the GOO rung
        win = window(cell, warm=False, runtime={"watchdog_factor": 0.0})
    finally:
        engine.set_compile_fault_hook(None)
        engine.clear_executable_cache()
    # none of the answers is the fused engine's, and the host rung's
    # answers are exact
    host = [r for r in win.recs if r.resp is not None
            and r.resp.status == "exact"
            and r.resp.meta.get("engine") == "host"]
    assert host, [(r.resp and (r.resp.status, r.resp.meta.get("engine")),
                   r.error) for r in win.recs]
    assert win.n_failed() == len(win.recs)
    assert win.plans_per_s() == 0.0
    checks = harness.compare(host, cell.mix.cost, cell.mix.check_sample,
                             SEED)
    assert harness.correct(checks)
    # a window answered by the ladder alone is not a correct run
    checks = harness.window_checks(win, cell.mix, SEED)
    assert checks["failed_share"]["value"] == 1.0
    assert not harness.correct(checks)
