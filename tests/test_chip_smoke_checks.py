"""``chip_smoke.py``'s checks, run on the CPU at small n.

The smoke proves the chip served the plans only if its checks reject a
run that was answered some other way.  Here the same phases run with the
Pallas kernels in interpret mode (``backend="pallas"``; the suite asks
for interpret mode in ``conftest.py``) and platform ``"cpu"``: the clean
run must pass, and a run whose compiles fail — so the failure ladder
answers from the host engine, bit-correct and ``"exact"`` — must not.
"""
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.core import engine
from repro.service import (BatchPolicy, FaultInjector, FaultPlan,
                           FaultSpec, PlanServer)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


SMALL = dict(clique_n=8, pallas_ns=(7, 8), lane_n=7, mixed_range=(6, 8))


def _server():
    return PlanServer(max_batch=2, batch_policy=BatchPolicy(
        max_batch=2, backend="pallas"))


def test_clean_cpu_run_passes(smoke):
    sz = smoke.Sizes(**SMALL)
    failures, summary = smoke.run(_server(), smoke.one_chip_phases(sz),
                                  "cpu", pallas_ns=sz.pallas_ns,
                                  log=lambda *a: None)
    assert failures == []
    assert summary["exact_vs_oracle"] == summary["served"] > 0
    assert summary["dispatches"] > 0


def test_host_failover_is_rejected(smoke):
    sz = smoke.Sizes(**SMALL)
    phases = smoke.one_chip_phases(sz)[:2]     # clique + a Pallas group
    srv = _server()
    engine.clear_executable_cache()            # every fused solve compiles
    plan = FaultPlan(seed=0, specs=(FaultSpec("compile", "raise", 1.0),))
    engine.set_compile_fault_hook(FaultInjector(plan).compile_fault)
    try:
        cases, runtimes = smoke.serve(srv, phases)
    finally:
        engine.set_compile_fault_hook(None)
    for c in cases:
        c.oracle = smoke.oracle(c.request)
    # the ladder recovered every plan exactly, from the host engine ...
    assert all(c.response.status == "exact" for c in cases)
    assert all(c.response.cost == c.oracle for c in cases)
    assert sum(rt.fstats.failover_host for rt in runtimes) > 0
    # ... and the checks refuse to count that as the device serving it
    failures = smoke.check(cases, runtimes, [], {}, "cpu",
                           pallas_ns=sz.pallas_ns)
    assert any("failure ladder used" in f for f in failures)
    assert any("solved by engine 'host'" in f for f in failures)
    assert any("no dispatch ran" in f for f in failures)


def test_no_tpu_exits_before_serving():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "JAX sees no TPU" in r.stderr
