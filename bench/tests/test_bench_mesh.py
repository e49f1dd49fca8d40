"""The four-chip cell's mesh path, rehearsed on four virtual CPU devices
in child processes (``mesh_rehearsal.py``): a clean window is correct
and every dispatch in it runs 4-wide over 4 devices; a window whose
layer merges leave the exchange between the chips out is not correct.
The children run on the CPU alone and never load the TPU's library."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness

SCRIPT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "mesh_rehearsal.py")


@pytest.fixture(scope="module")
def runs():
    """Both rehearsals, run side by side: fault -> result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                         " --xla_force_host_platform_device_count=4")}
    env.pop("REPRO_PALLAS_INTERPRET", None)
    procs = {f: subprocess.Popen([sys.executable, SCRIPT, "--fault", f],
                                 cwd=harness.ROOT, env=env, text=True,
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE)
             for f in ("none", "exchange")}
    out = {}
    try:
        for f, p in procs.items():
            stdout, stderr = p.communicate(timeout=600)
            assert p.returncode == 0, stderr[-4000:]
            out[f] = json.loads(stdout.strip().splitlines()[-1])
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.communicate()
    return out


def test_mesh_window_is_correct_and_sharded(runs):
    res = runs["none"]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["dispatches"] and all(d == [4, 4] for d in res["dispatches"])


def test_exchange_left_out_is_not_correct(runs):
    res = runs["exchange"]
    assert not res["correct"], res
    assert res["dispatches"] and all(d == [4, 4] for d in res["dispatches"])
