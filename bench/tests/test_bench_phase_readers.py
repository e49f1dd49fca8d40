"""The readers of the program's own phases, on the CPU.

Each reads what the program provides where it provides it (a response's
``timing_s``, ``engine.compiled_hlo_texts``, ``plan.*`` spans in the
trace) and returns None where it does not: the benchmark runs these
readers against the parent's program too, which has none of them.  The
last tests rehearse whole traced runs: one with the program's phase
hooks taken out, as on the parent, and one as the program now is."""
import os
import shutil
import statistics
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import trace_reduce as T
from bench.tests import test_bench_rehearsal as rehearsal

OLD = {"dispatch_ms.clique", "device_idle.clique", "zeta_roofline"}
COUNTERS = {"canon_ms.clique": "canonicalize",
            "prepare_ms.clique": "prepare", "fetch_ms.clique": "fetch"}
DEVICE = ("search_ms.clique", "extract_ms.clique")
NEW = set(COUNTERS) | set(DEVICE) | {"idle_admit.clique"}
DEV = "/device:TPU:0"


def _ctx(recs=(), trace=None):
    return {"window": harness.Window(start=0.0, recs=list(recs)),
            "trace": trace, "setup_s": 1.0, "layers": {},
            "peaks": {"hbm_bytes_per_s": 819e9}}


def _answered(timing):
    r = harness.Rec(query=None, due=0.0, sent=0.0, done=1.0)
    r.resp = types.SimpleNamespace(status="exact", meta={}, timing_s=timing)
    return r


# ------------------------------------------------------- timing_s medians
@pytest.mark.parametrize("metric", sorted(COUNTERS))
def test_timing_median_readers(metric):
    key = COUNTERS[metric]
    vals = [0.004, 0.0041, 9.0]          # one stalled by the profiler
    recs = [_answered({key: v, "admit": 1.0}) for v in vals]
    recs.append(_answered({"admit": 1.0}))  # a cache hit: no dispatch
    read = harness.load_reader(metric)
    assert read(_ctx(recs)) == pytest.approx(
        statistics.median(vals) * 1e3)


@pytest.mark.parametrize("metric", sorted(COUNTERS))
def test_timing_readers_without_the_breakdown(metric):
    read = harness.load_reader(metric)
    bare = harness.Rec(query=None, due=0.0, sent=0.0, done=1.0)
    bare.resp = types.SimpleNamespace(status="exact", meta={})
    lost = harness.Rec(query=None, due=0.0)
    assert read(_ctx([bare, lost])) is None
    assert read(_ctx([_answered(None)])) is None
    assert read(_ctx()) is None


# ------------------------------------------------- device phase readers
@pytest.fixture(scope="module")
def max_program():
    """The n = 6 max program's module name and instruction names under
    each scope."""
    from repro.core import engine
    from repro.core.querygraph import chain, make_cardinalities
    card = np.asarray(make_cardinalities(chain(6), seed=3), np.float64)
    engine.fused_dpconv_max(card[None], 6)
    texts = engine.compiled_hlo_texts()
    module = "jit_max_n6_B1_C64_xla"
    scopes = harness.load_reader("search_ms.clique").__globals__[
        "instruction_scopes"](texts[module])
    entry = texts[module].split("ENTRY", 1)[1].splitlines()[1:]
    names = {}
    for line in entry:
        name = line.strip().removeprefix("ROOT ").split(" ", 1)[0]
        names.setdefault(scopes.get(name.lstrip("%")), []).append(
            line.strip())
    return module, names


def _op(text, start, dur, line="XLA Ops"):
    return T.Event(DEV, line, text, float(start), float(dur))


def _device_ctx(names):
    """Two launches inside the window [0, 1000) and one past its end:
    each runs a search op (30 ns), an extract op (50 ns) and an op with
    no scope (7 ns)."""
    ops, launches = [], []
    for t0 in (100, 400, 900):
        launches.append((float(t0), float(t0 + 200),
                         "jit_max_n6_B1_C64_xla"))
        ops += [_op(names["search"][0], t0 + 10, 30),
                _op(names["extract"][0], t0 + 50, 50),
                _op(names[None][0], t0 + 120, 7)]
    red = T.Reduced(window_s=1e-6, busy_s=0.0, devices=1, op_s={},
                    gaps=[], ops=ops, lo_ns=0.0, hi_ns=1000.0)
    return _ctx(trace=red), {DEV: launches}


@pytest.mark.parametrize("metric,ns", [("search_ms.clique", 30),
                                       ("extract_ms.clique", 50)])
def test_device_phase_readers(metric, ns, max_program, monkeypatch):
    _module, names = max_program
    read = harness.load_reader(metric)
    ctx, launches = _device_ctx(names)
    monkeypatch.setitem(read.__globals__, "module_launches",
                        lambda ops: launches)
    # two launches lie wholly inside the window; the third ends past it
    assert read(ctx) == pytest.approx(2 * ns * 1e-6 / 2)


@pytest.mark.parametrize("metric", DEVICE)
def test_device_phase_readers_without_their_data(metric, max_program,
                                                 monkeypatch):
    from repro.core import engine
    _module, names = max_program
    read = harness.load_reader(metric)
    ctx, launches = _device_ctx(names)
    # a trace with no "XLA Modules" line (every CPU trace)
    monkeypatch.setitem(read.__globals__, "module_launches",
                        lambda ops: {})
    assert read(ctx) is None
    # launches of modules the program never compiled
    monkeypatch.setitem(read.__globals__, "module_launches", lambda ops: {
        DEV: [(s, e, "jit_fn") for s, e, _m in launches[DEV]]})
    assert read(ctx) is None
    # no op carries a phase scope
    monkeypatch.setitem(read.__globals__, "module_launches",
                        lambda ops: launches)
    ctx["trace"].ops[:] = [o for o in ctx["trace"].ops
                           if o.name in names[None]]
    assert read(ctx) is None
    # a program without compiled_hlo_texts (the parent's)
    ctx, _ = _device_ctx(names)
    monkeypatch.delattr(engine, "compiled_hlo_texts")
    assert read(ctx) is None
    assert read(_ctx()) is None


def test_device_reader_finds_no_module_line_in_a_cpu_trace(tmp_path,
                                                          monkeypatch):
    """The recorded CPU trace, where the harness's trace would be."""
    read = harness.load_reader("search_ms.clique")
    launches = read.__globals__["module_launches"]
    ops = [T.Event("/host:CPU", "python", "x", 0.0, 1.0)]
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    assert launches(ops) == {}            # no trace at all
    where = tmp_path / ".bench_trace" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "fixtures", "cpu_trace.xplane.pb"), where)
    assert launches(ops) == {}


# ------------------------------------------------------------ idle_admit
def test_idle_under_admission_spans(monkeypatch):
    """Device busy [0, 10) and [20, 30) of a window [0, 40); admission
    open over [5, 25) and [35, 50): idle under it is [10, 20) and
    [35, 40), 15 of 40."""
    read = harness.load_reader("idle_admit.clique")
    ops = [_op("%a = s32[] add(x)", 0, 10), _op("%b = s32[] add(x)", 20, 10)]
    red = T.Reduced(window_s=40e-9, busy_s=20e-9, devices=1, op_s={},
                    gaps=[], ops=ops, lo_ns=0.0, hi_ns=40.0)
    monkeypatch.setitem(read.__globals__, "host_spans",
                        lambda lo: [(5.0, 25.0), (35.0, 50.0)])
    assert read(_ctx(trace=red)) == pytest.approx(15 / 40 * 100)
    monkeypatch.setitem(read.__globals__, "host_spans", lambda lo: [])
    assert read(_ctx(trace=red)) is None
    assert read(_ctx()) is None


def _record_trace(root, admit: bool):
    """A CPU trace under ``<root>/.bench_trace``: three admissions (a
    2 ms sleep each, with ``admit``) between device work."""
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: jnp.cumsum(x * 2))
    x = jnp.ones(4096)
    f(x).block_until_ready()
    profile = harness.Profile(os.path.join(root, ".bench_trace"))
    profile.start()
    for i in range(3):
        if admit:
            with TraceAnnotation("plan.admit", req_id=i):
                time.sleep(0.002)
        f(x).block_until_ready()
    profile.stop()
    path = T.newest_trace(profile.dir)
    return T.load_events(path), T.reduce(T.load_events(path), "cpu")


@pytest.mark.parametrize("admit", [True, False])
def test_idle_admit_reads_the_windows_trace(admit, tmp_path, monkeypatch):
    read = harness.load_reader("idle_admit.clique")
    events, red = _record_trace(str(tmp_path), admit)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    if not admit:                      # a trace of the parent's program
        assert read(_ctx(trace=red)) is None
        return
    spans = [(e.start_ns, e.end_ns) for e in events
             if e.name == "plan.admit"]
    assert len(spans) == 3
    # no device op runs while an admission sleeps
    idle = sum(e - s for s, e in spans) / (red.hi_ns - red.lo_ns) * 100
    assert read(_ctx(trace=red)) == pytest.approx(idle, rel=1e-9)
    # a trace other than the window's is not read
    other = T.Reduced(**{**red.__dict__, "lo_ns": red.lo_ns + 1.0})
    assert read(_ctx(trace=other)) is None
    monkeypatch.setattr(harness, "ROOT", str(tmp_path / "none"))
    assert read(_ctx(trace=red)) is None


# --------------------------------------------------- whole traced runs
@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")


class _NoPhase:
    """The parent's program: no phase annotations, no child spans."""

    seconds = 0.0

    def __init__(self, name, parent=None, **ids):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _traced_run(tmp_path):
    lines = []
    res = harness.measure(rehearsal.small_cell(), rehearsal.SEED, 1.0,
                          True, time.perf_counter(), rehearsal.PEAKS,
                          log=lines.append,
                          trace_dir=str(tmp_path / "trace"))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    return set(res["metrics"])


def test_traced_run_of_a_program_without_the_phases(interpret, tmp_path,
                                                    monkeypatch):
    from repro.core import engine
    from repro.service import runtime
    monkeypatch.setattr(runtime, "phase", _NoPhase)
    monkeypatch.setattr(engine, "phase", _NoPhase)
    monkeypatch.setattr(runtime, "_timing", lambda root: None)
    monkeypatch.delattr(engine, "compiled_hlo_texts")
    got = _traced_run(tmp_path)
    # a CPU trace holds no Pallas call, so zeta_roofline is not read here
    assert got <= OLD and {"dispatch_ms.clique", "device_idle.clique"} <= got


def test_traced_run_reports_the_phase_counters(interpret, tmp_path):
    got = _traced_run(tmp_path)
    assert {"dispatch_ms.clique", "device_idle.clique"} | set(COUNTERS) \
        <= got <= OLD | NEW
    # a CPU trace has no module line; the trace is not under .bench_trace
    assert not got & set(DEVICE) and "idle_admit.clique" not in got
