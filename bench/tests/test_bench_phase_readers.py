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

from bench import harness, readers
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
    scopes = readers.instruction_scopes(texts[module])
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
    monkeypatch.setattr(readers, "module_launches", lambda ops: launches)
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
    monkeypatch.setattr(readers, "module_launches", lambda ops: {})
    assert read(ctx) is None
    # launches of modules the program never compiled
    monkeypatch.setattr(readers, "module_launches", lambda ops: {
        DEV: [(s, e, "jit_fn") for s, e, _m in launches[DEV]]})
    assert read(ctx) is None
    # no op carries a phase scope
    monkeypatch.setattr(readers, "module_launches", lambda ops: launches)
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
    launches = readers.module_launches
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
    monkeypatch.setattr(readers, "host_spans",
                        lambda lo, span: [(5.0, 25.0), (35.0, 50.0)]
                        if span == "plan.admit" else [])
    assert read(_ctx(trace=red)) == pytest.approx(15 / 40 * 100)
    monkeypatch.setattr(readers, "host_spans", lambda lo, span: [])
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


# ------------------------------------------- the four-chip cell's readers
MESH = ("dispatch_ms.mesh4", "prepare_ms.mesh4", "search_ms.mesh4",
        "merge_ms.mesh4", "extract_ms.mesh4", "device_idle.mesh4",
        "canon_ms.mesh4", "fetch_ms.mesh4", "idle_admit.mesh4",
        "idle_close_bucket.mesh4")
MESH_SPANS = {"idle_admit.mesh4": "plan.admit",
              "idle_close_bucket.mesh4": "plan.close_bucket"}
MESH_DEVICE = ("search_ms.mesh4", "merge_ms.mesh4", "extract_ms.mesh4")
MESH_MODULE = "jit_out_n15_B2_C0_xla_s4"
LAYOUT = "{0,1:T(8,128)S(1)}"
# one layer's merge as the v5e's compiler prints it (the sharded C_out
# program at n = 15, B = 2), an async pair, and instructions that name an
# all-reduce without being one
MESH_HLO = f"""HloModule {MESH_MODULE}

%region_4.5 (a: s32[], b: s32[]) -> s32[] {{
  %add.1 = s32[] add(s32[] %a, s32[] %b)
}}

ENTRY %main {{
  %and_convert_fusion.10 = s32[2,4096]{LAYOUT} fusion(%p), kind=kLoop, calls=%fc.1, metadata={{op_name="jit(<lambda>)/shard_map/search/and"}}
  %all-reduce.33 = (s32[2,4096]{LAYOUT}, s32[2,4096]{LAYOUT}) all-reduce(%and_convert_fusion.10, %shift-right-arithmetic_convert_fusion.10), channel_id=1, replica_groups={{{{0,1,2,3}}}}, use_global_device_ids=true, to_apply=%region_4.5, metadata={{op_name="jit(<lambda>)/shard_map/search/psum" stack_frame_id=196}}, backend_config={{"flag_configs":[],"barrier_config":{{"barrier_type":"CUSTOM"}}}}
  %all-reduce-start.2 = (s32[2,128]{{1,0}}, /*index=1*/s32[2,128]{{1,0}}) all-reduce-start(%x, %y), channel_id=2, replica_groups={{{{0,1,2,3}}}}, to_apply=%region_4.5, metadata={{op_name="jit(<lambda>)/shard_map/search/psum"}}
  %all-reduce-done.2 = (s32[2,128]{{1,0}}, s32[2,128]{{1,0}}) all-reduce-done(%all-reduce-start.2), metadata={{op_name="jit(<lambda>)/shard_map/search/psum"}}
  %get-tuple-element.5 = s32[2,4096]{LAYOUT} get-tuple-element(%all-reduce.33), index=0, metadata={{op_name="jit(<lambda>)/shard_map/search/psum"}}
  %select_fusion.3 = s64[2,32768]{{1,0}} fusion(%get-tuple-element.5), kind=kLoop, calls=%fc.2, metadata={{op_name="jit(<lambda>)/shard_map/search/select_n"}}
  %reverse.179 = s32[2,32768]{{1,0:T(2,128)}} reverse(%r), dimensions={{1}}, metadata={{op_name="jit(<lambda>)/shard_map/extract/rev"}}
  %copy.3 = s32[2]{{0}} copy(%c)
}}
"""
# (instruction, offset, duration) of one launch; the all-reduce's
# duration is 20 + 4 d on chip d (a chip waits for the slowest)
LAUNCH = [("and_convert_fusion.10", 0, 40), ("all-reduce.33", 40, None),
          ("all-reduce-start.2", 75, 3), ("all-reduce-done.2", 80, 9),
          ("get-tuple-element.5", 90, 2), ("select_fusion.3", 95, 11),
          ("reverse.179", 110, 50), ("copy.3", 165, 7)]


def _mesh_trace(lattice=(100, 400, 900), other=(600,)):
    """Four TPU planes, each with launches of the mesh program at
    ``lattice`` (the last ends past the window [0, 1000)) and of a module
    the program never compiled at ``other``, each running ``LAUNCH``."""
    events = [T.Event("/host:CPU", "python3", "bench.traced", 0.0, 1000.0)]
    launches = {}
    for d in range(4):
        plane = f"/device:TPU:{d}"
        launches[plane] = sorted(
            [(float(t), float(t + 200), MESH_MODULE) for t in lattice]
            + [(float(t), float(t + 190), "jit_fn") for t in other])
        for t0 in lattice + other:
            for name, at, dur in LAUNCH:
                events.append(T.Event(plane, "XLA Ops",
                                      f"%{name} = s32[2]{{0}} op(x)",
                                      float(t0 + at),
                                      float(dur or 20 + 4 * d)))
    return events, launches


@pytest.fixture
def mesh_trace(monkeypatch):
    from repro.core import engine
    events, launches = _mesh_trace()
    monkeypatch.setattr(engine, "compiled_hlo_texts",
                        lambda: {MESH_MODULE: MESH_HLO})
    monkeypatch.setattr(readers, "module_launches", lambda ops: launches)
    return events


def test_all_reduces_are_found_by_opcode():
    assert readers.all_reduces(MESH_HLO) == {
        "all-reduce.33": "all-reduce", "all-reduce-start.2": "all-reduce",
        "all-reduce-done.2": "all-reduce"}
    scopes = readers.instruction_scopes(MESH_HLO)
    assert scopes["all-reduce.33"] == scopes["select_fusion.3"] == "search"
    assert scopes["reverse.179"] == "extract" and "copy.3" not in scopes


@pytest.mark.parametrize("metric,ns", [
    # the merges: the all-reduce (26 ns averaged over the chips) and the
    # async pair; not the tuple read nor the fusion that follow it
    ("merge_ms.mesh4", 26 + 3 + 9),
    ("search_ms.mesh4", 40 + 26 + 3 + 9 + 2 + 11),
    ("extract_ms.mesh4", 50)])
def test_mesh_device_readers_on_four_chips(metric, ns, mesh_trace):
    """Two lattice launches lie wholly inside the window on each of the
    four chips; the third ends past it, and the other module's launch
    (whose ops carry the same instruction names) is no lattice launch."""
    red = T.reduce(mesh_trace, "tpu")
    assert red.devices == 4
    read = harness.load_reader(metric)
    assert read(_ctx(trace=red)) == pytest.approx(ns * 1e-6)


def test_mesh_idle_is_averaged_over_the_four_chips(mesh_trace):
    red = T.reduce(mesh_trace, "tpu")
    busy = [sum(min(e.end_ns, 1000.0) - e.start_ns for e in red.ops
                if e.plane == f"/device:TPU:{d}") for d in range(4)]
    want = (1 - sum(busy) / 4 / 1000.0) * 100
    for metric in ("device_idle.mesh4", "device_idle.clique"):
        assert harness.load_reader(metric)(_ctx(trace=red)) == \
            pytest.approx(want)


def test_merge_reader_without_collectives(mesh_trace, monkeypatch):
    """A program that runs on one chip has no all-reduce: the merge
    reader finds nothing to read while the phase readers still read."""
    from repro.core import engine
    one_chip = "\n".join(line for line in MESH_HLO.splitlines()
                         if "all-reduce" not in line.split("=")[0])
    monkeypatch.setattr(engine, "compiled_hlo_texts",
                        lambda: {MESH_MODULE: one_chip})
    red = T.reduce(mesh_trace, "tpu")
    assert harness.load_reader("merge_ms.mesh4")(_ctx(trace=red)) is None
    assert harness.load_reader("search_ms.mesh4")(_ctx(trace=red)) == \
        pytest.approx((40 + 2 + 11) * 1e-6)


@pytest.mark.parametrize("metric", MESH_DEVICE)
def test_mesh_device_readers_without_their_data(metric, mesh_trace,
                                                monkeypatch):
    from repro.core import engine
    read = harness.load_reader(metric)
    red = T.reduce(mesh_trace, "tpu")
    assert read(_ctx()) is None                        # no trace
    monkeypatch.setattr(readers, "module_launches", lambda ops: {})
    assert read(_ctx(trace=red)) is None               # no module line
    events, launches = _mesh_trace()
    monkeypatch.setattr(readers, "module_launches", lambda ops: launches)
    monkeypatch.delattr(engine, "compiled_hlo_texts")
    assert read(_ctx(trace=red)) is None               # no HLO texts


def test_mesh_counter_readers():
    ctx = _ctx([_answered({"prepare": v, "canonicalize": v / 10,
                           "fetch": v / 4}) for v in (0.02, 0.03, 0.5)])
    ctx["layers"] = {"execute": (4, 2.0)}
    assert harness.load_reader("dispatch_ms.mesh4")(ctx) == \
        pytest.approx(500.0)
    assert harness.load_reader("prepare_ms.mesh4")(ctx) == \
        pytest.approx(30.0)
    assert harness.load_reader("canon_ms.mesh4")(ctx) == \
        pytest.approx(3.0)
    assert harness.load_reader("fetch_ms.mesh4")(ctx) == \
        pytest.approx(7.5)
    bare = _ctx([_answered(None)])
    bare["layers"] = {"execute": (0, 0.0)}
    for metric in MESH:
        assert harness.load_reader(metric)(bare) is None


@pytest.mark.parametrize("metric", sorted(MESH_SPANS))
def test_mesh_idle_under_host_spans(metric, mesh_trace, monkeypatch):
    """Each reader takes the chips' idle time under its own host span:
    open over [0, 100) (no launch yet: idle on every chip) and [600, 700)
    (the other module's launch, idle over [660 + 4 d, 675), [678, 680),
    [689, 690) and [692, 695) on chip d), so 121 - 4 d ns on chip d, 11.5
    % of the window on average.  The other reader's span lies where the
    chips are busy."""
    span = MESH_SPANS[metric]
    other = (MESH_SPANS.keys() - {metric}).pop()
    red = T.reduce(mesh_trace, "tpu")

    def spans(lo, name):
        if name == span:
            return [(0.0, 100.0), (600.0, 700.0)]
        return [(100.0, 140.0)] if name == MESH_SPANS[other] else []
    monkeypatch.setattr(readers, "host_spans", spans)
    read = harness.load_reader(metric)
    assert read(_ctx(trace=red)) == pytest.approx(11.5)
    monkeypatch.setattr(readers, "host_spans", lambda lo, name: [])
    assert read(_ctx(trace=red)) is None               # no such span
    assert read(_ctx()) is None                        # no trace
