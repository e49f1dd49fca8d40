"""What the per-layer readers in ``bench/metrics`` share.  A reader file
names its metric and calls one of these; each returns None where the
program or the trace lacks the data, so the metric is left out of the
run's line rather than read as 0.

* ``timing_median_ms``: the median of a response's ``timing_s`` key over
  the window's answered requests (robust to the few requests the
  profiler's stop stalls);
* ``execute_mean_ms``: the mean ``DispatchRecord.execute_s`` over the
  window's dispatches, up to the trace;
* ``idle_percent``: the idle share of the traced window, averaged over
  the device planes;
* ``idle_under_percent``: the idle share of the traced window while one
  of the program's host spans (``plan.admit``, ``plan.close_bucket``) is
  open, read from the host planes of the window's own trace;
* ``scope_ms`` and ``all_reduce_ms``: device self time per
  lattice-program launch per chip of the ops under a phase scope, or of
  the all-reduce instructions.

A TPU trace names a device op by its HLO instruction alone.  Each op is
matched to the launch on its plane's "XLA Modules" line that contains it,
which names the executable (``jit_max_n15_B2_C32768_pallas``); the
instruction is looked up in that module's optimized HLO
(``engine.compiled_hlo_texts()``): its ``op_name`` metadata carries the
scope, its opcode says whether it is a collective.  Launches count only
if they lie wholly inside the window, once on each plane they ran on.
"""
from __future__ import annotations

import bisect
import os
import re
import statistics

from bench import harness, trace_reduce

PHASES = ("search", "extract")
ALL_REDUCE = ("all-reduce", "all-reduce-start", "all-reduce-done")
_NAME = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LAYOUT = re.compile(r"\{[^{}]*\}")
# after the layouts are taken out: "= <shape or (tuple)> <opcode>("
_OPCODE = re.compile(r"=\s*(?:\([^()]*\)|\S+)\s+([\w\-]+)\(")


# ---------------------------------------------------- program counters
def timing_median_ms(ctx, key: str):
    vals = [t[key] for r in ctx["window"].recs
            if (t := getattr(r.resp, "timing_s", None)) and key in t]
    return statistics.median(vals) * 1e3 if vals else None


def execute_mean_ms(ctx):
    count, total = ctx["layers"]["execute"]
    return total / count * 1e3 if count else None


# ---------------------------------------------------------- device trace
def idle_percent(ctx):
    red = ctx["trace"]
    if red is None or not red.window_s:
        return None
    return red.idle_share * 100.0


def idle_under_percent(ctx, span: str):
    """Percent of the traced window in which no operation ran on a
    device plane while a host span named ``span`` was open, averaged over
    the planes.  None where the trace is not the window's or holds no
    such span."""
    red = ctx.get("trace")
    if red is None or not red.window_s:
        return None
    spans = host_spans(red.lo_ns, span)
    if not spans:
        return None
    return idle_under(red, spans) * 100.0


def host_spans(lo_ns: float, span: str) -> list:
    """(start, end) of every ``span`` event on the host planes of the
    newest trace under ``.bench_trace``, if its ``bench.traced`` span
    starts at ``lo_ns``; else empty.  (The harness hands readers the
    device ops only.)"""
    try:
        path = trace_reduce.newest_trace(
            os.path.join(harness.ROOT, ".bench_trace"))
    except FileNotFoundError:
        return []
    from jax.profiler import ProfileData
    spans, traced = [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name == span:
                    spans.append((float(e.start_ns),
                                  float(e.start_ns + e.duration_ns)))
                elif name == "bench.traced" and traced is None:
                    traced = float(e.start_ns)
    return spans if traced == lo_ns else []


def idle_under(red, spans: list) -> float:
    """Share of the window that is idle on a device plane and under one
    of ``spans``, averaged over the planes."""
    lo, hi = red.lo_ns, red.hi_ns
    under = trace_reduce.clip(trace_reduce.union(spans), lo, hi)
    planes = sorted({e.plane for e in red.ops}) or ["-"]
    total = 0.0
    for plane in planes:
        busy = trace_reduce.clip(trace_reduce.union(
            (e.start_ns, e.end_ns) for e in red.ops if e.plane == plane),
            lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        total += _overlap(idle, under)
    return total / (hi - lo) / len(planes)


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def scope_ms(ctx, scope: str):
    return module_ms(ctx, instruction_scopes, scope)


def all_reduce_ms(ctx):
    return module_ms(ctx, all_reduces, "all-reduce")


def module_ms(ctx, classify, label: str):
    """Device self time per launch per chip of the window's ops that
    ``classify`` (a module's HLO text -> {instruction name: label})
    labels ``label``.  None where the program gives no HLO texts, the
    trace has no module line, or no op of a launch inside the window
    carries any label."""
    red = ctx.get("trace")
    if red is None or not red.ops:
        return None
    from repro.core import engine
    texts = getattr(engine, "compiled_hlo_texts", None)
    if texts is None:
        return None
    launches = module_launches(red.ops)
    return per_launch_ms(red, launches, texts(), classify, label) \
        if launches else None


def module_launches(ops) -> dict:
    """plane -> [(start_ns, end_ns, module name)] of the "XLA Modules"
    line of each plane the ops ran on, from the newest trace under
    ``.bench_trace``; empty where there is none."""
    try:
        path = trace_reduce.newest_trace(
            os.path.join(harness.ROOT, ".bench_trace"))
    except FileNotFoundError:
        return {}
    from jax.profiler import ProfileData
    planes = {e.plane for e in ops}
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name not in planes:
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                out[plane.name] = sorted(
                    (float(e.start_ns), float(e.start_ns + e.duration_ns),
                     _name(e.name)) for e in line.events)
    return out


def _name(text: str) -> str:
    """The leading name of an instruction or module event's text."""
    m = _NAME.match(text)
    return m.group(1) if m else ""


def per_launch_ms(red, launches: dict, texts: dict, classify,
                  label: str):
    """Device self time of the window's ops labelled ``label`` over the
    lattice-program launches wholly inside the window (counted on each
    plane), in ms."""
    inside = {(p, s) for p, ls in launches.items() for s, e, m in ls
              if m in texts and red.lo_ns <= s and e <= red.hi_ns}
    if not inside:
        return None
    own = trace_reduce.self_times(red.ops)
    starts = {p: [s for s, _e, _m in ls] for p, ls in launches.items()}
    modules: dict = {}
    named = False
    total = 0.0
    for op in red.ops:
        i = bisect.bisect_right(starts.get(op.plane, []), op.start_ns) - 1
        if i < 0:
            continue
        s, e, module = launches[op.plane][i]
        if (op.plane, s) not in inside or op.end_ns > e:
            continue
        if module not in modules:
            modules[module] = classify(texts[module])
        found = modules[module].get(_name(op.name))
        named = named or found is not None
        if found == label:
            total += own[id(op)]
    return total * 1e-6 / len(inside) if named else None


def instruction_scopes(text: str) -> dict:
    """Instruction name -> the phase scope in its ``op_name`` metadata,
    for each instruction of one module's HLO text that carries one."""
    out = {}
    for line in text.splitlines():
        path = _OP_NAME.search(line)
        name = _name(line)
        if path is None or not name or "=" not in line:
            continue
        scope = next((p for p in path.group(1).split("/") if p in PHASES),
                     None)
        if scope is not None:
            out[name] = scope
    return out


def all_reduces(text: str) -> dict:
    """Instruction name -> "all-reduce" for each instruction of one
    module's HLO text whose opcode is an all-reduce or one of its async
    halves (``all-reduce-start``, ``all-reduce-done``)."""
    out = {}
    for line in text.splitlines():
        if "all-reduce" not in line:
            continue
        m = _OPCODE.search(_LAYOUT.sub("", line))
        if m is not None and m.group(1) in ALL_REDUCE:
            out[_name(line)] = "all-reduce"
    return out
