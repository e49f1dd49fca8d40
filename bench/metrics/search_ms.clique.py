"""Engine: device self time of the fused program's ``search`` phase (the
gate builder and the threshold search loop, ``jax.named_scope("search")``
in the program's ``core/lattice.py``) per lattice-program launch in the
traced window, in ms.

A TPU trace names a device op by its HLO instruction alone.  Each op is
matched to the launch on its plane's "XLA Modules" line that contains it,
which names the executable (``jit_max_n15_B2_C32768_pallas``); the
instruction is looked up in that module's optimized HLO
(``engine.compiled_hlo_texts()``), whose ``op_name`` metadata carries the
scope; an op whose instruction carries none (a copy, a parameter) counts
in no phase.  Launches count only if they lie wholly inside the window.
None where the program gives no HLO texts, the trace has no module line,
or no op carries a phase scope.
"""
import bisect
import os
import re

from bench import harness, trace_reduce

SCOPE = "search"
PHASES = ("search", "extract")
_NAME = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def read(ctx):
    red = ctx.get("trace")
    if red is None or not red.ops:
        return None
    from repro.core import engine
    texts = getattr(engine, "compiled_hlo_texts", None)
    if texts is None:
        return None
    launches = module_launches(red.ops)
    return per_launch_ms(red, launches, texts(), SCOPE) if launches \
        else None


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


def per_launch_ms(red, launches: dict, texts: dict, scope: str):
    """Device self time of the window's ops under ``scope`` over the
    lattice-program launches wholly inside the window, in ms."""
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
            modules[module] = instruction_scopes(texts[module])
        found = modules[module].get(_name(op.name))
        named = named or found is not None
        if found == scope:
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
