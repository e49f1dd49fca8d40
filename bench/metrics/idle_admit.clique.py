"""Admission: percent of the traced window in which no operation ran on
the device while the program's ``plan.admit`` span (``ServingRuntime.
submit``, on the event loop) was open: the idle time admission holds the
chip back.

The harness hands readers the device ops only, so this reads the host
planes of the newest trace under ``.bench_trace`` and takes that trace
only if its ``bench.traced`` span starts where the window does.  Idle is
what ``trace_reduce`` takes it to be: the window less the union of the
device ops, per device plane, averaged over the planes.  None where that
trace is not the window's or holds no ``plan.admit`` span.
"""
import os

from bench import harness, trace_reduce

SPAN = "plan.admit"


def read(ctx):
    red = ctx.get("trace")
    if red is None or not red.window_s:
        return None
    spans = host_spans(red.lo_ns)
    if not spans:
        return None
    return idle_under(red, spans) * 100.0


def host_spans(lo_ns: float) -> list:
    """(start, end) of every ``plan.admit`` event on the host planes of
    the newest trace, if its ``bench.traced`` span starts at ``lo_ns``;
    else empty."""
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
                if name == SPAN:
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
