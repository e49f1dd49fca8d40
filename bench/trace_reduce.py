"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the benchmark's
device numbers: busy time (the union of device-op intervals), the idle
share of the traced window, device time per operation name, and the idle
gaps named by what the harness was doing in them.

The caller names the platform the trace was taken on.  On a TPU the
device operations are the events of each TPU plane's "XLA Ops" line, and
a trace without them is an error, not an empty device.  A trace taken on
the CPU (the recorded test fixture, the CPU rehearsal) has no device
plane; there the operations are the host events that carry an ``hlo_op``
statistic.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()            # (key, value) pairs

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def stat(self, key: str, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default


def newest_trace(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_events(path: str) -> list:
    """Every event of the trace, flattened."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 tuple((str(k), v) for k, v in e.stats)))
    return out


def device_ops(events: list, platform: str) -> list:
    """The device operations of a trace taken on ``platform`` (see the
    module docstring)."""
    if platform == "tpu":
        planes = {e.plane for e in events
                  if e.plane.startswith("/device:TPU:")}
        ops = [e for e in events
               if e.plane in planes and e.line == "XLA Ops"]
        if not ops:
            raise ValueError(
                f"trace has {len(planes)} TPU plane(s) and no \"XLA Ops\" "
                "events: the profiler's layout is not the one this reduction "
                "reads")
        return ops
    if platform == "cpu":
        return [e for e in events if e.stat("hlo_op") is not None
                and e.dur_ns > 0]
    raise ValueError(f"no device-op form for platform {platform!r}")


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


_INSTR = re.compile(r"\s*%?(?P<name>[\w.\-]+)\s*=\s*(?P<result>\S+)")


def op_label(text: str) -> str:
    """A short name for a device operation.  A TPU trace names each by
    its HLO instruction text: keep the instruction name and its result
    shape, and put every call of one Pallas kernel under one label.  A
    CPU trace's names are already short."""
    m = _INSTR.match(text)
    if m is None:
        return text
    name, result = m["name"], m["result"]
    if 'custom_call_target="tpu_custom_call"' in text:
        return name.split(".")[0] + " (Pallas kernel)"
    result = "(tuple)" if result.startswith("(") else \
        re.sub(r"\{.*$", "", result)
    return f"{name} {result}"


def self_times(ops: list) -> dict:
    """Device time of each op less the ops nested inside it (a ``while``
    event spans its body's ops on the same line), keyed by ``id``."""
    out = {}
    by_line: dict = {}
    for e in ops:
        by_line.setdefault((e.plane, e.line), []).append(e)
    for evs in by_line.values():
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        stack: list = []
        for e in evs:
            while stack and stack[-1].end_ns <= e.start_ns:
                stack.pop()
            out[id(e)] = e.dur_ns
            if stack and e.end_ns <= stack[-1].end_ns:
                out[id(stack[-1])] -= e.dur_ns
            stack.append(e)
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float               # length of the traced window
    busy_s: float                 # union of device ops, averaged per device
    devices: int
    op_s: dict                    # op label -> summed device self seconds
    gaps: list                    # (seconds, what the host was doing)
    ops: list                     # the device-op events inside the window
    lo_ns: float = 0.0            # the window, on the trace's clock
    hi_ns: float = 0.0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def top_ops(self, k: int = 10) -> list:
        return sorted(([n, s] for n, s in self.op_s.items()),
                      key=lambda x: -x[1])[:k]

    def top_gaps(self, k: int = 10) -> list:
        """Idle seconds summed by what the host was doing, largest first."""
        by: dict = {}
        for secs, what in self.gaps:
            by[what] = by.get(what, 0.0) + secs
        return sorted(([w, s] for w, s in by.items()),
                      key=lambda x: -x[1])[:k]


def reduce(events: list, platform: str, window: str = "bench.traced",
           host_prefix: str = "bench.") -> Reduced:
    """Reduce one trace taken on ``platform``.  The window is the span of
    the host event named ``window`` (the whole trace if there is none);
    idle gaps are named after the ``host_prefix`` host span that covers
    most of each gap."""
    ops = device_ops(events, platform)
    marks = [e for e in events if e.name == window]
    if marks:
        lo, hi = marks[0].start_ns, marks[0].end_ns
    else:
        lo = min(e.start_ns for e in events)
        hi = max(e.end_ns for e in events)
    planes = sorted({e.plane for e in ops}) or ["-"]
    busy = 0.0
    gaps: list = []
    host = _Host([e for e in events if e.name.startswith(host_prefix)
                  and e.name != window])
    inside = []
    for plane in planes:
        mine = [e for e in ops if e.plane == plane or plane == "-"]
        mine = [e for e in mine if e.end_ns > lo and e.start_ns < hi]
        inside += mine
        merged = clip(union((e.start_ns, e.end_ns) for e in mine), lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append(((g1 - g0) * 1e-9, host.doing(g0, g1)))
    op_s: dict = {}
    own = self_times(inside)
    for e in inside:
        f = op_label(e.name)
        op_s[f] = op_s.get(f, 0.0) + own[id(e)] * 1e-9
    n = len(planes)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                   devices=n, op_s=op_s, gaps=gaps, ops=inside,
                   lo_ns=lo, hi_ns=hi)


class _Host:
    """Harness host spans, sorted for the question "which one covers
    most of [g0, g1)"."""

    SHORT_NS = 10_000            # gaps under 10 us are not looked up

    def __init__(self, spans: list):
        self.spans = sorted(spans, key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.spans]
        self.longest = max((e.dur_ns for e in spans), default=0.0)

    def doing(self, g0: float, g1: float) -> str:
        if g1 - g0 < self.SHORT_NS:
            return "between ops (under 10 us)"
        best, cover = "no harness span", 0.0
        lo = bisect.bisect_left(self.starts, g0 - self.longest)
        hi = bisect.bisect_right(self.starts, g1)
        for e in self.spans[lo:hi]:
            c = min(e.end_ns, g1) - max(e.start_ns, g0)
            if c > cover:
                best, cover = e.name, c
        return best
