"""The plan server's phases on the profiler's clock.

``repro.obs.trace.phase`` wraps one block of host work in a
``plan.<name>`` ``TraceAnnotation`` and, under a request's span, a child
span; the engine's dispatch phases carry the ``DispatchRecord``'s id; a
traced response carries its phases' seconds in ``timing_s``; and the
lattice programs name their device phases, ``search`` and ``extract``,
in modules named after their bucket.
"""
import asyncio
import re

import jax
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core.querygraph import chain, make_cardinalities
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer, phase
from repro.service import (PlanServer, VirtualClock, WorkloadSpec,
                           make_workload)

REQUEST_KEYS = {"admit", "canonicalize", "probe"}
DISPATCH_KEYS = {"queue_wait", "prepare", "execute", "fetch"}


@pytest.fixture
def annotations(monkeypatch):
    """Every ``TraceAnnotation`` opened, as (name, metadata) pairs."""
    opened = []

    class Recording:
        def __init__(self, name, **ids):
            self.entry = (name, ids)

        def __enter__(self):
            opened.append(self.entry)
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    return opened


def test_phase_annotates_and_feeds_the_span_histogram(annotations):
    reg = MetricsRegistry()
    clk = VirtualClock()
    tracer = Tracer(clk, registry=reg)
    root = tracer.request()
    with phase("canonicalize", root, req_id=7) as p:
        clk.advance(0.25)
    assert annotations == [("plan.canonicalize", {"req_id": 7})]
    child = root.find("canonicalize")
    assert not child.open and child.duration == 0.25
    h = reg.histogram("trace.canonicalize_s")
    assert h.count == 1 and h.sum == 0.25
    assert p.seconds >= 0.0
    # without a parent span: the annotation alone
    with phase("prepare", dispatch=3):
        pass
    assert annotations[-1] == ("plan.prepare", {"dispatch": 3})
    assert [c.name for c in root.children] == ["canonicalize"]


def test_dispatch_phases_carry_the_record_id(annotations):
    card = np.asarray(make_cardinalities(chain(6), seed=3), np.float64)
    mark = engine_mod.dispatch_mark()
    engine_mod.fused_dpconv_max(card[None], 6)
    (rec,) = engine_mod.dispatches_since(mark)
    assert annotations == [("plan.prepare", {"dispatch": rec.seq}),
                           ("plan.execute", {"dispatch": rec.seq}),
                           ("plan.fetch", {"dispatch": rec.seq})]
    assert rec.prepare_s > 0 and rec.execute_s > 0 and rec.fetch_s > 0


def _misses(k: int):
    reqs = make_workload(WorkloadSpec(n_requests=24, seed=3, n_range=(6, 7),
                                      pool_size=6, rate=500.0))
    return [r for r in reqs if r.cost == "max" and r.q.n >= 6][:k]


def test_plan_async_response_carries_timing(annotations):
    reqs = _misses(2)
    srv = PlanServer(max_batch=4)

    async def main(batch):
        return await asyncio.gather(*(srv.plan_async(r.q, r.card,
                                                     cost="max", req_id=i)
                                      for i, r in enumerate(batch)))
    try:
        solved = asyncio.run(main(reqs))
        (hit,) = asyncio.run(main(reqs[:1]))
    finally:
        srv.async_runtime().close()
    for resp in solved:
        assert set(resp.timing_s) == REQUEST_KEYS | DISPATCH_KEYS
        assert all(v >= 0 for v in resp.timing_s.values())
        assert resp.timing_s["execute"] > 0
        assert resp.timing_s["admit"] >= resp.timing_s["canonicalize"]
    assert hit.cache_hit and set(hit.timing_s) == REQUEST_KEYS
    # the cache replays the plan, not the first request's timings
    assert "timing_s" not in hit.meta
    names = {name for name, _ in annotations}
    assert {"plan.admit", "plan.canonicalize", "plan.probe",
            "plan.close_bucket", "plan.finalize", "plan.prepare",
            "plan.execute", "plan.fetch"} <= names
    assert ("plan.admit", {"req_id": 1}) in annotations


def test_serve_answers_do_not_depend_on_tracing():
    reqs = make_workload(WorkloadSpec(n_requests=16, seed=5, n_range=(5, 7),
                                      pool_size=5, rate=500.0))
    traced, _ = PlanServer(max_batch=8).serve(list(reqs), closed_loop=True)
    plain, _ = PlanServer(max_batch=8, trace=False).serve(
        list(reqs), closed_loop=True)
    for a, b in zip(traced, plain):
        assert float(a.cost) == float(b.cost)
        assert repr(a.tree) == repr(b.tree)
        assert b.timing_s is None
        assert REQUEST_KEYS <= set(a.timing_s)


def test_named_scopes_reach_op_name_in_distinct_modules(monkeypatch):
    engine_mod.clear_executable_cache()
    calls = []
    as_text = jax.stages.Compiled.as_text
    monkeypatch.setattr(jax.stages.Compiled, "as_text",
                        lambda self, *a, **k: calls.append(1)
                        or as_text(self, *a, **k))
    card = np.asarray(make_cardinalities(chain(6), seed=3), np.float64)
    engine_mod.fused_dpconv_max(card[None], 6)
    engine_mod.fused_dpconv_max(np.stack([card, card[::-1]]), 6)
    assert not calls                 # compiling and serving read no text
    texts = engine_mod.compiled_hlo_texts()
    assert len(calls) == 2
    assert set(texts) == {"jit_max_n6_B1_C64_xla", "jit_max_n6_B2_C64_xla"}
    assert {m["module"] for m in engine_mod.compiled_buckets()} == set(texts)
    for name, text in texts.items():
        assert text.startswith(f"HloModule {name},")
        scopes = {part for path in re.findall(r'op_name="([^"]*)"', text)
                  for part in path.split("/")}
        assert {"search", "extract"} <= scopes
    engine_mod.clear_executable_cache()
