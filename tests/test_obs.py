"""Tests for the observability layer (``repro.obs``) and its threading
through the serving stack.

Everything here is deterministic: span trees are driven on a
``VirtualClock`` with injected durations, so each asserted ``shape()``
reproduces bit-for-bit; engine-profiling attributes (AOT cache hit,
compile/execute split, while-loop rounds) come from the real fused
engine and are asserted structurally, not on wall times.
"""
import json
import threading

import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core.querygraph import chain, make_cardinalities
from repro.obs.export import prometheus, span_phase_summary
from repro.obs.metrics import BOUNDS, Histogram, MetricsRegistry
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import NULL_SPAN, Tracer
from repro.service import (PlanRequest, PlanServer, RuntimeConfig,
                           SLOClass, VirtualClock, WorkloadSpec,
                           make_workload)

DUR = {"admit": 0.0, "solve": 1.0, "single": 0.01}
# admission's span with its phase children
ADMIT = ("admit", (("canonicalize", ()), ("probe", ())))


def _dur(kind, info):
    return DUR[kind]


def _mk(max_batch=8, **cfg_kw):
    srv = PlanServer(max_batch=max_batch)
    clk = VirtualClock()
    cfg = RuntimeConfig(max_batch=max_batch, **cfg_kw)
    return srv, clk, srv.make_runtime(clock=clk, config=cfg,
                                      duration_fn=_dur)


def _reqs(**kw):
    base = dict(n_requests=24, seed=0, n_range=(6, 7), pool_size=6,
                rate=500.0)
    base.update(kw)
    return make_workload(WorkloadSpec(**base))


# ------------------------------------------------------------ histograms
def test_histogram_empty_quantiles_are_zero():
    h = Histogram("t")
    s = h.summary()
    assert s["count"] == 0
    assert s["p50"] == 0.0 and s["p95"] == 0.0 and s["p99"] == 0.0
    assert s["min"] == 0.0 and s["max"] == 0.0


def test_histogram_single_sample():
    h = Histogram("t")
    h.observe(0.5)
    s = h.summary()
    assert s["count"] == 1
    assert s["min"] == s["max"] == 0.5
    # the quantile is the enclosing log-bucket's upper bound
    assert s["p50"] >= 0.5
    assert s["p50"] <= 0.5 * 10 ** 0.25 * 1.001


def test_histogram_saturated_overflow_returns_observed_max():
    h = Histogram("t")
    for _ in range(100):
        h.observe(5e4)          # far past the 1e3 s top bound
    assert h.overflow == 100
    assert h.percentile(50) == 5e4
    assert h.percentile(99) == 5e4
    assert h.max == 5e4


def test_histogram_underflow_clamps_to_lowest_bucket():
    h = Histogram("t")
    h.observe(1e-12)
    h.observe(0.0)
    assert h.count == 2
    assert h.percentile(50) <= BOUNDS[0]


def test_histogram_quantile_ordering():
    h = Histogram("t")
    for v in (1e-4,) * 90 + (1e-1,) * 9 + (10.0,):
        h.observe(v)
    assert h.percentile(50) < h.percentile(95) <= h.percentile(99)
    assert abs(h.sum - (90 * 1e-4 + 9 * 1e-1 + 10.0)) < 1e-9


# -------------------------------------------------------------- registry
def test_registry_name_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_registry_thread_safety_under_contention():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("h")

    def work():
        for _ in range(2000):
            c.inc()
            h.observe(1e-3)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 16000
    assert h.count == 16000


def test_prometheus_rendering():
    reg = MetricsRegistry()
    reg.counter("engine.dispatches").inc(3)
    reg.histogram("trace.dispatch_s").observe(0.01)
    text = prometheus(reg)
    assert "# TYPE engine_dispatches counter" in text
    assert "engine_dispatches 3" in text
    assert 'le="+Inf"' in text
    assert "trace_dispatch_s_count 1" in text


# --------------------------------------------------------- engine stats
def test_engine_stats_registry_backed_and_reset():
    engine_mod.reset_stats()
    st = engine_mod.stats()
    d = st.as_dict()
    assert set(d) == set(engine_mod.EngineStats.FIELDS)
    assert all(v == 0 for v in d.values())
    st.inc("dispatches", 2)
    assert st.dispatches == 2
    engine_mod.reset_stats()
    assert engine_mod.stats().dispatches == 0


def test_engine_dispatch_records_compile_execute_split():
    engine_mod.reset_stats()
    engine_mod.clear_executable_cache()
    q = chain(6)
    card = make_cardinalities(q, seed=3)
    cards = np.asarray(card, np.float64)[None, :]
    mark = engine_mod.dispatch_mark()
    fs = engine_mod.fused_dpconv_max(cards, 6)
    recs = engine_mod.dispatches_since(mark)
    assert len(recs) == 1
    r = recs[0]
    assert not r.aot_cache_hit and r.compile_s > 0
    assert r.execute_s > 0 and r.rounds == fs.rounds
    assert r.prepare_s > 0 and r.fetch_s > 0
    assert r.cost == "max" and r.n == 6 and r.B == 1
    # second solve: AOT cache hit, no compile time charged
    mark = engine_mod.dispatch_mark()
    engine_mod.fused_dpconv_max(cards, 6)
    r2 = engine_mod.dispatches_since(mark)[0]
    assert r2.aot_cache_hit and r2.compile_s == 0.0
    d = r.as_dict()
    assert {"seq", "cost", "compile_s", "prepare_s", "execute_s",
            "fetch_s", "rounds"} <= set(d)
    assert not {"flops", "bytes_accessed"} & set(d)


# ----------------------------------------------------------- span trees
def test_deterministic_span_tree_batch_miss():
    """The acceptance-criterion tree: a batched miss through the runtime
    on VirtualClock yields exactly request(admit, queue_wait, dispatch,
    extract, respond), with the dispatch child carrying the engine's
    compile/execute split and round count."""
    reqs = _reqs()
    srv, clk, rt = _mk()
    miss = next(r for r in reqs if r.cost == "max" and r.q.n >= 6)
    t = rt.submit(miss)
    rt.drain()
    assert t.done and not t.refused
    assert t.span.shape() == (
        "request", (ADMIT, ("queue_wait", ()), ("dispatch", ()),
                    ("extract", ()), ("respond", ())))
    d = t.span.find("dispatch")
    assert d.attrs["duration_s"] == 1.0          # injected solve time
    assert d.attrs["items"] == 1
    assert "fused" in d.attrs["engine_tag"] or \
        "host" in d.attrs["engine_tag"]
    if d.attrs.get("dispatches"):                # fused lane profiled
        assert d.attrs["execute_s"] > 0
        assert d.attrs["rounds"] >= 0
        assert "compile_s" in d.attrs and "aot_cache_hits" in d.attrs
    # span times are virtual-clock deterministic
    assert t.span.t0 == 0.0 and t.span.t1 == t.completed_at
    assert rt.tracer.stats()["unclosed_spans"] == 0
    assert rt.tracer.stats()["open_spans"] == 0
    assert rt.tracer.stats()["lane_shape_mismatches"] == 0


def test_fast_path_span_tree_and_relabel_hit():
    """A relabeled duplicate serves from cache on the fast path: 4-span
    tree, and CacheStats.relabel_hits counts it."""
    from repro.core.querygraph import permute_card, relabel
    reqs = _reqs()
    srv, clk, rt = _mk()
    base = next(r for r in reqs if r.cost == "max" and r.q.n >= 6)
    t0 = rt.submit(base)
    rt.drain()
    assert t0.done
    rng = np.random.default_rng(7)
    perm = rng.permutation(base.q.n)
    req2 = PlanRequest(q=relabel(base.q, perm),
                       card=permute_card(base.card, base.q.n, perm),
                       cost=base.cost, req_id="relabeled")
    t1 = rt.submit(req2)
    assert t1.done and t1.response.cache_hit
    assert t1.span.shape() == (
        "request", (ADMIT, ("fast_path", ()), ("respond", ())))
    assert srv.cache.stats.relabel_hits >= 1


def test_coalesced_follower_span_tree():
    reqs = _reqs()
    srv, clk, rt = _mk()
    miss = next(r for r in reqs if r.cost == "max" and r.q.n >= 6)
    t_lead = rt.submit(miss)
    t_follow = rt.submit(miss)          # same key, still queued: joins
    rt.drain()
    assert rt.stats.coalesced == 1
    assert t_follow.span.shape() == (
        "request", (ADMIT, ("coalesce", ()), ("queue_wait", ()),
                    ("dispatch", ()), ("extract", ()), ("respond", ())))
    assert t_follow.response.meta.get("coalesced") is True
    assert t_lead.span.find("coalesce") is None
    # the cache counted the leader's insert; the follower's fast replay
    # went through the coalesce path, not the cache
    assert rt.tracer.stats()["lane_shape_mismatches"] == 0


def test_shed_span_tree_and_recorder_capture():
    srv = PlanServer()
    clk = VirtualClock()
    cfg = RuntimeConfig(slo_classes={
        "strict": SLOClass("strict", 1e-9, "refuse")})
    rt = srv.make_runtime(clock=clk, config=cfg, duration_fn=_dur)
    reqs = _reqs()
    miss = next(r for r in reqs if r.cost == "max" and r.q.n >= 6)
    miss = miss.__class__(**{**miss.__dict__, "slo": "strict"})
    t = rt.submit(miss)
    assert t.refused
    assert t.span.shape() == ("request", (ADMIT, ("shed", ())))
    rec = rt.recorder
    assert rec.counts["shed"] == 1
    assert rec.incidents[0]["kind"] == "shed"
    assert rec.incidents[0]["span"] is t.span
    lines = rec.dump_jsonl()
    parsed = [json.loads(ln) for ln in lines]
    assert any(p["kind"] == "shed" for p in parsed)


def test_tracer_disabled_is_null_and_costless():
    srv, clk, rt = _mk(trace=False)
    reqs = _reqs()
    t = rt.submit(reqs[0])
    rt.drain()
    assert t.span is NULL_SPAN
    assert rt.tracer.stats()["requests"] == 0
    assert rt.tracer.stats()["spans_opened"] == 0
    assert rt.recorder.counts["completed"] == 0


def test_unclosed_span_forced_and_counted():
    reg = MetricsRegistry()
    tr = Tracer(VirtualClock(), registry=reg)
    root = tr.request()
    root.child("dispatch")               # never closed
    tr.finish(root, expected_spans=2)
    assert tr.unclosed_spans == 1
    assert tr.shape_mismatches == 0      # count matches: 2 spans


def test_span_phase_summary_reads_trace_histograms():
    srv, clk, rt = _mk()
    reqs = _reqs()
    for r in reqs[:6]:
        rt.submit(r)
    rt.drain()
    phases = span_phase_summary(srv.registry)
    assert phases["request"]["count"] >= 6
    assert phases["dispatch"]["count"] >= 1
    assert phases["dispatch"]["p95_ms"] >= phases["dispatch"]["p50_ms"] \
        or phases["dispatch"]["count"] == 1


def test_recorder_ring_bounded_incident_counts_exact():
    rec = FlightRecorder(capacity=4, incident_capacity=8)
    tr = Tracer(VirtualClock(), recorder=rec)
    for _ in range(10):
        tr.finish(tr.request())
    assert len(rec.ring) == 4
    assert rec.counts["completed"] == 10
    for i in range(20):
        rec.incident("deadline_miss", None, req_id=str(i))
    assert len(rec.incidents) == 8          # bounded retention...
    assert rec.counts["deadline_miss"] == 20  # ...exact counting


def test_slo_stream_recorder_counts_equal_runtime_stats():
    """A duplicate-heavy SLO-classed stream that sheds, downgrades and
    misses deadlines: the flight recorder counts exactly the runtime's
    incidents, every span closes on its lane's taxonomy, and each
    batched solve is still one fused dispatch."""
    classes = {"strict": SLOClass("strict", 1e-12, "refuse"),
               "loose": SLOClass("loose", 1e-12, "downgrade"),
               "tight": SLOClass("tight", 1.5),
               "batch": SLOClass("batch", None)}
    reqs = _reqs(n_requests=48, seed=3, n_range=(5, 8), fresh_frac=0.0,
                 relabel_frac=0.8, zipf_a=2.0, rate=20.0,
                 cost_mix=(("max", 0.7), ("cap", 0.2), ("out", 0.1)),
                 slo_mix=(("strict", 0.2), ("loose", 0.2),
                          ("tight", 0.3), ("batch", 0.3)))
    srv, clk, rt = _mk(max_batch=4, slo_classes=classes)
    engine_mod.reset_stats()
    for r in sorted(reqs, key=lambda r: r.arrival):
        rt.run_until(r.arrival)
        rt.submit(r)
    rt.drain()
    st, counts = rt.stats, rt.recorder.counts
    assert st.shed and st.downgraded and st.deadline_misses
    assert counts["shed"] == st.shed + st.shed_backpressure
    assert counts["downgraded"] == st.downgraded
    assert counts["deadline_miss"] == st.deadline_misses
    tr = rt.tracer.stats()
    assert tr["requests"] == len(reqs)
    assert tr["open_spans"] == tr["unclosed_spans"] == 0
    assert tr["lane_shape_mismatches"] == 0
    est = engine_mod.stats()
    assert est.solves > 0 and est.dispatches == est.solves
    assert est.host_extractions == 0


# --------------------------------------------------- runtime stats schema
def test_runtime_stats_as_dict_schema_snapshot():
    srv, clk, rt = _mk()
    for r in _reqs()[:8]:
        rt.submit(r)
    rt.drain()
    d = rt.stats.as_dict()
    assert set(d) == {
        "submitted", "served", "fast_path_hits", "overtakes",
        "coalesced", "coalesce_rate", "downgraded", "shed",
        "shed_backpressure", "shed_rate", "batches",
        "mean_batch_occupancy", "steals", "hedges", "lanes",
        "deadline_misses", "solve_s", "miss_solve_ms_mean",
        "hit_p99_ms", "per_class"}
    for lane in d["lanes"].values():
        assert set(lane) == {"dispatches", "steals"}
    for cls in d["per_class"].values():
        assert set(cls) == {"served", "deadline_misses", "downgraded",
                            "shed", "p50_ms", "p95_ms", "p99_ms"}


def test_server_registry_snapshot_has_all_providers():
    srv, clk, rt = _mk()
    for r in _reqs()[:6]:
        rt.submit(r)
    rt.drain()
    snap = srv.registry.snapshot()
    assert {"cache", "router", "serve", "solver", "engine", "runtime",
            "tracer", "recorder"} <= set(snap["providers"])
    assert snap["providers"]["tracer"]["open_spans"] == 0
    # span-duration histograms landed in the metric section
    assert any(k.startswith("trace.") for k in snap["metrics"])


# ------------------------------------------------ explain + connected cap
def test_explain_provenance_on_miss_and_hit():
    srv = PlanServer()
    reqs = _reqs()
    r = next(x for x in reqs if x.cost == "max" and x.q.n >= 6)
    miss = srv.plan_one(r.q, r.card, cost="max", explain=True)
    assert miss.explain is not None
    assert {"lane", "method", "lane_cost", "engine_tag", "cache_key",
            "cache_hit"} <= set(miss.explain)
    assert miss.explain["cache_hit"] is False
    hit = srv.plan_one(r.q, r.card, cost="max", explain=True)
    assert hit.explain["cache_hit"] is True


def test_connected_cap_distinct_cache_key_and_lane():
    srv = PlanServer()
    q = chain(7)
    card = make_cardinalities(q, seed=5)
    plain = srv.plan_one(q, card, cost="cap", explain=True)
    conn = srv.plan_one(q, card, cost="cap", connected=True, explain=True)
    assert plain.explain["cache_key"] != conn.explain["cache_key"]
    assert conn.explain["lane_cost"] == "cap_conn"
    assert conn.explain["engine_tag"].endswith("cap_conn")
    assert plain.explain["lane_cost"] == "cap"
    # both plans satisfy the same cap; the connected plan's tree stays
    # inside the no-cross-products search space
    assert all(q.is_connected(m) for m in conn.tree.internal_masks())
    # parity against the host connected-cap reference
    from repro.core.ccap import ccap
    ref = ccap(q, card, engine="host", connected=True)
    assert float(conn.cost) == pytest.approx(float(ref.cout), rel=1e-12)
    # serving the connected request again is a cache hit on its own key
    again = srv.plan_one(q, card, cost="cap", connected=True)
    assert again.cache_hit


def test_connected_cap_runtime_bucket_separation():
    """cap and cap_conn requests never share a micro-batch bucket: the
    runtime buckets on lane_cost."""
    srv, clk, rt = _mk()
    q = chain(7)
    card = make_cardinalities(q, seed=6)
    t_plain = rt.submit(PlanRequest(q=q, card=card, cost="cap",
                                    req_id="p"))
    t_conn = rt.submit(PlanRequest(q=q, card=card, cost="cap",
                                   connected=True, req_id="c"))
    keys = set(rt._buckets)
    assert (7, "cap") in keys and (7, "cap_conn") in keys
    rt.drain()
    assert t_plain.done and t_conn.done
    assert rt.stats.coalesced == 0       # distinct keys: no join
    assert float(t_conn.response.cost) >= float(t_plain.response.cost)
    dc = t_conn.span.find("dispatch")
    assert dc.attrs["engine_tag"].endswith("cap_conn")


# ---------------------------------------------- head sampling (tracer)
def test_tracer_sample_rate_validation():
    with pytest.raises(ValueError):
        Tracer(VirtualClock(), sample_rate=1.5)
    with pytest.raises(ValueError):
        Tracer(VirtualClock(), sample_rate=-0.1)


def test_tracer_head_sampling_deterministic_even_spread():
    """sample_rate=f traces exactly floor(k*f) of the first k requests,
    counter-based — two tracers agree bit-for-bit, no RNG."""

    def pattern(rate, n):
        tr = Tracer(VirtualClock(), sample_rate=rate)
        picks = []
        for _ in range(n):
            root = tr.request()
            picks.append(root is not NULL_SPAN)
            tr.finish(root)
        return tr, picks

    tr, picks = pattern(0.25, 100)
    assert sum(picks) == 25
    assert tr.sampled == 25 and tr.sampled_out == 75
    assert tr.stats()["sampled"] == 25
    assert tr.stats()["sampled_out"] == 75
    assert tr.open_spans == 0 and tr.unclosed_spans == 0
    assert picks == pattern(0.25, 100)[1]       # deterministic replay
    # rate 1.0 never samples out; rate 0.0 never traces
    tr_all, picks_all = pattern(1.0, 20)
    assert all(picks_all) and tr_all.sampled_out == 0
    tr_none, picks_none = pattern(0.0, 20)
    assert not any(picks_none) and tr_none.sampled == 0
    assert tr_none.spans_opened == 0


def test_runtime_sampling_keeps_incident_capture_unconditional():
    """trace_sample=0 hands every request NULL_SPAN, yet sheds still
    land on the flight recorder — sampling can never hide incidents."""
    srv = PlanServer()
    clk = VirtualClock()
    cfg = RuntimeConfig(trace_sample=0.0, slo_classes={
        "strict": SLOClass("strict", 1e-9, "refuse")})
    rt = srv.make_runtime(clock=clk, config=cfg, duration_fn=_dur)
    reqs = _reqs()
    served = shed = 0
    for r in reqs[:8]:
        strict = r.__class__(**{**r.__dict__, "slo": "strict"})
        t = rt.submit(strict)
        shed += 1 if t.refused else 0
        assert t.span is NULL_SPAN
    rt.drain()
    assert shed > 0
    assert rt.tracer.sampled == 0
    assert rt.tracer.sampled_out == 8
    assert rt.tracer.spans_opened == 0
    assert rt.recorder.counts["shed"] == shed
    # sampled-out incidents carry no span payload, but full info
    assert all(i["span"] is None for i in rt.recorder.incidents)
    assert all(i["info"] for i in rt.recorder.incidents)


def test_runtime_sampling_traces_exact_fraction():
    srv, clk, rt = _mk(trace_sample=0.5)
    for r in _reqs()[:12]:
        rt.submit(r)
    rt.drain()
    st = rt.tracer.stats()
    assert st["requests"] == 12
    assert st["sampled"] == 6 and st["sampled_out"] == 6
    assert st["open_spans"] == 0 and st["unclosed_spans"] == 0
    # the recorder sees exactly the traced completions
    assert rt.recorder.counts["completed"] == 6


# -------------------------------------------------- obs_tail CLI (merge)
def _obs_tail():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "scripts", "obs_tail.py")
    spec = importlib.util.spec_from_file_location("obs_tail", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _dump_replica(tmp_path, rid, t0, n_completed, n_shed):
    clk = VirtualClock()
    clk.advance(t0)
    rec = FlightRecorder()
    tr = Tracer(clk, recorder=rec)
    for i in range(n_completed):
        clk.advance(0.5)
        root = tr.request(req_id=f"{rid}-{i}")
        child = root.child("solve")
        clk.advance(0.010)
        child.close()
        tr.finish(root)
    bare = Tracer(clk)                   # spans for incidents only:
    for i in range(n_shed):              # no recorder, so no double
        clk.advance(0.5)                 # "completed" counting
        root = bare.request(req_id=f"{rid}-shed-{i}")
        root.close()
        rec.incident("shed", root, req_id=f"{rid}-shed-{i}",
                     tenant="noisy")
    path = tmp_path / f"flight_{rid}.jsonl"
    rec.dump_jsonl(str(path), replica=rid)
    return str(path)


def test_obs_tail_merges_tags_and_orders_multi_replica_dumps(tmp_path):
    ot = _obs_tail()
    p0 = _dump_replica(tmp_path, "r0", t0=0.00, n_completed=3, n_shed=1)
    p1 = _dump_replica(tmp_path, "r1", t0=0.25, n_completed=2, n_shed=2)
    recs = ot.merge_records([p0, p1])
    assert len(recs) == 8
    assert {r["replica"] for r in recs} == {"r0", "r1"}
    # global timestamp order, interleaved across replicas

    def at(r):
        return r.get("at") if r.get("at") is not None \
            else r["span"]["t0"]

    assert [at(r) for r in recs] == sorted(at(r) for r in recs)
    assert {r["replica"] for r in recs[:2]} == {"r0", "r1"}
    summary = ot.summarize(recs)
    assert summary["records"] == 8
    assert summary["kinds"] == {"completed": 5, "shed": 3}
    assert summary["replicas"]["r0"] == {"completed": 3, "shed": 1}
    assert summary["replicas"]["r1"] == {"completed": 2, "shed": 2}
    assert summary["phases"]["solve"]["count"] == 5
    assert summary["phases"]["solve"]["p50_ms"] == pytest.approx(
        10.0, rel=1e-6)
    line = ot.format_line(recs[-1])
    assert "shed" in line and "tenant=noisy" in line and "t=" in line


def test_obs_tail_untagged_dump_falls_back_to_filename_stem(tmp_path):
    ot = _obs_tail()
    rec = FlightRecorder()
    rec.incident("error", None, req_id="x")
    path = tmp_path / "flight_r9.jsonl"
    rec.dump_jsonl(str(path))               # no replica tag
    (tmp_path / "flight_bad.jsonl").write_text(
        "not json\n\n" + "\n".join(rec.dump_jsonl()) + "\n")
    recs = ot.load_records(str(path))
    assert recs and all(r["replica"] == "r9" for r in recs)
    # malformed lines are skipped, valid ones still load
    bad = ot.load_records(str(tmp_path / "flight_bad.jsonl"))
    assert len(bad) == 1 and bad[0]["replica"] == "bad"


def test_obs_tail_main_kind_filter_and_summary(tmp_path, capsys):
    ot = _obs_tail()
    p0 = _dump_replica(tmp_path, "r0", t0=0.0, n_completed=2, n_shed=2)
    assert ot.main([p0, "--kinds", "shed"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 2 and all("shed" in ln for ln in out)
    assert ot.main([p0, "--summary"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["kinds"] == {"completed": 2, "shed": 2}
