"""Plan-serving benchmark: plans/sec and latency, engine × probe × cache.

    PYTHONPATH=src python benchmarks/serve_bench.py [--quick] [...]

Compares ways of serving the same mixed workload (chain/star/cycle/
grid/clique/sparse topologies × cardinality regimes, Zipf-repeated
templates with random relabelings, Poisson arrivals):

* ``naive``   — one ``repro.core.dpconv.optimize`` call per request, no
  cache, no batching;
* ``service`` on the **host-loop engine** (``BatchPolicy(engine="host")``)
  — the PR-1 serving path: lockstep binary search with one device
  dispatch + host sync per feasibility round;
* ``service`` on the **fused engine** (``engine="fused"``, the default)
  — each chunk's whole solve (search, layered DP, the (min,+) C_cap
  pass, Alg. 2 extraction) as ONE compiled lattice program
  (``repro.core.engine`` over ``repro.core.lattice``); swept over
  micro-batch sizes, cache off/on, and both probe strategies: binary
  search (``gamma_batch=1``) and (G+1)-ary gamma probing
  (``gamma_batch=3`` — fewer while-loop rounds, same single dispatch).

Reports plans/sec, p50/p99 latency, cache stats, batch-lane solver
throughput, and the pass/dispatch/round accounting per configuration:
the host loop pays ~n device dispatches per batched solve, the fused
engine exactly 1 — for ``cost="max"`` AND ``cost="cap"`` chunks alike —
asserted against the engine's dispatch counter, along with zero
per-solve host extraction recursions.  Verifies **exact parity**: every
response produced by an exact route is bit-compared against a fresh
single-query ``optimize`` on the raw request, with DPconv references
forced onto the host-loop engine so the fused paths are checked against
the independent host implementations (optima bitwise, join-tree costs
identical; C_cap trees to f64 tolerance of the replayed sum order).

Four extra sections ride along:

* **replay** — the einsum contraction-log workload
  (``service.workload.make_einsum_workload``) served and
  parity-checked, so the gate also covers real-trace traffic
  (``--workload einsum`` makes it the main sweep's stream too);
* **reuse** — the incremental-planning row (always emitted): the einsum
  replay stream grown with model-planner traces
  (``workload.einsum_replay_pool``) served cold vs layer-cache-seeded
  with the plan cache off, reporting the layer-fragment hit rate, the
  p50 delta, and seeded-vs-cold **bitwise** parity booleans, plus a
  deadline-pressed pass asserting zero degraded plans served to
  exact-capable requests — ``scripts/smoke.sh`` gates on it;
* **out lane** — a sparse out-only stream served on the host-DPccp and
  the fused connectivity-masked C_out engines (``--cost out`` makes it
  the main sweep's mix too); the row records host-vs-fused plans/sec,
  dispatches- and rounds-per-solve, and its parity/one-dispatch/
  no-host-extraction fields are what ``scripts/smoke.sh`` gates on —
  it is emitted unconditionally, no flag drops it;
* **runtime** — a duplicate-heavy SLO-classed stream through the async
  deadline-aware scheduler (``repro.service.runtime``) on a
  ``VirtualClock``: per-class latency percentiles, shed / downgrade /
  coalesce rates, batch occupancy, and the fast-path evidence (cache
  hits overtaking the in-flight batched miss: hit p99 under the mean
  solve time, one fused dispatch preserved), every response
  bit-compared against the synchronous serve path — emitted
  unconditionally, ``scripts/smoke.sh`` gates on it;
* **obs** — rides the runtime row (always on): the same stream through
  an untraced runtime prices the span-tracing overhead per request, and
  the tracer/recorder tallies (zero unclosed/open spans, zero
  lane-shape mismatches, recorder incident counts exactly equal to the
  runtime's shed/downgrade/miss stats, per-phase p50/p95 from the
  ``trace.*`` histograms) are emitted into ``BENCH_serve.json`` for the
  ``scripts/smoke.sh`` telemetry gates;
* **faults** — the resilience row (always on): a seeded ~1% chaos mix
  (dispatch raise/hang/garbage, compile, cache, worker faults) plus a
  deterministic breaker-opening burst is injected into a VirtualClock
  runtime; every request must resolve bit-correct / certified-degraded
  / typed-error (``wrong_plans`` and ``unresolved`` are hard gates), at
  least one breaker lane must open AND close again, and the zero-fault
  overhead of the always-on layer (plan verification + watchdog
  bookkeeping) is priced against a runtime with both disabled —
  ``scripts/smoke.sh`` gates on all of it;
* **cluster** — the distributed-serving row (always on; ``--only
  cluster`` runs it alone for the CI multi-replica job): the same
  fresh out-cost stream served by 1 and by 4 REAL spawn-context
  replica processes (``repro.service.cluster.ReplicaCluster``) behind
  the asyncio line protocol, every response bit-compared against a
  local ``plan_one`` reference (cross-replica parity); the scaling
  gate is *modeled* like the lanes row — measured 1-replica service
  latencies partitioned by consistent-hash ring owner give
  ``total_s / makespan4`` (the single-core CI container serializes
  real processes, so wall-clock 4-replica rates are recorded but not
  gated); plus the shared plan-cache tier exercised for real
  (non-owner solves published to the ring owner, isomorph requests
  hitting cluster-wide), an ``obs_tail`` merge of the per-replica
  flight-recorder dumps, and a tenant-quota gate on a loopback
  replica (over-quota tenants shed/downgraded, the in-quota promised
  class missing zero deadlines, client admission ceilings pre-shedding
  after ``refresh_ceilings``) — ``scripts/smoke.sh`` gates on all of
  it;
* **cold start** — the executable cache is cleared and a sub-workload
  is served cold with and without ``PlanServer.prewarm``, measuring the
  cold-bucket p99 spike the prewarm satellite exists to kill.

Writes ``benchmarks/results/serve_bench.json`` (full rows) and a compact
cross-PR trajectory record ``BENCH_serve.json`` at the repo root
(``scripts/bench.sh`` drives this; ``scripts/smoke.sh`` calls it).

Exits non-zero if parity fails anywhere, if a fused solve takes more
than one device dispatch or any host extraction runs, if gamma probing
fails to reduce rounds-per-solve vs binary search, or (unless
``--no-target``) if the serving targets are missed: full fused path >=
2x plans/sec over the naive loop, fused >= 2x over the host-loop (PR-1)
serving path — judged on the cache-off end-to-end rate OR the
batch-lane solver rate, whichever clears it (the end-to-end ratio on
the shared CPU is noisy: Python canonicalization / routing overhead,
identical in both engines, dilutes it under load; both ratios are
recorded in BENCH_serve.json so regressions in either view stay
visible) — and prewarmed cold-start p99 below the un-prewarmed one.

A jit warm-up pass (the same shapes, separate server) runs before every
timed configuration so the numbers measure serving, not tracing.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

from repro.core import engine as engine_mod
from repro.core.dpconv import optimize
from repro.service import (PlanServer, RuntimeConfig, SLOClass,
                           VirtualClock, WorkloadSpec,
                           make_einsum_workload, make_workload)
from repro.service.batch import BatchPolicy
from repro.service.layercache import LayerCacheStats
from repro.service.workload import einsum_replay_pool

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "results")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (engine, gamma_batch) configurations swept by the service rows; the
# gamma-probe config runs cache-off only (its row exists to measure
# rounds-per-solve and parity, cache hits are engine-independent)
ENGINE_CONFIGS = (("host", 1), ("fused", 1), ("fused", 3))


def _route_method_for(resp) -> "tuple[str, dict]":
    return resp.route.method, resp.route.kw()


def check_parity(reqs, resps) -> "tuple[int, int]":
    """Bit-compare exact-route responses against single-query optimize.

    The naive reference deliberately runs OUTSIDE the service (raw request
    labels, no canonicalization, no batching): serving must not change
    answers.  DPconv references are forced onto the HOST-LOOP engine, so
    fused-engine responses — including the fused two-pass C_cap program —
    are checked bitwise against the independent per-round implementation,
    and the response's relabeled join tree must reproduce the optimum
    cost (bit-exactly for C_max; to f64 sum-order tolerance for C_cap's
    C_out, whose tree replays the sum in a different association).  GOO
    fallbacks are best-effort and approx is only checked for route
    equality, so both are skipped here.
    """
    checked = mismatched = 0
    for req, resp in zip(reqs, resps):
        method, kw = _route_method_for(resp)
        if method in ("goo", "approx"):
            continue
        if req.cost == "cap":
            # pin the reference's BOTH passes to the host pipeline —
            # otherwise cap routes would check fused against itself
            ref = optimize(req.q, req.card, cost="cap", engine="host")
        else:
            if method == "dpconv" and req.cost == "max":
                kw = {**kw, "engine": "host"}
            ref = optimize(req.q, req.card, cost=req.cost, method=method,
                           **kw)
        checked += 1
        bad = float(ref.cost) != float(resp.cost)
        if (not bad and req.cost == "max" and method == "dpconv"
                and resp.tree is not None):
            # the relabeled tree must realize the optimum bit-exactly
            bad = float(resp.tree.cost_max(req.card)) != float(resp.cost)
        if (not bad and req.cost == "cap" and resp.tree is not None):
            got = float(resp.tree.cost_out(req.card))
            bad = abs(got - float(resp.cost)) > \
                1e-9 * max(1.0, abs(float(resp.cost)))
        if (not bad and req.cost == "out" and method == "dpccp"
                and resp.tree is not None):
            # the relabeled tree must replay the optimum (f64 sum-order
            # tolerance) AND stay inside the DPccp search space: every
            # internal node connected in the *request's* labeling
            got = float(resp.tree.cost_out(req.card))
            bad = (abs(got - float(resp.cost))
                   > 1e-9 * max(1.0, abs(float(resp.cost)))
                   or not all(req.q.is_connected(m)
                              for m in resp.tree.internal_masks()))
        if bad:
            mismatched += 1
            print(f"  PARITY MISMATCH req={req.req_id} cost={req.cost} "
                  f"method={method}: service={resp.cost!r} "
                  f"single={ref.cost!r}", file=sys.stderr)
    return checked, mismatched


def _naive_kw(cost: str) -> dict:
    # exact C_out via the polynomial embedding needs small integral
    # cardinalities; the practical single-query exact default is DPsub.
    # DPconv routes pin engine="host": the naive row is the PRE-FUSED
    # status quo, comparable against PR-1's recorded numbers (the fused
    # engine's single-query win shows up in the service rows)
    return {"method": "dpsub"} if cost in ("out", "smj") \
        else {"engine": "host"}


def run_naive(reqs, passes: int = 2) -> dict:
    """One-query-at-a-time loop, no cache, host-loop engine — the
    pre-service (PR-1) status quo.  Runs ``passes`` times and reports the
    fastest (noise floor)."""
    best_wall = None
    lat = []
    for p in range(passes):
        lat = []
        t_all = time.perf_counter()
        clock = 0.0
        for req in reqs:
            clock = max(clock, req.arrival)
            t0 = time.perf_counter()
            optimize(req.q, req.card, cost=req.cost,
                     **_naive_kw(req.cost))
            dt = time.perf_counter() - t0
            clock += dt
            lat.append(clock - req.arrival)
        wall = time.perf_counter() - t_all
        best_wall = wall if best_wall is None else min(best_wall, wall)
    lat = np.asarray(lat)
    return {"config": "naive", "plans_per_s": len(reqs) / best_wall,
            "wall_s": best_wall,
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3}


def _make_server(batch_size: int, cache: bool, engine: str = "fused",
                 gamma: int = 1) -> PlanServer:
    # layer cache off: these rows price the engine and the whole-plan
    # cache with their historical semantics (cold rows really solve cold
    # — binary rounds ~log2(C), no seeded-variant compiles mid-row); the
    # fragment-reuse tier is measured by its own `reuse` row
    return PlanServer(max_batch=batch_size, cache_capacity=8192,
                      enable_cache=cache, enable_layer_cache=False,
                      batch_policy=BatchPolicy(max_batch=batch_size,
                                               engine=engine,
                                               gamma_batch=gamma))


def _dpconv_pass_stats(resps) -> dict:
    """Mean feasibility passes / device dispatches per *batched solve* on
    the DPconv batch lane — C_max and C_cap chunks alike (cache misses
    only).  Every response in a chunk copies its solve's counters, so
    each is weighted by 1/chunk — the result is a true per-solve mean,
    not a chunk-size-weighted one."""
    passes, disp, weights = [], [], []
    cap_disp = []
    for r in resps:
        if (r.route.method == "dpconv" and r.route.lane == "batch"
                and not r.cache_hit
                and r.meta.get("passes") is not None):
            w = 1.0 / max(int(r.meta.get("chunk", 1)), 1)
            weights.append(w)
            passes.append(r.meta["passes"] * w)
            if r.meta.get("dispatches") is not None:
                disp.append(r.meta["dispatches"] * w)
                if r.route.cost == "cap":
                    cap_disp.append(r.meta["dispatches"])
    out = {"queries_on_lane": len(passes),
           "solves_on_lane": round(sum(weights), 2)}
    if weights:
        out["passes_per_solve"] = float(sum(passes) / sum(weights))
    if disp:
        out["dispatches_per_solve"] = float(sum(disp) / sum(weights))
    if cap_disp:
        out["cap_queries"] = len(cap_disp)
        out["cap_max_dispatches"] = int(max(cap_disp))
    return out


def run_service(reqs, batch_size: int, cache: bool, engine: str = "fused",
                gamma: int = 1,
                passes: int = 3) -> "tuple[dict, list]":
    """Throughput from closed-loop passes (back-to-back micro-batches —
    apples-to-apples with the naive loop's pure-compute rate).  The same
    server serves the recurring stream ``passes`` times: the first pass
    is the cold cache-fill, later passes are the steady state a
    production plan server lives in; the best pass is reported (and the
    cold pass kept in the row).  Latency percentiles come from a fresh
    cold server honoring the workload's Poisson arrivals."""
    engine_mod.reset_stats()
    srv = _make_server(batch_size, cache, engine, gamma)
    resps = None
    pass_rates = []
    for p in range(passes):
        served0, wall0 = srv.stats.served, srv.stats.wall_s
        rs, stats = srv.serve(list(reqs), closed_loop=True)
        dw = stats.wall_s - wall0
        pass_rates.append((stats.served - served0) / dw if dw > 0
                          else 0.0)
        if resps is None:
            resps = rs
    # snapshot the engine counters NOW: they must describe the timed
    # throughput configuration, not the separate latency server below
    est = dict(engine_mod.stats().as_dict())
    srv_lat = _make_server(batch_size, cache, engine, gamma)
    _, lat_stats = srv_lat.serve(list(reqs), closed_loop=False)
    cs = srv.cache.stats
    solver = srv.solver.total_solved / srv.solver.total_solve_s \
        if srv.solver.total_solve_s > 0 else 0.0
    probe = "binary" if gamma == 1 else f"gamma{gamma}"
    row = {"config": f"service/engine={engine}/probe={probe}/"
                     f"batch={batch_size}/"
                     f"cache={'on' if cache else 'off'}",
           "engine": engine,
           "probe": probe,
           "plans_per_s": max(pass_rates),
           "cold_plans_per_s": pass_rates[0],
           "solver_plans_per_s": solver,
           "p50_ms": lat_stats.latency.percentile(50) * 1e3,
           "p99_ms": lat_stats.latency.percentile(99) * 1e3,
           "cache": cs.as_dict(),
           "routes": dict(srv.router.decisions),
           "deadline_fallbacks": srv.stats.deadline_fallbacks,
           "batches": srv.stats.batches,
           "dpconv_lane": _dpconv_pass_stats(resps),
           "engine_counters": est}
    if engine == "fused" and est["solves"]:
        # the acceptance invariants: a fused batched solve is ONE device
        # execution (dispatches counted at the engine's exe call site,
        # max and cap chunks alike), and tree extraction never falls
        # back to a host recursion — checked by main() alongside parity,
        # not skippable
        row["fused_one_dispatch"] = bool(
            est["dispatches"] == est["solves"])
        row["fused_no_host_extraction"] = est["host_extractions"] == 0
        row["dpconv_lane"]["fused_rounds_per_solve"] = \
            est["rounds"] / est["solves"]
    return row, resps


def warmup(reqs, batch_sizes) -> None:
    """Compile every shape the timed runs can hit: all power-of-two batch
    buckets per ``n`` on the batched lane (both engines, both probe
    modes) plus each single-query route.  Candidate tables always pad to
    the canonical per-``n`` bucket, so a full serve pass per
    configuration covers the real chunkings; the host jit caches key on
    gate shapes, covered by the same passes."""
    from repro.core.dpconv import optimize_batch

    by_n: dict = {}
    for r in reqs:
        by_n.setdefault(r.q.n, r)
    for n, r in sorted(by_n.items()):
        b = 1            # b = 1 compiles the single-query (chunk-1) tier
        while b <= max(batch_sizes):
            for eng, gamma in ENGINE_CONFIGS:
                optimize_batch([r.q] * b, [r.card] * b, cost="max",
                               engine=eng, gamma_batch=gamma)
            b *= 2
    for eng, gamma in ENGINE_CONFIGS:
        srv = _make_server(max(batch_sizes), cache=False, engine=eng,
                           gamma=gamma)
        srv.serve(list(reqs), closed_loop=True)
        if eng == "fused":
            # arrival-honoring batching chunks differently (other batch
            # buckets for the fused executables) — warm those too so
            # latency rows measure serving, not compiles
            srv2 = _make_server(max(batch_sizes), cache=False,
                                engine=eng, gamma=gamma)
            srv2.serve(list(reqs), closed_loop=False)


def run_runtime_sweep(spec_seed: int, n_requests: int,
                      batch_size: int) -> "tuple[dict, dict, int, int]":
    """The async-runtime row — emitted unconditionally, the smoke gate
    reads it.  A duplicate-heavy SLO-classed stream is served through
    ``ServingRuntime`` on a ``VirtualClock`` honoring Poisson arrivals
    (solve durations from the wall clock), and every non-downgraded
    response is bit-compared against the synchronous ``serve`` path on
    the SLO-free copy of the same workload: scheduling must not change
    answers.  The row records per-SLO-class latency percentiles, shed /
    downgrade / coalesce counters, batch occupancy, and the fast-path
    evidence the acceptance criterion names: cache-hit p99 under the
    mean in-flight batched-miss solve time, with one fused dispatch per
    batched solve preserved.
    """
    # rate >> 1/solve-time: duplicates land while their canonical form
    # is still queued or in flight, so join-on-completion (not just the
    # cache) is exercised — the coalesce-rate smoke gate reads this row
    spec = WorkloadSpec(
        n_requests=n_requests, seed=spec_seed, n_range=(5, 8),
        pool_size=6, fresh_frac=0.0, relabel_frac=0.8, zipf_a=2.0,
        rate=20000.0,
        cost_mix=(("max", 0.7), ("cap", 0.2), ("out", 0.1)),
        slo_mix=(("interactive", 0.4), ("standard", 0.4),
                 ("batch", 0.2)))
    reqs = make_workload(spec)
    slo_free = [dataclasses.replace(r, slo=None) for r in reqs]
    # sync reference: the same canonical answers, no deadline machinery
    sync_srv = _make_server(batch_size, cache=True)
    sync_resps, _ = sync_srv.serve(list(slo_free), closed_loop=True)
    by_id = {r.req_id: r for r in sync_resps}
    # warm the executable/jit caches for the runtime server's shapes
    warm = _make_server(batch_size, cache=False)
    warm.serve(list(slo_free), closed_loop=True)

    engine_mod.reset_stats()
    srv = _make_server(batch_size, cache=True)
    clk = VirtualClock()
    cfg = RuntimeConfig(
        max_batch=batch_size,
        slo_classes={
            "interactive": SLOClass("interactive", 1.0),
            "standard": SLOClass("standard", 5.0),
            "batch": SLOClass("batch", None),
        })
    rt = srv.make_runtime(clock=clk, config=cfg)
    tickets = []
    t_traced = time.perf_counter()
    for r in sorted(reqs, key=lambda r: r.arrival):
        rt.run_until(r.arrival)
        tickets.append(rt.submit(r))
    rt.drain()
    t_traced = time.perf_counter() - t_traced
    est = engine_mod.stats().as_dict()

    # --- obs row (always on): the same stream replayed through traced
    # and UNTRACED runtimes on fresh servers (same warm jit/executable
    # caches) prices the tracer's overhead; the tracer/recorder tallies
    # of the FIRST traced run above are the telemetry-integrity
    # evidence scripts/smoke.sh gates on.  The whole loop is sub-100ms,
    # so a single comparison is noise-dominated on a shared CPU: each
    # mode is timed as the min over several interleaved replays with GC
    # paused, the noise-robust estimate of the true per-mode floor.
    def _replay(trace: bool) -> float:
        s = _make_server(batch_size, cache=True)
        r_ = s.make_runtime(clock=VirtualClock(),
                            config=dataclasses.replace(cfg, trace=trace))
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            for r in sorted(reqs, key=lambda r: r.arrival):
                r_.run_until(r.arrival)
                r_.submit(r)
            r_.drain()
            return time.perf_counter() - t0
        finally:
            gc.enable()

    _replay(True), _replay(False)          # first-touch warmup, untimed
    # alternate which mode runs first in each pair: on a 1-core host the
    # scheduler / frequency state penalises whichever replay goes first,
    # and a fixed order folds that bias straight into the traced-minus-
    # plain delta
    ts, ps = [], []
    for i in range(10):
        if i % 2 == 0:
            ts.append(_replay(True)), ps.append(_replay(False))
        else:
            ps.append(_replay(False)), ts.append(_replay(True))
    t_traced = min(ts)
    t_plain = min(ps)
    trs = rt.tracer.stats()
    rec = rt.recorder.snapshot()
    rts_ = rt.stats
    overhead = max(0.0, (t_traced - t_plain) / t_plain) if t_plain > 0 \
        else 0.0
    from repro.obs.export import span_phase_summary
    obs_row = {
        "config": "obs/runtime",
        "traced_wall_s": round(t_traced, 4),
        "untraced_wall_s": round(t_plain, 4),
        "overhead_frac": round(overhead, 4),
        "span_overhead_us_per_request": round(
            max(0.0, t_traced - t_plain) / max(len(reqs), 1) * 1e6, 2),
        "requests_traced": trs["requests"],
        "spans_per_request": round(
            trs["spans_opened"] / max(trs["requests"], 1), 3),
        "unclosed_spans": trs["unclosed_spans"],
        "open_spans": trs["open_spans"],
        "lane_shape_mismatches": trs["lane_shape_mismatches"],
        "phases": span_phase_summary(srv.registry),
        "recorder": dict(rec["counts"]),
        "recorder_shed_exact": bool(
            rec["counts"]["shed"] == rts_.shed + rts_.shed_backpressure),
        "recorder_miss_exact": bool(
            rec["counts"]["deadline_miss"] == rts_.deadline_misses),
        "recorder_downgrade_exact": bool(
            rec["counts"]["downgraded"] == rts_.downgraded),
    }

    checked = bad = 0
    for t in tickets:
        if t.refused or t.downgraded or t.response is None:
            continue
        ref = by_id[t.request.req_id]
        if ref.route.method in ("goo", "approx"):
            continue
        checked += 1
        mismatch = float(t.response.cost) != float(ref.cost)
        if not mismatch and (t.response.tree is None) != (ref.tree is None):
            mismatch = True
        if not mismatch and ref.tree is not None \
                and repr(t.response.tree) != repr(ref.tree):
            mismatch = True
        if mismatch:
            bad += 1
            print(f"  RUNTIME PARITY MISMATCH req={t.request.req_id}: "
                  f"runtime={t.response.cost!r} sync={ref.cost!r}",
                  file=sys.stderr)

    rts = rt.stats
    row = {"config": f"runtime/batch={batch_size}/cache=on",
           **rts.as_dict(),
           "parity_checked": checked,
           "parity_mismatches": bad,
           "one_dispatch": bool(est["solves"] == 0
                                or est["dispatches"] == est["solves"]),
           "host_extractions": est["host_extractions"],
           "cache": srv.cache.stats.as_dict()}
    return row, obs_row, checked, bad


def run_faults_row(spec_seed: int, n_requests: int,
                   batch_size: int) -> dict:
    """The resilience row — emitted unconditionally, the smoke gate
    reads it.  Two measurements:

    1. **chaos classification** — the ~1% chaos mix (every seam:
       dispatch raise/hang/garbage, compile, cache, worker) plus a
       deterministic 2-failure burst injected into a VirtualClock
       runtime with constant injected durations, so the fault schedule
       replays bit-for-bit.  Every ticket must resolve as a bit-correct
       exact plan (vs the fault-free sync serve), a certified degraded
       plan, or a typed error — ``wrong_plans`` and ``unresolved`` are
       hard smoke gates.  The burst (with ``failure_threshold=1`` and a
       tiny cooldown) forces at least one breaker open -> half-open ->
       closed round trip per run.
    2. **zero-fault overhead** — the same stream through the default
       runtime (verification + watchdog on, no injector) vs a runtime
       with the resilience layer's per-dispatch work disabled
       (``verify_plans=False, watchdog_factor=0``), min over five
       interleaved replays: what the always-on layer costs when nothing
       fails.
    """
    from repro.service import faults

    spec = WorkloadSpec(n_requests=n_requests, seed=spec_seed,
                        n_range=(5, 8), pool_size=6, rate=2000.0)
    reqs = make_workload(spec)
    # fault-free ground truth (and jit/executable warmup for the shapes)
    ref_srv = _make_server(batch_size, cache=True)
    ref_resps, _ = ref_srv.serve(list(reqs), closed_loop=True)
    ref = {r.req_id: r for r in ref_resps}

    # ---- 1. chaos run: deterministic virtual time + injected durations
    chaos = faults.FaultPlan.chaos(seed=spec_seed, rate=0.01)
    plan = dataclasses.replace(chaos, specs=chaos.specs + (
        # deterministic burst: two consecutive dispatch failures mid-
        # stream guarantee a breaker opens even at the 1% chaos rate
        faults.FaultSpec("dispatch", "raise", rate=1.0, after=10,
                         max_fires=2),))
    dur = {"admit": 0.0, "solve": 0.002, "single": 0.001}
    srv = _make_server(batch_size, cache=False)  # every request solves
    cfg = RuntimeConfig(
        max_batch=batch_size,
        breaker=faults.BreakerConfig(failure_threshold=1,
                                     cooldown_s=0.002))
    rt = srv.make_runtime(clock=VirtualClock(), config=cfg,
                          duration_fn=lambda kind, info: dur[kind],
                          injector=faults.FaultInjector(plan))
    tickets = []
    for r in sorted(reqs, key=lambda r: r.arrival):
        rt.run_until(r.arrival)
        tickets.append(rt.submit(r))
    rt.drain()
    unresolved = sum(not t.done for t in tickets)
    recovered = sum(t.done and t.faulted and t.status == "exact"
                    for t in tickets)
    degraded = sum(t.status == "degraded" for t in tickets)
    errors = sum(t.status == "error" for t in tickets)
    wrong = 0
    for t in tickets:
        if t.status != "exact" or t.response is None:
            continue
        r0 = ref[t.request.req_id]
        if r0.status == "exact" \
                and float(t.response.cost) != float(r0.cost):
            wrong += 1
            print(f"  FAULTS WRONG PLAN req={t.request.req_id}: "
                  f"chaos={t.response.cost!r} ref={r0.cost!r}",
                  file=sys.stderr)
    fstats = rt.fstats.as_dict()
    brk = rt.breakers.snapshot()
    inj = rt.injector.snapshot()
    rt.close()

    # ---- 2. zero-fault overhead: resilience on vs off, interleaved
    def _replay(base: bool) -> float:
        s = _make_server(batch_size, cache=True)
        c = RuntimeConfig(max_batch=batch_size, verify_plans=not base,
                          watchdog_factor=0.0 if base else 8.0)
        r_ = s.make_runtime(clock=VirtualClock(), config=c)
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            for r in sorted(reqs, key=lambda r: r.arrival):
                r_.run_until(r.arrival)
                r_.submit(r)
            r_.drain()
            return time.perf_counter() - t0
        finally:
            gc.enable()

    _replay(False), _replay(True)          # first-touch warmup, untimed
    pairs = [(_replay(False), _replay(True)) for _ in range(5)]
    t_full = min(t for t, _ in pairs)
    t_base = min(b for _, b in pairs)
    overhead = max(0.0, (t_full - t_base) / t_base) if t_base > 0 else 0.0
    return {
        "config": f"faults/chaos=1%/batch={batch_size}/cache=off",
        "n_requests": len(reqs),
        "faults_armed": inj["armed"],
        "faults_fired": inj["fired"],
        "unresolved": unresolved,
        "recovered": recovered,
        "recovered_frac": round(recovered / len(reqs), 4),
        "degraded": degraded,
        "degraded_frac": round(degraded / len(reqs), 4),
        "errors": errors,
        "error_frac": round(errors / len(reqs), 4),
        "wrong_plans": wrong,
        "fstats": fstats,
        "breaker_opens": brk["opens"],
        "breaker_closes": brk["closes"],
        "breaker_open_lanes": brk["open_lanes"],
        "overhead_wall_s": round(t_full, 4),
        "baseline_wall_s": round(t_base, 4),
        "overhead_frac": round(overhead, 4),
        "overhead_us_per_request": round(
            max(0.0, t_full - t_base) / max(len(reqs), 1) * 1e6, 2),
    }


def _sharded_parity_section() -> dict:
    """Sharded-solve bit-parity booleans for the lanes row (device count
    permitting).  On a one-device host the solve mesh has nothing to
    split over, so the section records that and skips; the CI
    forced-8-device job runs the full set including the n = 15 C_cap
    case above the old single-device fused ceiling."""
    import jax
    from repro.core.ccap import ccap
    from repro.core.dpconv_max import dpconv_max
    from repro.core.querygraph import chain, make_cardinalities

    ndev = len(jax.devices())
    sec = {"devices": ndev}
    if ndev < 2:
        sec["skipped"] = ("single device: the solve mesh has nothing "
                          "to split over")
        return sec
    D = 4 if ndev >= 4 else 2
    sec["shards"] = D
    sec["fused_cap_max_n_lifted"] = engine_mod.sharded_ceiling(13, D)
    q = chain(7)
    card = make_cardinalities(q, seed=0)
    mx_s = dpconv_max(q, card, engine="fused", shards=D)
    mx_h = dpconv_max(q, card, engine="host")
    sec["max_parity"] = bool(mx_s.optimum == mx_h.optimum
                             and repr(mx_s.tree) == repr(mx_h.tree))
    cp_s = ccap(q, card, engine="fused", shards=D)
    cp_h = ccap(q, card, engine="host")
    sec["cap_parity"] = bool(cp_s.gamma == cp_h.gamma
                             and cp_s.cout == cp_h.cout
                             and repr(cp_s.tree) == repr(cp_h.tree))
    o_s = optimize(q, card, cost="out", method="dpccp", engine="fused",
                   shards=D)
    o_h = optimize(q, card, cost="out", method="dpccp", engine="host")
    sec["out_parity"] = bool(float(o_s.cost) == float(o_h.cost)
                             and repr(o_s.tree) == repr(o_h.tree))
    if ndev >= 4:
        # the scale-out acceptance case: n = 15 C_cap on a 4-way mesh —
        # above the old single-device fused ceiling (13) — bit-identical
        # to the host pipeline.  The AOT compile dominates the sharded
        # wall time; both times are recorded for the trajectory.
        q15 = chain(15)
        c15 = make_cardinalities(q15, seed=0)
        t0 = time.perf_counter()
        s15 = ccap(q15, c15, engine="fused", shards=4)
        sec["cap_n15_sharded_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        h15 = ccap(q15, c15, engine="host")
        sec["cap_n15_host_s"] = round(time.perf_counter() - t0, 2)
        sec["cap_n15_parity"] = bool(s15.gamma == h15.gamma
                                     and s15.cout == h15.cout
                                     and repr(s15.tree) == repr(h15.tree))
    return sec


def run_lanes_row() -> "tuple[dict, int]":
    """The N-lane scale-out row — emitted unconditionally, the smoke
    gate reads it.  Two measurements:

    1. **modeled scheduling throughput** — six executable buckets
       ((n, cost) pairs, one full micro-batch each, distinct
       cardinalities so nothing caches or coalesces) served through a
       1-lane and a 4-lane runtime on ``VirtualClock`` with constant
       injected solve durations.  Virtual time prices only the
       *scheduling* layer — lane placement, serial-executor occupancy —
       so the aggregate plans/sec ratio is the deterministic scale-out
       factor of the lane scheduler itself (>= 1.5x at 4 lanes is the
       acceptance gate; the bucket spread puts the ideal at 3x), free
       of shared-CPU noise.  Every response is bit-compared across lane
       counts: lanes change WHERE a solve runs, never WHAT it computes.
    2. **sharded-solve parity** — ``_sharded_parity_section``:
       bitwise fused-vs-host booleans per cost program on the solve
       mesh, incl. the n = 15 above-ceiling C_cap case (>= 4 devices).
    """
    from repro.core.querygraph import chain, make_cardinalities, star
    from repro.service.server import PlanRequest

    dur = {"admit": 0.0, "solve": 0.01, "single": 0.005}
    stream = []
    rid = 0
    for n in (6, 7, 8):
        for cost, topo in (("max", chain), ("cap", star)):
            q = topo(n)
            for _ in range(8):
                stream.append(PlanRequest(
                    q=q, card=make_cardinalities(q, seed=1000 + rid),
                    cost=cost, req_id=rid))
                rid += 1

    def run(lanes):
        srv = _make_server(8, cache=False)
        rt = srv.make_runtime(
            clock=VirtualClock(),
            config=RuntimeConfig(max_batch=8, lanes=lanes),
            duration_fn=lambda kind, info: dur[kind])
        tickets = [rt.submit(r) for r in stream]
        rt.drain()
        makespan = max(t.completed_at for t in tickets)
        return rt, tickets, (len(tickets) / makespan if makespan > 0
                             else 0.0)

    rt1, t1, rate1 = run(1)
    rt4, t4, rate4 = run(4)
    mism = 0
    for a, b in zip(t1, t4):
        if (a.response is None or b.response is None
                or float(a.response.cost) != float(b.response.cost)
                or repr(a.response.tree) != repr(b.response.tree)):
            mism += 1
            print(f"  LANES PARITY MISMATCH req={a.request.req_id}: "
                  f"lanes1={getattr(a.response, 'cost', None)!r} "
                  f"lanes4={getattr(b.response, 'cost', None)!r}",
                  file=sys.stderr)
    row = {
        "config": "lanes/modeled/1v4",
        "n_requests": len(stream),
        "modeled_plans_per_s": {"lanes1": round(rate1, 1),
                                "lanes4": round(rate4, 1)},
        "scaling_x": round(rate4 / rate1, 3) if rate1 > 0 else 0.0,
        "lane_dispatches": {str(k): v for k, v in
                            sorted(rt4.stats.lane_dispatches.items())},
        "steals": rt4.stats.steals,
        "hedges": rt4.stats.hedges,
        "parity_mismatches": mism,
        "sharded": _sharded_parity_section(),
    }
    return row, mism


def run_cold_start(reqs, batch_size: int, gamma: int = 1) -> dict:
    """The prewarm satellite's measurement: serve a cold sub-workload
    (executable cache cleared) with and without ``PlanServer.prewarm``.
    Without it, the first seconds of serving pay the AOT compiles inline
    — the cold-bucket p99 spike; with it, startup pays them before
    traffic arrives and the served latencies stay flat."""
    ns = sorted({r.q.n for r in reqs})
    out = {}
    for prewarm in (False, True):
        engine_mod.clear_executable_cache()
        srv = _make_server(batch_size, cache=False, engine="fused",
                           gamma=gamma)
        if prewarm:
            pw = srv.prewarm(ns)
            out["prewarm_compiled"] = pw["compiled"]
            out["prewarm_s"] = round(pw["seconds"], 2)
        _, stats = srv.serve(list(reqs), closed_loop=False)
        key = "prewarm" if prewarm else "no_prewarm"
        out[f"p99_ms_{key}"] = stats.latency.percentile(99) * 1e3
        out[f"p50_ms_{key}"] = stats.latency.percentile(50) * 1e3
    return out


def run_replay(spec_seed: int, n_requests: int,
               batch_size: int) -> "tuple[dict, int, int]":
    """The einsum contraction-log replay lane: real-trace templates
    through the full serving path, parity-checked like the main sweep."""
    spec = WorkloadSpec(n_requests=n_requests, seed=spec_seed)
    reqs = make_einsum_workload(spec)
    warm = _make_server(batch_size, cache=False)
    warm.serve(list(reqs), closed_loop=True)
    srv = _make_server(batch_size, cache=True)
    t0 = time.perf_counter()
    resps, _ = srv.serve(list(reqs), closed_loop=True)
    wall = time.perf_counter() - t0
    checked, bad = check_parity(reqs, resps)
    row = {"config": f"replay/einsum/batch={batch_size}/cache=on",
           "plans_per_s": len(reqs) / wall if wall > 0 else 0.0,
           "n_requests": len(reqs),
           "cache": srv.cache.stats.as_dict(),
           "parity_checked": checked}
    return row, checked, bad


def run_reuse_row(spec_seed: int, n_requests: int,
                  batch_size: int) -> "tuple[dict, int]":
    """The incremental-planning reuse row — always emitted.

    Two passes over the SAME einsum replay stream — the pool grown with
    traces logged from the ``train/steps`` model planners
    (``workload.einsum_replay_pool``) — with the plan cache OFF in both
    so every request actually solves:

    * ``cold``   — layer-fragment cache disabled: the no-reuse baseline;
    * ``seeded`` — layer-fragment cache enabled AND pre-populated by the
      warm pass (a replica that has been serving the template family for
      a while — the steady state the tier exists for): template repeats
      warm-start the C_max/C_cap search bracket from the cached optimum,
      shared sub-networks seed already-solved C_out value layers.

    Layer seeds are pure perf hints, so the passes must agree **bitwise**
    on every cost and join tree (``parity_ok`` — the incremental-planning
    acceptance gate, enforced by ``scripts/smoke.sh``).  A third pass
    with the plan cache ON and deadline pressure replays the
    degraded-plan poisoning fix at bench scale: a best-effort (GOO) plan
    cached under the primary key must never be served to an
    exact-capable request (``degraded_to_exactcap == 0``).
    """
    spec = WorkloadSpec(n_requests=n_requests, seed=spec_seed,
                        cost_mix=(("max", 0.55), ("out", 0.30),
                                  ("cap", 0.15)),
                        relabel_frac=0.4)
    reqs = make_einsum_workload(spec, contractions=einsum_replay_pool())

    def make(layer_cache: bool, plan_cache: bool = False) -> PlanServer:
        return PlanServer(max_batch=batch_size, enable_cache=plan_cache,
                          enable_layer_cache=layer_cache,
                          batch_policy=BatchPolicy(max_batch=batch_size,
                                                   engine="fused"))

    # warm both variants: the seeded pass compiles the seeded program
    # cards (4-input max search, seeded out replay) on top of the cold
    # ones, and timing must measure serving, not tracing.  The warm
    # pass's populated fragment store carries into the timed seeded
    # servers (fresh counters) — the timed passes price steady-state
    # reuse, not the one-time fill of an empty store.
    warm_layers = None
    for lc in (False, True):
        s = make(lc)
        s.serve(list(reqs), closed_loop=True)
        if lc:
            warm_layers = s.layers
    # steady-state warm: a FULL store seeds far more (bucket, cost)
    # combinations than the fill pass did while the store was still
    # growing — re-serve both pacing modes over the populated store so
    # every seeded executable bucket compiles outside the timed region
    for closed in (True, False):
        s = make(True)
        s.layers = warm_layers
        s.serve(list(reqs), closed_loop=closed)

    def make_timed(lc: bool) -> PlanServer:
        s = make(lc)
        if lc:
            s.layers = warm_layers
        return s

    # fresh counters over the warm store: the row's hit/seed tallies
    # cover exactly the two timed seeded passes below
    warm_layers.stats = LayerCacheStats()
    runs = {}
    for name, lc in (("cold", False), ("seeded", True)):
        srv = make_timed(lc)
        t0 = time.perf_counter()
        resps, _ = srv.serve(list(reqs), closed_loop=True)
        wall = time.perf_counter() - t0
        _, lat = make_timed(lc).serve(list(reqs), closed_loop=False)
        runs[name] = (srv, resps, wall, lat)

    cold_r, seeded_r = runs["cold"][1], runs["seeded"][1]
    mismatches = sum(
        1 for c, s in zip(cold_r, seeded_r)
        if c.cost != s.cost or repr(c.tree) != repr(s.tree))
    ls = runs["seeded"][0].layers.stats
    probes = (ls.search_hits + ls.search_misses
              + ls.value_hits + ls.value_misses)
    hit_rate = ((ls.search_hits + ls.value_hits) / probes
                if probes else 0.0)
    p50_cold = runs["cold"][3].latency.percentile(50) * 1e3
    p50_seeded = runs["seeded"][3].latency.percentile(50) * 1e3

    # degraded-poisoning replay: deadline-pressed repeats force GOO
    # plans into the shared plan cache; exact-capable repeats of the
    # same templates must miss through and re-solve exactly
    spec_d = dataclasses.replace(spec, seed=spec_seed + 1,
                                 budget_frac=0.3, budget_s=1e-6)
    reqs_d = make_einsum_workload(spec_d,
                                  contractions=einsum_replay_pool())
    srv_d = make(True, plan_cache=True)
    resps_d, _ = srv_d.serve(list(reqs_d), closed_loop=True)
    degraded_served = sum(r.status == "degraded" for r in resps_d)
    degraded_to_exactcap = sum(
        1 for req, r in zip(reqs_d, resps_d)
        if req.latency_budget is None and r.status == "degraded")

    row = {"config": f"reuse/einsum-model-trace/batch={batch_size}/"
                     f"plancache=off",
           "n_requests": len(reqs),
           "layer_hit_rate": round(hit_rate, 4),
           "layer_cache": ls.as_dict(),
           "seeded_solves": ls.seeded_solves,
           "plans_per_s_cold": len(reqs) / runs["cold"][2],
           "plans_per_s_seeded": len(reqs) / runs["seeded"][2],
           "p50_ms_cold": p50_cold,
           "p50_ms_seeded": p50_seeded,
           "p50_delta_ms": p50_cold - p50_seeded,
           "parity_checked": len(reqs),
           "parity_mismatches": mismatches,
           "parity_ok": mismatches == 0,
           "degraded_served": degraded_served,
           "degraded_to_exactcap": degraded_to_exactcap,
           "plan_cache_degraded_skips":
               srv_d.cache.stats.degraded_skips}
    return row, mismatches


def run_out_sweep(spec_seed: int, n_requests: int,
                  batch_size: int) -> "tuple[dict, int, int]":
    """The connected-C_out lane sweep — host DPccp enumeration vs the
    fused connectivity-masked lattice program, on a sparse out-only
    workload inside the fused window.  Emitted unconditionally: the
    smoke gate asserts this row's parity/dispatch/extraction fields, so
    no flag combination may drop it.
    """
    spec = WorkloadSpec(n_requests=n_requests, seed=spec_seed,
                        n_range=(6, 9), cost_mix=(("out", 1.0),),
                        topologies=("chain", "star", "cycle", "sparse",
                                    "grid"))
    reqs = make_workload(spec)
    row = {"config": f"out_sweep/batch={batch_size}/cache=off"}
    checked_total = bad_total = 0
    for eng in ("host", "fused"):
        warm = _make_server(batch_size, cache=False, engine=eng)
        warm.serve(list(reqs), closed_loop=True)
        engine_mod.reset_stats()
        srv = _make_server(batch_size, cache=False, engine=eng)
        t0 = time.perf_counter()
        resps, _ = srv.serve(list(reqs), closed_loop=True)
        wall = time.perf_counter() - t0
        checked, bad = check_parity(reqs, resps)
        checked_total += checked
        bad_total += bad
        est = engine_mod.stats().as_dict()
        row[f"{eng}_plans_per_s"] = len(reqs) / wall if wall > 0 else 0.0
        if eng == "fused":
            disp = [r.meta["dispatches"] for r in resps
                    if r.route.method == "dpccp" and not r.cache_hit
                    and r.meta.get("dispatches") is not None]
            row["queries_on_lane"] = len(disp)
            row["fused_solves"] = est["solves"]
            row["max_dispatches_per_solve"] = max(disp) if disp else 0
            row["dispatches_per_solve"] = (est["dispatches"]
                                           / max(est["solves"], 1))
            # the (min,+) layer sweep probes nothing: zero search rounds
            # per solve, by construction — recorded so a future probing
            # variant shows up in the trajectory
            row["rounds_per_solve"] = (est["rounds"]
                                       / max(est["solves"], 1))
            row["host_extractions"] = est["host_extractions"]
            row["routes"] = dict(srv.router.decisions)
    row["parity_checked"] = checked_total
    row["parity_mismatches"] = bad_total
    row["speedup"] = (row["fused_plans_per_s"] / row["host_plans_per_s"]
                      if row["host_plans_per_s"] > 0 else 0.0)
    return row, checked_total, bad_total


def _relabel_query(q, card, rng):
    """A random isomorph of ``(q, card)``: permuted relation labels, same
    canonical key — what the shared-cache tier must hit cluster-wide."""
    from repro.core.querygraph import permute_card, relabel

    p = [int(x) for x in rng.permutation(q.n)]
    return relabel(q, p), permute_card(np.asarray(card, np.float64),
                                       q.n, p)


def run_cluster_row(quick: bool, seed: int) -> "tuple[dict, int]":
    """The distributed-serving row — always emitted, ``scripts/smoke.sh``
    and the CI multi-replica job gate on it.  Four sections:

    * **scaling** — the same fresh out-cost stream is served by a
      1-replica and a 4-replica ``ReplicaCluster`` (real spawn-context
      server processes behind the asyncio line protocol, host engine so
      replica throughput is CPU-bound).  Both real wall-clock rates are
      reported; the >= 1.5x acceptance gate is judged on the **modeled**
      aggregate rate — each request priced at its *measured* 1-replica
      service latency and assigned to its consistent-hash ring owner,
      the 4-replica rate being the partition's makespan.  Same
      discipline as the lanes row: the model prices exactly the layer
      under test (the ring's load spread across replica processes) and
      stays meaningful on the single-core CI container, where four
      CPU-bound processes physically cannot beat one.
    * **parity** — every cluster response is bit-compared (cost equality
      on exact routes) against a fresh single-process ``plan_one``
      reference: zero cross-replica mismatches is a hard gate.
    * **shared cache** — a fresh stream is spread round-robin
      (``affinity=False``) so non-owner replicas solve and *publish* to
      the ring owner, then random isomorphs of the same queries are
      routed by affinity: the owner answers them from published entries
      (``origin != "local"``), so the summed cross-replica hit count
      must be > 0.
    * **tenants** — a deterministic VirtualClock loopback replica with
      tenant quotas: the over-quota tenants get shed/downgraded, the
      unmetered tenant's promised-deadline misses stay 0 under the same
      interleaved stream, and the client ceilings (fed from the
      replica's deny rates) pre-shed the over-quota excess.
    """
    import tempfile

    from repro.service import (ClusterClient, LoopbackTransport,
                               ReplicaCluster, ReplicaState, TenantQuota)
    from repro.service import net as net_mod

    n_scale = 32 if quick else 48
    n_range = (10, 11) if quick else (11, 12)
    spec = WorkloadSpec(n_requests=n_scale, seed=seed, n_range=n_range,
                        fresh_frac=1.0, cost_mix=(("out", 1.0),),
                        topologies=("chain", "star", "cycle", "sparse"))
    timed = [dataclasses.replace(r, latency_budget=None, slo=None)
             for r in make_workload(spec)]
    warm_spec = dataclasses.replace(spec, n_requests=8, seed=seed + 17,
                                    n_range=(8, 9))
    warm = [dataclasses.replace(r, latency_budget=None, slo=None)
            for r in make_workload(warm_spec)]

    # single-process bit-exact references (same host engine, no cluster)
    ref_srv = PlanServer(enable_batch=False,
                         batch_policy=BatchPolicy(engine="host"))
    refs = {r.req_id: ref_srv.plan_one(r.q, r.card, cost=r.cost)
            for r in timed}

    cfg = {"engine": "host", "enable_batch": False,
           "prewarm_ns": (n_range[0],), "prewarm_costs": ("max", "out")}
    rates: dict = {}
    lat1: dict = {}
    checked = bad = errors = 0
    shared: dict = {}
    obs_merge: dict = {}
    manifest_buckets = 0
    client_stats: dict = {}
    clusters: list = []
    try:
        cluster4 = client4 = None
        for n_rep in (1, 4):
            cl = ReplicaCluster(n_rep, config=dict(cfg))
            clusters.append(cl)
            client = cl.start()
            client.plan_many(list(warm), threads=8)
            t0 = time.perf_counter()
            resps = client.plan_many(list(timed), threads=8)
            wall = time.perf_counter() - t0
            rates[f"replicas{n_rep}"] = round(len(timed) / wall, 1)
            for req, resp in zip(timed, resps):
                if resp is None or resp.status != "exact":
                    errors += 1
                    continue
                checked += 1
                if resp.cost != refs[req.req_id].cost:
                    bad += 1
                if n_rep == 1:
                    lat1[req.req_id] = max(float(resp.latency or 0.0),
                                           1e-6)
            if n_rep == 4:
                cluster4, client4 = cl, client
            else:
                cl.stop()

        # ---- shared plan-cache tier on the (kept) 4-replica cluster:
        # spread fresh solves off-owner (publish), then route isomorphs
        # by affinity (the owner answers from the published entries)
        transport = client4.transport
        manifest_buckets = len(cluster4.manifest)
        sh_spec = WorkloadSpec(n_requests=12 if quick else 16,
                               seed=seed + 29, n_range=(8, 9),
                               fresh_frac=1.0, cost_mix=(("out", 1.0),),
                               topologies=("chain", "star", "cycle",
                                           "sparse"))
        sh_reqs = [dataclasses.replace(r, latency_budget=None, slo=None)
                   for r in make_workload(sh_spec)]
        spread = ClusterClient(transport, cluster4.replica_ids,
                               affinity=False)
        for r in sh_reqs:
            spread.plan_request(r)
        owner_client = ClusterClient(transport, cluster4.replica_ids)
        rng = np.random.default_rng(seed + 23)
        iso_hits = 0
        for r in sh_reqs:
            q2, c2 = _relabel_query(r.q, r.card, rng)
            resp = owner_client.plan_request(
                dataclasses.replace(r, q=q2, card=c2))
            iso_hits += bool(resp.cache_hit)
        cross_hits = remote_inserts = 0
        for rid in cluster4.replica_ids:
            out = transport.call(rid, {"op": "stats"})
            cs = net_mod._dec(out["stats"])["cache"]
            cross_hits += cs.get("cross_hits", 0)
            remote_inserts += cs.get("remote_inserts", 0)
        shared = {"publishes": spread.stats["publishes"],
                  "remote_inserts": remote_inserts,
                  "cross_hits": cross_hits,
                  "isomorph_hits": iso_hits,
                  "isomorph_probes": len(sh_reqs)}
        client_stats = {k: client4.stats[k]
                        for k in ("requests", "failovers", "hedges",
                                  "net_errors", "replica_deaths",
                                  "errors")}

        # ---- multi-replica observability: replica-tagged flight dumps
        # merged by the obs_tail CLI (the operator view the satellites
        # exist for); counts only, no gate — a clean run has no incidents
        dumpdir = tempfile.mkdtemp(prefix="serve_bench_flight_")
        cluster4.dump_recorders(dumpdir)
        import importlib.util as _ilu
        ot_spec = _ilu.spec_from_file_location(
            "obs_tail", os.path.join(REPO_ROOT, "scripts", "obs_tail.py"))
        ot = _ilu.module_from_spec(ot_spec)
        ot_spec.loader.exec_module(ot)
        merged = ot.merge_records(
            [os.path.join(dumpdir, f"flight_{rid}.jsonl")
             for rid in cluster4.replica_ids
             if os.path.exists(os.path.join(dumpdir,
                                            f"flight_{rid}.jsonl"))])
        ms = ot.summarize(merged)
        obs_merge = {"records": ms["records"],
                     "replicas": len(ms["replicas"])}
    finally:
        for cl in clusters:
            try:
                cl.stop()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass

    # ---- tenant SLO quotas: one deterministic VirtualClock loopback
    # replica, an interleaved three-tenant stream.  "free" (shed) and
    # "trial" (downgrade) are metered at 2/s but arrive at ~6.7/s;
    # "paid" is unmetered on the interactive (1s-deadline) class with a
    # virtual 1ms solve — its promised deadlines must all hold.
    srv = PlanServer(enable_batch=False,
                     batch_policy=BatchPolicy(engine="host"))
    clk = VirtualClock()
    quotas = {"free": TenantQuota("free", rate=2.0, burst=2.0,
                                  on_exceed="shed"),
              "trial": TenantQuota("trial", rate=2.0, burst=2.0,
                                   on_exceed="downgrade")}
    rt = srv.make_runtime(
        clock=clk,
        config=RuntimeConfig(
            max_batch=1,
            slo_classes={"interactive": SLOClass("interactive", 1.0)},
            tenant_quotas=quotas),
        duration_fn=lambda kind, info: 1e-3)
    state = ReplicaState(srv, replica_id="t0", runtime=rt)
    t_spec = WorkloadSpec(n_requests=60, seed=seed + 31, n_range=(5, 6),
                          pool_size=4, fresh_frac=0.0, relabel_frac=0.0,
                          cost_mix=(("max", 1.0),))
    t_reqs = make_workload(t_spec)
    for i, r in enumerate(t_reqs):
        clk.advance(0.05)
        tenant = ("free", "trial", "paid")[i % 3]
        state.plan_sync(dataclasses.replace(
            r, tenant=tenant, latency_budget=None, arrival=clk.now(),
            slo="interactive" if tenant == "paid" else None))
    snap = rt.quotas.snapshot()["tenants"]
    paid_cls = rt.stats.per_class.get("interactive")
    # client-side ceilings: fold the replica's deny rates back, then
    # pre-shed a fresh burst of the over-quota tenant at the client
    tclient = ClusterClient(LoopbackTransport({"t0": state}), ["t0"])
    tclient.refresh_ceilings()
    for _ in range(20):
        clk.advance(0.05)
        tclient.plan(t_reqs[0].q, t_reqs[0].card, cost="max",
                     tenant="free")
    tenants = {
        "over_quota_shed": snap.get("free", {}).get("shed", 0),
        "over_quota_downgraded": snap.get("trial", {}).get(
            "downgraded", 0),
        "in_quota_served": paid_cls.served if paid_cls else 0,
        "in_quota_deadline_misses":
            paid_cls.deadline_misses if paid_cls else -1,
        "in_quota_shed": paid_cls.shed if paid_cls else -1,
        "ceiling_free": tclient.ceilings.ceiling("free"),
        "client_shed": tclient.stats["client_shed"],
    }

    # modeled scale-out (the gate): each request priced at its measured
    # 1-replica service latency, partitioned to its ring owner — the
    # 4-replica rate is the partition makespan (lanes-row discipline:
    # deterministic given the measurements, meaningful on 1-core CI)
    from repro.service import HashRing
    from repro.service.canon import canonicalize as _canon

    ring4 = HashRing([f"r{i}" for i in range(4)])
    per_replica: dict = {}
    for r in timed:
        rid = ring4.owner(_canon(r.q, r.card).key)
        per_replica[rid] = per_replica.get(rid, 0.0) \
            + lat1.get(r.req_id, 1e-6)
    total_s = sum(per_replica.values())
    makespan4 = max(per_replica.values()) if per_replica else 0.0
    modeled = {"replicas1": round(len(timed) / total_s, 1)
               if total_s > 0 else 0.0,
               "replicas4": round(len(timed) / makespan4, 1)
               if makespan4 > 0 else 0.0}
    row = {"config": "cluster/host/1-4x",
           "n_queries": len(timed),
           "plans_per_s": rates,
           "modeled_plans_per_s": modeled,
           "ring_load": {rid: round(s, 4)
                         for rid, s in sorted(per_replica.items())},
           "scaling_x": round(total_s / makespan4, 2)
           if makespan4 > 0 else 0.0,
           "parity_checked": checked, "parity_mismatches": bad,
           "errors": errors,
           "manifest_buckets": manifest_buckets,
           "shared_cache": shared,
           "client": client_stats,
           "obs_tail": obs_merge,
           "tenants": tenants}
    return row, bad


def _cluster_gate(row: dict, enforce_target: bool) -> "list[str]":
    """The cluster row's invariant violations (empty = clean)."""
    bad = []
    if row["parity_mismatches"]:
        bad.append(f"{row['parity_mismatches']} cross-replica parity "
                   "mismatches")
    if row["errors"]:
        bad.append(f"{row['errors']} cluster responses were not exact")
    if row["shared_cache"].get("cross_hits", 0) <= 0:
        bad.append("shared cache tier scored no cross-replica hits")
    if row["shared_cache"].get("publishes", 0) <= 0:
        bad.append("no exact solves were published to their ring owner")
    t = row["tenants"]
    if t["over_quota_shed"] <= 0 or t["over_quota_downgraded"] <= 0:
        bad.append("over-quota tenants were not shed/downgraded "
                   f"(shed={t['over_quota_shed']}, "
                   f"downgraded={t['over_quota_downgraded']})")
    if t["in_quota_deadline_misses"] != 0 or t["in_quota_shed"] != 0:
        bad.append("in-quota tenant lost promised deadlines under the "
                   f"mixed stream (misses={t['in_quota_deadline_misses']}"
                   f", shed={t['in_quota_shed']})")
    if t["client_shed"] <= 0:
        bad.append("client admission ceilings pre-shed nothing")
    if enforce_target and row["scaling_x"] < 1.5:
        bad.append(f"modeled 1->4 replica scaling only "
                   f"{row['scaling_x']}x (>= 1.5x required)")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small workload: the smoke/CI gate")
    ap.add_argument("--n-requests", type=int, default=None)
    ap.add_argument("--n-min", type=int, default=None)
    ap.add_argument("--n-max", type=int, default=None)
    ap.add_argument("--batch-sizes", default=None,
                    help="comma-separated micro-batch sizes to sweep")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget-frac", type=float, default=0.05)
    ap.add_argument("--workload", choices=("synthetic", "einsum"),
                    default="synthetic",
                    help="main-sweep stream: synthetic templates or the "
                         "einsum contraction-log replay lane")
    ap.add_argument("--cost", choices=("mix", "out"), default="mix",
                    help="main-sweep cost mix: the default serving mix, "
                         "or an out-only sparse stream that pins the "
                         "whole sweep onto the connected-C_out lane "
                         "(the dedicated out_sweep row runs either way)")
    ap.add_argument("--no-target", action="store_true",
                    help="report only; don't enforce the 2x acceptance "
                         "targets")
    ap.add_argument("--skip-cold", action="store_true",
                    help="skip the cold-start/prewarm measurement "
                         "(it recompiles every bucket twice)")
    ap.add_argument("--bench-out",
                    default=os.path.join(REPO_ROOT, "BENCH_serve.json"),
                    help="compact cross-PR trajectory record (repo root)")
    ap.add_argument("--only", choices=("all", "cluster"), default="all",
                    help="'cluster' runs just the distributed-serving "
                         "row (the CI multi-replica smoke job)")
    args = ap.parse_args(argv)
    engine_mod.use_compile_cache(REPO_ROOT)

    if args.only == "cluster":
        cluster_row, cluster_bad = run_cluster_row(args.quick, args.seed)
        print(f"{cluster_row['config']},,,,"
              f"scaling={cluster_row['scaling_x']}x;"
              f"cross_hits={cluster_row['shared_cache']['cross_hits']};"
              f"publishes={cluster_row['shared_cache']['publishes']};"
              f"parity_bad={cluster_row['parity_mismatches']};"
              f"tenant_shed={cluster_row['tenants']['over_quota_shed']};"
              f"client_shed={cluster_row['tenants']['client_shed']}",
              flush=True)
        with open(args.bench_out, "w") as f:
            json.dump({"generated_by": "benchmarks/serve_bench.py "
                                       "--only cluster"
                                       + (" --quick" if args.quick
                                          else ""),
                       "cluster": cluster_row,
                       "parity_mismatches": cluster_bad},
                      f, indent=1, default=str)
        print(f"# written {args.bench_out}")
        violations = _cluster_gate(cluster_row, not args.no_target)
        for v in violations:
            print(f"FAIL: {v}", file=sys.stderr)
        return 1 if violations else 0

    if args.quick:
        n_requests = args.n_requests or 192
        n_range = (args.n_min or 5, args.n_max or 9)
        batch_sizes = [int(b) for b in
                       (args.batch_sizes or "1,16").split(",")]
    else:
        n_requests = args.n_requests or 512
        n_range = (args.n_min or 6, args.n_max or 14)
        batch_sizes = [int(b) for b in
                       (args.batch_sizes or "1,4,16").split(",")]

    spec_kw = {}
    engine_configs = ENGINE_CONFIGS
    if args.cost == "out":
        # out-only sparse stream: everything rides the DPccp lane.  The
        # (min,+) layer sweep never probes, so the gamma-probe config
        # (and its rounds-reduction gate) has nothing to measure here.
        spec_kw = {"cost_mix": (("out", 1.0),),
                   "topologies": ("chain", "star", "cycle", "sparse",
                                  "grid")}
        engine_configs = tuple(c for c in ENGINE_CONFIGS if c[1] == 1)
    spec = WorkloadSpec(n_requests=n_requests, seed=args.seed,
                        n_range=n_range, budget_frac=args.budget_frac,
                        **spec_kw)
    reqs = make_workload(spec) if args.workload == "synthetic" \
        else make_einsum_workload(spec)
    ns = sorted({r.q.n for r in reqs})
    print(f"# workload: {args.workload}, {n_requests} requests, "
          f"n in {ns}, "
          f"{len(set(id(r.q) for r in reqs))} distinct graph objects")
    print("# warmup (jit tracing all shapes) ...", flush=True)
    t0 = time.perf_counter()
    warmup(reqs, batch_sizes)
    # the naive loop shares single-query jit caches; warm them too
    for req in reqs[: min(len(reqs), 64)]:
        optimize(req.q, req.card, cost=req.cost, **_naive_kw(req.cost))
    print(f"# warmup done in {time.perf_counter() - t0:.1f}s", flush=True)

    rows = []
    print("config,plans_per_s,p50_ms,p99_ms,extra")
    naive = run_naive(reqs)
    rows.append(naive)
    print(f"{naive['config']},{naive['plans_per_s']:.1f},"
          f"{naive['p50_ms']:.2f},{naive['p99_ms']:.2f},", flush=True)

    parity_fail = 0
    invariant_fail = 0
    best: dict = {}
    rounds_by_probe: dict = {}
    for engine, gamma in engine_configs:    # host first: the PR-1 path
        probe = "binary" if gamma == 1 else f"gamma{gamma}"
        # the gamma-probe config is a cache-off measurement row
        cache_sweep = (False,) if gamma > 1 else (False, True)
        for cache in cache_sweep:
            for b in batch_sizes:
                row, resps = run_service(list(reqs), b, cache, engine,
                                         gamma)
                rows.append(row)
                cs = row["cache"]
                lane = row["dpconv_lane"]
                extra = (f"hit_rate={cs['hit_rate']};"
                         f"batches={row['batches']};"
                         f"solver={row['solver_plans_per_s']:.0f}/s;"
                         f"passes={lane.get('passes_per_solve', 0):.1f};"
                         f"dispatches="
                         f"{lane.get('dispatches_per_solve', 0):.1f};"
                         f"rounds="
                         f"{lane.get('fused_rounds_per_solve', 0):.1f}")
                print(f"{row['config']},{row['plans_per_s']:.1f},"
                      f"{row['p50_ms']:.2f},{row['p99_ms']:.2f},{extra}",
                      flush=True)
                if not row.get("fused_one_dispatch", True):
                    invariant_fail += 1
                    print("#   INVARIANT VIOLATION: fused solve took "
                          f"{row['engine_counters']['dispatches']} "
                          f"dispatches for "
                          f"{row['engine_counters']['solves']} solves",
                          file=sys.stderr)
                if not row.get("fused_no_host_extraction", True):
                    invariant_fail += 1
                    print("#   INVARIANT VIOLATION: host extraction "
                          "recursion ran on the fused path",
                          file=sys.stderr)
                if engine == "fused" and not cache and b == max(
                        batch_sizes) and "fused_rounds_per_solve" in lane:
                    rounds_by_probe[probe] = \
                        lane["fused_rounds_per_solve"]
                key = (engine, gamma, "cache" if cache else "nocache")
                cur = best.get(key)
                if cur is None or row["plans_per_s"] > cur["plans_per_s"]:
                    best[key] = row
                checked, bad = check_parity(reqs, resps)
                parity_fail += bad
                print(f"#   parity: {checked} exact routes checked, "
                      f"{bad} mismatches", flush=True)

    # ------------------------------------------------- replay lane row
    replay_row, replay_checked, replay_bad = run_replay(
        args.seed + 1, min(96, n_requests), max(batch_sizes))
    rows.append(replay_row)
    parity_fail += replay_bad
    print(f"{replay_row['config']},{replay_row['plans_per_s']:.1f},,,"
          f"hit_rate={replay_row['cache']['hit_rate']}")
    print(f"#   replay parity: {replay_checked} checked, "
          f"{replay_bad} mismatches", flush=True)

    # ------------------------------------- incremental-planning reuse
    reuse_row, reuse_bad = run_reuse_row(
        args.seed + 5, min(96, n_requests), max(batch_sizes))
    rows.append(reuse_row)
    parity_fail += reuse_bad
    print(f"{reuse_row['config']},"
          f"{reuse_row['plans_per_s_seeded']:.1f},"
          f"{reuse_row['p50_ms_seeded']:.2f},,"
          f"layer_hit_rate={reuse_row['layer_hit_rate']};"
          f"seeded={reuse_row['seeded_solves']};"
          f"p50_cold={reuse_row['p50_ms_cold']:.2f}ms;"
          f"p50_delta={reuse_row['p50_delta_ms']:.2f}ms;"
          f"parity_ok={reuse_row['parity_ok']};"
          f"degraded_to_exactcap={reuse_row['degraded_to_exactcap']}")
    print(f"#   reuse parity: {reuse_row['parity_checked']} checked, "
          f"{reuse_bad} mismatches", flush=True)
    if reuse_row["layer_hit_rate"] <= 0.0:
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: layer-fragment cache scored no "
              "hits on the model-trace replay stream", file=sys.stderr)
    if reuse_row["degraded_to_exactcap"]:
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: "
              f"{reuse_row['degraded_to_exactcap']} degraded plans were "
              "served to exact-capable requests", file=sys.stderr)

    # --------------------------------------- connected-C_out lane row
    out_row, out_checked, out_bad = run_out_sweep(
        args.seed + 2, min(96, n_requests), max(batch_sizes))
    rows.append(out_row)
    parity_fail += out_bad
    print(f"{out_row['config']},{out_row['fused_plans_per_s']:.1f},,,"
          f"host={out_row['host_plans_per_s']:.1f}/s;"
          f"speedup={out_row['speedup']:.2f}x;"
          f"dispatches={out_row['dispatches_per_solve']:.1f};"
          f"rounds={out_row['rounds_per_solve']:.1f}")
    print(f"#   out-lane parity: {out_checked} checked, "
          f"{out_bad} mismatches", flush=True)
    if out_row["queries_on_lane"] and \
            out_row["max_dispatches_per_solve"] != 1:
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: fused out solve took "
              f"{out_row['max_dispatches_per_solve']} dispatches",
              file=sys.stderr)
    if out_row["host_extractions"]:
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: host extraction recursion ran "
              "on the fused out lane", file=sys.stderr)

    # ------------------------------------------------ async runtime row
    rt_row, obs_row, rt_checked, rt_bad = run_runtime_sweep(
        args.seed + 3, min(160, max(n_requests, 96)), max(batch_sizes))
    rows.append(rt_row)
    rows.append(obs_row)
    parity_fail += rt_bad
    print(f"{rt_row['config']},,,,"
          f"coalesce_rate={rt_row['coalesce_rate']};"
          f"shed_rate={rt_row['shed_rate']};"
          f"occupancy={rt_row['mean_batch_occupancy']};"
          f"overtakes={rt_row['overtakes']};"
          f"hit_p99={rt_row['hit_p99_ms']}ms;"
          f"miss_solve={rt_row['miss_solve_ms_mean']}ms")
    print(f"#   runtime parity vs sync serve: {rt_checked} checked, "
          f"{rt_bad} mismatches; deadline_misses="
          f"{rt_row['deadline_misses']}", flush=True)
    if not rt_row["one_dispatch"] or rt_row["host_extractions"]:
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: runtime serving broke the "
              "one-dispatch / no-host-extraction contract",
              file=sys.stderr)
    if rt_row["batches"] and not (
            rt_row["hit_p99_ms"] < rt_row["miss_solve_ms_mean"]):
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: fast-path hit p99 "
              f"({rt_row['hit_p99_ms']}ms) did not undercut the mean "
              f"in-flight batched solve "
              f"({rt_row['miss_solve_ms_mean']}ms)", file=sys.stderr)
    if rt_row["deadline_misses"]:
        invariant_fail += 1
        print(f"#   INVARIANT VIOLATION: {rt_row['deadline_misses']} "
              "deadline misses in promised (non-downgraded) classes",
              file=sys.stderr)
    print(f"{obs_row['config']},,,,"
          f"spans/req={obs_row['spans_per_request']};"
          f"unclosed={obs_row['unclosed_spans']};"
          f"mismatches={obs_row['lane_shape_mismatches']};"
          f"overhead={obs_row['overhead_frac']};"
          f"recorder={obs_row['recorder']}", flush=True)
    if (obs_row["unclosed_spans"] or obs_row["open_spans"]
            or obs_row["lane_shape_mismatches"]
            or not obs_row["recorder_shed_exact"]
            or not obs_row["recorder_miss_exact"]
            or not obs_row["recorder_downgrade_exact"]):
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: span tracing leaked "
              "(unclosed/open spans, lane-shape mismatch, or recorder "
              "capture not exact)", file=sys.stderr)

    # ------------------------------------------------ resilience row
    faults_row = run_faults_row(args.seed + 4, min(160, n_requests),
                                max(batch_sizes))
    rows.append(faults_row)
    print(f"{faults_row['config']},,,,"
          f"fired={faults_row['faults_fired']};"
          f"recovered={faults_row['recovered']};"
          f"degraded={faults_row['degraded']};"
          f"errors={faults_row['errors']};"
          f"breaker_opens={faults_row['breaker_opens']};"
          f"breaker_closes={faults_row['breaker_closes']};"
          f"overhead={faults_row['overhead_frac']}", flush=True)
    if faults_row["wrong_plans"] or faults_row["unresolved"]:
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: chaos run produced "
              f"{faults_row['wrong_plans']} wrong plans and left "
              f"{faults_row['unresolved']} requests unresolved",
              file=sys.stderr)
    if not (faults_row["faults_fired"] and faults_row["breaker_opens"]
            and faults_row["breaker_closes"]):
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: the chaos schedule did not "
              "exercise the breaker round trip (fired="
              f"{faults_row['faults_fired']}, opens="
              f"{faults_row['breaker_opens']}, closes="
              f"{faults_row['breaker_closes']})", file=sys.stderr)

    # ------------------------------------------------ N-lane scale-out
    lanes_row, lanes_bad = run_lanes_row()
    rows.append(lanes_row)
    parity_fail += lanes_bad
    shd = lanes_row["sharded"]
    print(f"{lanes_row['config']},,,,"
          f"modeled1={lanes_row['modeled_plans_per_s']['lanes1']}/s;"
          f"modeled4={lanes_row['modeled_plans_per_s']['lanes4']}/s;"
          f"scaling={lanes_row['scaling_x']}x;"
          f"lane_dispatches={lanes_row['lane_dispatches']};"
          f"sharded_devices={shd['devices']}", flush=True)
    if lanes_row["scaling_x"] < 1.5:
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: 4-lane modeled throughput only "
              f"{lanes_row['scaling_x']}x the 1-lane runtime (>= 1.5x "
              "required)", file=sys.stderr)
    shard_parity = [k for k in shd if k.endswith("_parity")]
    if any(not shd[k] for k in shard_parity):
        invariant_fail += 1
        print("#   INVARIANT VIOLATION: sharded solve parity failed: "
              f"{ {k: shd[k] for k in shard_parity} }", file=sys.stderr)
    if shard_parity:
        print(f"#   sharded parity (D={shd.get('shards')}): "
              + ", ".join(f"{k}={shd[k]}" for k in sorted(shard_parity)),
              flush=True)

    # -------------------------------------- distributed serving cluster
    cluster_row, cluster_bad = run_cluster_row(args.quick, args.seed)
    rows.append(cluster_row)
    parity_fail += cluster_bad
    print(f"{cluster_row['config']},,,,"
          f"plans1={cluster_row['plans_per_s']['replicas1']}/s;"
          f"plans4={cluster_row['plans_per_s']['replicas4']}/s;"
          f"scaling={cluster_row['scaling_x']}x;"
          f"cross_hits={cluster_row['shared_cache']['cross_hits']};"
          f"publishes={cluster_row['shared_cache']['publishes']};"
          f"tenant_shed={cluster_row['tenants']['over_quota_shed']};"
          f"client_shed={cluster_row['tenants']['client_shed']}",
          flush=True)
    print(f"#   cluster parity: {cluster_row['parity_checked']} checked, "
          f"{cluster_bad} mismatches", flush=True)
    for v in _cluster_gate(cluster_row, not args.no_target):
        invariant_fail += 1
        print(f"#   INVARIANT VIOLATION: cluster: {v}", file=sys.stderr)

    # -------------------------------------------- cold start / prewarm
    cold = {}
    if not args.skip_cold:
        cold = run_cold_start(reqs[: min(48, len(reqs))],
                              min(8, max(batch_sizes)))
        rows.append({"config": "cold_start", **cold})
        p99_cold = cold["p99_ms_no_prewarm"]
        print(f"# cold start: no_prewarm p99={p99_cold:.1f}ms, "
              f"prewarm p99={cold['p99_ms_prewarm']:.1f}ms "
              f"({cold['prewarm_compiled']} executables in "
              f"{cold['prewarm_s']}s before traffic)", flush=True)

    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "serve_bench.json")
    with open(out, "w") as f:
        json.dump({"workload": dataclass_dict(spec), "rows": rows},
                  f, indent=1, default=str)
    print(f"# written {out}")

    # ------------------------------------------------ acceptance targets
    # 1) full fused serving path (cache + batching) vs the naive loop
    fused_full = best[("fused", 1, "cache")]["plans_per_s"]
    speedup_naive = fused_full / naive["plans_per_s"]
    print(f"# best fused batched+cached vs naive: {speedup_naive:.2f}x")
    # 2) fused engine vs the PR-1 (host-loop) serving path.  Compared
    # cache-OFF so the ratio measures the solve path, not replayed cache
    # hits (a hit costs the same regardless of engine); the batch-lane
    # solver rate is reported alongside as the pure-solver view.
    host_row = best[("host", 1, "nocache")]
    fused_row = best[("fused", 1, "nocache")]
    speedup_host = (fused_row["plans_per_s"] / host_row["plans_per_s"]
                    if host_row["plans_per_s"] > 0 else 0.0)
    solver_speedup = (fused_row["solver_plans_per_s"]
                      / host_row["solver_plans_per_s"]
                      if host_row["solver_plans_per_s"] > 0 else 0.0)
    print(f"# fused vs host-loop serving (cache off): "
          f"{speedup_host:.2f}x end-to-end, {solver_speedup:.2f}x on the "
          f"batch-lane solver")
    print(f"# dispatches per batched solve: host~="
          f"{host_row['dpconv_lane'].get('passes_per_solve', 0):.1f}"
          f" (one per feasibility pass), fused="
          f"{fused_row['dpconv_lane'].get('dispatches_per_solve', 0):.1f}")
    # 3) probe strategies: (G+1)-ary gamma probing must cut the while-
    # loop rounds per solve vs binary search (equal optima/trees were
    # already asserted by its parity sweep)
    gamma_probe = [p for p in rounds_by_probe if p != "binary"]
    rounds_ok = True
    if gamma_probe and "binary" in rounds_by_probe:
        g = gamma_probe[0]
        rounds_ok = rounds_by_probe[g] < rounds_by_probe["binary"]
        print(f"# rounds per fused solve: binary="
              f"{rounds_by_probe['binary']:.2f}, {g}="
              f"{rounds_by_probe[g]:.2f} "
              f"({'OK' if rounds_ok else 'NOT REDUCED'})")

    summary = {
        "generated_by": "benchmarks/serve_bench.py "
                        + ("--quick" if args.quick else "(full)"),
        "workload": args.workload,
        "n_requests": len(reqs),
        "n_range": list(n_range),
        "plans_per_s": {
            "naive": naive["plans_per_s"],
            "host_serving": best[("host", 1, "cache")]["plans_per_s"],
            "host_serving_nocache": host_row["plans_per_s"],
            "fused_serving": fused_full,
            "fused_serving_nocache": fused_row["plans_per_s"],
        },
        "solver_plans_per_s": {
            "host": host_row["solver_plans_per_s"],
            "fused": fused_row["solver_plans_per_s"],
        },
        "latency_ms": {
            "fused_p50": best[("fused", 1, "cache")]["p50_ms"],
            "fused_p99": best[("fused", 1, "cache")]["p99_ms"],
            "host_p50": best[("host", 1, "cache")]["p50_ms"],
            "host_p99": best[("host", 1, "cache")]["p99_ms"],
        },
        "passes_per_solve": {
            "host": host_row["dpconv_lane"].get("passes_per_solve"),
            "fused": fused_row["dpconv_lane"].get("passes_per_solve"),
        },
        "dispatches_per_solve": {
            "host": host_row["dpconv_lane"].get("dispatches_per_solve"),
            "fused": fused_row["dpconv_lane"].get("dispatches_per_solve"),
        },
        "rounds_per_solve": rounds_by_probe,
        "cap_lane": {
            "queries": fused_row["dpconv_lane"].get("cap_queries", 0),
            "max_dispatches_per_solve":
                fused_row["dpconv_lane"].get("cap_max_dispatches"),
        },
        "cold_start": cold,
        "replay": replay_row,
        "reuse": reuse_row,
        "runtime": {k: rt_row[k] for k in
                    ("parity_checked", "parity_mismatches",
                     "one_dispatch", "host_extractions",
                     "fast_path_hits", "overtakes", "coalesced",
                     "coalesce_rate", "shed", "shed_backpressure",
                     "shed_rate", "downgraded", "batches",
                     "mean_batch_occupancy", "deadline_misses",
                     "hit_p99_ms", "miss_solve_ms_mean", "per_class")},
        "obs": obs_row,
        "faults": faults_row,
        "lanes": lanes_row,
        "cluster": cluster_row,
        "out_lane": {
            "queries": out_row["queries_on_lane"],
            "parity_checked": out_row["parity_checked"],
            "parity_mismatches": out_row["parity_mismatches"],
            "host_plans_per_s": out_row["host_plans_per_s"],
            "fused_plans_per_s": out_row["fused_plans_per_s"],
            "speedup": out_row["speedup"],
            "max_dispatches_per_solve":
                out_row["max_dispatches_per_solve"],
            "dispatches_per_solve": out_row["dispatches_per_solve"],
            "rounds_per_solve": out_row["rounds_per_solve"],
            "host_extractions": out_row["host_extractions"],
        },
        "speedup": {
            "fused_vs_naive": speedup_naive,
            "fused_vs_host_serving": speedup_host,
            "fused_vs_host_solver": solver_speedup,
        },
        "parity_mismatches": parity_fail,
    }
    with open(args.bench_out, "w") as f:
        json.dump(summary, f, indent=1, default=str)
    print(f"# written {args.bench_out}")

    if parity_fail:
        print("FAIL: parity mismatches", file=sys.stderr)
        return 1
    if invariant_fail:
        print("FAIL: fused dispatch/extraction invariants violated",
              file=sys.stderr)
        return 1
    if not rounds_ok:
        print("FAIL: gamma probing did not reduce rounds per solve",
              file=sys.stderr)
        return 1
    if not args.no_target:
        if speedup_naive < 2.0:
            print("FAIL: < 2x plans/sec over the naive loop",
                  file=sys.stderr)
            return 1
        if max(speedup_host, solver_speedup) < 2.0:
            print("FAIL: fused engine < 2x over the host-loop serving "
                  "path", file=sys.stderr)
            return 1
        if (cold and cold["p99_ms_prewarm"]
                >= cold["p99_ms_no_prewarm"]):
            print("FAIL: prewarm did not improve cold-start p99",
                  file=sys.stderr)
            return 1
    return 0


def dataclass_dict(spec) -> dict:
    return dataclasses.asdict(spec)


if __name__ == "__main__":
    sys.exit(main())
