"""Batched serving demo: prefill + greedy decode through the KV-cache
decode path (the same serve_step the multi-pod dry-run lowers at
decode_32k / long_500k scale).

    PYTHONPATH=src python examples/serve_lm.py [--arch gemma3-1b]

gemma3's 5:1 local:global pattern exercises the ring-buffer local caches.

Before the decode loop, the model stack's einsum contraction orders are
planned through the *synchronous* ``PlanServer.serve`` front end — which
is now a thin driver over the same deadline-aware scheduler the async
``plan_async`` path uses (``repro.service.runtime``), so this demo
exercises the sync lane of the runtime outside the test suite (the
concurrent lane lives in examples/planner_demo.py).
"""
import argparse
import os
import sys

from repro.launch.serve import main as serve_main


def plan_contraction_orders() -> None:
    """Serve the canned model-stack contraction trace through the
    runtime-backed sync front end, SLO-classed as interactive traffic."""
    from repro.service import PlanServer, WorkloadSpec, \
        make_einsum_workload

    reqs = make_einsum_workload(WorkloadSpec(
        n_requests=32, seed=0, rate=500.0,
        cost_mix=(("max", 0.8), ("out", 0.2)),
        slo_mix=(("interactive", 0.5), ("standard", 0.5))))
    srv = PlanServer(max_batch=8)
    # compile the fused executable buckets before traffic arrives —
    # without this the first interactive-class requests blow their
    # deadline budgets on inline jit compiles (the cold-bucket spike
    # serve_bench's cold-start row measures)
    pw = srv.prewarm(sorted({r.q.n for r in reqs}))
    print(f"[planner] prewarmed {pw['compiled']} executables in "
          f"{pw['seconds']:.1f}s before admitting traffic")
    _, stats = srv.serve(reqs)                 # sync driver, arrivals on
    rs = srv.last_runtime.stats
    cs = srv.cache.stats
    print(f"[planner] {stats.served} contraction plans served via the "
          f"sync runtime driver: {rs.fast_path_hits} fast-path hits, "
          f"{rs.coalesced} coalesced, {rs.batches} batched solves, "
          f"{rs.deadline_misses} deadline misses")
    print(f"[planner] cache hit rate {cs.hit_rate:.0%} "
          f"({cs.relabel_hits} relabeled), "
          f"latency p99 {stats.latency.percentile(99) * 1e3:.2f}ms")
    # the sync driver threads the same span tracer as the async path:
    # per-phase latency breakdown straight from the server's registry
    rt = srv.last_runtime
    trs = rt.tracer.stats()
    from repro.obs import span_phase_summary
    phases = span_phase_summary(srv.registry)
    disp = phases.get("dispatch", {"count": 0})
    print(f"[planner] obs: {trs['requests']} span trees "
          f"({trs['unclosed_spans']} unclosed), dispatch p95 "
          f"{disp.get('p95_ms', 0.0):.2f}ms over {disp['count']} solves; "
          f"recorder {rt.recorder.snapshot()['counts']}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    args, _ = ap.parse_known_args()
    from repro.core import engine
    engine.use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    plan_contraction_orders()
    sys.exit(serve_main([
        "--arch", args.arch, "--reduced",
        "--batch", "4", "--prompt-len", "24", "--gen", "24",
    ]))
