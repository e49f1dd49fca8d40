#!/usr/bin/env python3
"""Serve join plans on a TPU through the plan server's normal path, and
check every answer against the host oracles.

    python chip_smoke.py             # one chip
    python chip_smoke.py --mesh 4    # the 4-chip solve mesh, nothing else

One process, one ``PlanServer``.  The one-chip run builds the server with
the default policy (fused engine, ``backend="auto"``, layer cache on),
prewarms exactly the (n, cost) buckets it then serves, and serves:

* C_max on the paper's clique family at n = 17 (``querygraph.clique`` +
  ``make_cardinalities``, as ``benchmarks/run.py:bench_fig6`` sweeps);
* C_max at n = 13 and 15, the Pallas int32 tier;
* C_cap, connected C_cap and sparse connected C_out at n = 13, their
  single-chip ceiling, on chain, star and cycle graphs;
* one mixed ``make_workload`` stream with n in 6..13;
* one group through ``plan_async`` (the rest through ``serve``).

``--mesh 4`` serves C_cap, connected C_cap and C_out at n = 14 and 15 on
a 4-device ``make_solve_mesh(4)`` (``BatchPolicy(solve_shards=4)``).

Every plan must equal the host oracle bit for bit (``dpconv_max_ref`` /
``dpsub_max`` for C_max, ``dpccp`` for C_out, the host C_cap pipeline
``dpsub_max`` + pruned ``dpsub`` / ``dpccp``), come from the fused
engine on the chip, and never touch the failure ladder (``check``).  The
script exits non-zero before serving anything when JAX sees no TPU.  The
last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else
at ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from repro.core import engine as engine_mod  # noqa: E402
from repro.core.baselines import dpsub, dpsub_max  # noqa: E402
from repro.core.dpccp import dpccp  # noqa: E402
from repro.core.dpconv_max import dpconv_max_ref  # noqa: E402
from repro.core.querygraph import (chain, clique, cycle,  # noqa: E402
                                   make_cardinalities, star)
from repro.kernels.ops import INTERPRET_ENV, interpret_requested  # noqa: E402
from repro.service import (BatchPolicy, PlanRequest, PlanServer,  # noqa: E402
                           WorkloadSpec, make_workload)
from repro.service.workload import REGIMES  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke serves.  The defaults are the chip run; the CPU
    tests shrink them."""
    clique_n: int = 17             # the paper's clique C_max family
    pallas_ns: tuple = (13, 15)    # max chunks on the Pallas int32 tier
    lane_n: int = 13               # cap / connected cap / out ceiling
    mixed_range: tuple = (6, 13)   # the mixed make_workload stream
    mesh_ns: tuple = (14, 15)      # --mesh: lanes lifted by the mesh
    group: int = 2                 # requests per C_max group = max_batch


@dataclasses.dataclass
class Phase:
    name: str
    requests: list
    driver: str = "serve"          # "serve" (sync runtime) | "async"


@dataclasses.dataclass
class Case:
    phase: str
    request: PlanRequest
    response: object               # PlanResponse, or the raised error
    oracle: float = float("nan")


_GRAPHS = {"chain": chain, "star": star, "cycle": cycle, "clique": clique}
_SPARSE = ("chain", "star", "cycle")


def _requests(graphs, n: int, cost: str, seed: int,
              connected: bool = False) -> list:
    """Cliques get the paper's cardinalities (``make_cardinalities``
    defaults, capped at 1e8).  Sparse graphs get the workload's
    ``selective`` regime: under the defaults a chain's c(V) saturates at
    the cap, which leaves one C_max candidate and no search."""
    out = []
    for i, g in enumerate(graphs):
        q = _GRAPHS[g](n)
        kw = {} if g == "clique" else dict(
            zip(("base_range", "selectivity_range"), REGIMES["selective"]))
        card = make_cardinalities(q, seed=seed + i, **kw)
        out.append(PlanRequest(q=q, card=card, cost=cost,
                               connected=connected, explain=True))
    return out


def one_chip_phases(sz: Sizes) -> list:
    g = sz.group
    phases = [Phase("clique_max",
                    _requests(["clique"] * g, sz.clique_n, "max", 100))]
    for n in sz.pallas_ns:
        phases.append(Phase(f"pallas_max_n{n}",
                            _requests(_SPARSE[:g], n, "max", 200 + n)))
    n = sz.lane_n
    phases += [Phase("cap", _requests(_SPARSE, n, "cap", 300)),
               Phase("cap_conn", _requests(_SPARSE, n, "cap", 400,
                                           connected=True)),
               Phase("out", _requests(_SPARSE, n, "out", 500))]
    mixed = make_workload(WorkloadSpec(
        n_requests=12, seed=7, n_range=sz.mixed_range, topologies=_SPARSE,
        cost_mix=(("max", 0.5), ("cap", 0.25), ("out", 0.25)),
        pool_size=3, fresh_frac=0.0))
    phases.append(Phase("mixed", [dataclasses.replace(r, explain=True)
                                  for r in mixed]))
    phases.append(Phase("async_max",
                        _requests(_SPARSE[:g], sz.pallas_ns[0], "max", 600),
                        driver="async"))
    return _number(phases)


def mesh_phases(sz: Sizes) -> list:
    phases = []
    for n in sz.mesh_ns:
        phases += [Phase(f"cap_n{n}", _requests(_SPARSE[:2], n, "cap", 700)),
                   Phase(f"cap_conn_n{n}", _requests(_SPARSE[:2], n, "cap",
                                                     800, connected=True)),
                   Phase(f"out_n{n}", _requests(_SPARSE[:2], n, "out", 900))]
    return _number(phases)


def _number(phases: list) -> list:
    rid = 0
    for ph in phases:
        for i, r in enumerate(ph.requests):
            ph.requests[i] = dataclasses.replace(r, req_id=rid)
            rid += 1
    return phases


def _lane_cost(r: PlanRequest) -> str:
    return "cap_conn" if (r.cost == "cap" and r.connected) else r.cost


# ------------------------------------------------------------ the run
def prewarm(srv: PlanServer, phases: list) -> dict:
    """Compile every (n, cost) bucket the phases will hit, and no other
    (n, cost): each lane cost gets exactly the relation counts served
    under it."""
    by_cost: dict = {}
    for ph in phases:
        for r in ph.requests:
            by_cost.setdefault(_lane_cost(r), set()).add(r.q.n)
    total = {"compiled": 0, "seconds": 0.0}
    for cost, ns in sorted(by_cost.items()):
        w = srv.prewarm(sorted(ns), costs=(cost,))
        total["compiled"] += w["compiled"]
        total["seconds"] += w["seconds"]
    return total


async def _plan_async(srv: PlanServer, reqs: list) -> list:
    return await asyncio.gather(
        *(srv.plan_async(r.q, r.card, cost=r.cost, connected=r.connected,
                         explain=r.explain, req_id=r.req_id) for r in reqs),
        return_exceptions=True)


def serve(srv: PlanServer, phases: list) -> "tuple[list, list]":
    """Serve every phase; returns the cases and every runtime used."""
    cases, runtimes = [], []
    for ph in phases:
        if ph.driver == "serve":
            resps, _ = srv.serve(list(ph.requests), closed_loop=True)
            runtimes.append(srv.last_runtime)
        else:
            resps = asyncio.run(_plan_async(srv, ph.requests))
        cases += [Case(ph.name, r, resp)
                  for r, resp in zip(ph.requests, resps)]
    if any(ph.driver == "async" for ph in phases):
        rt = srv.async_runtime()
        rt.close()
        runtimes.append(rt)
    return cases, runtimes


def oracle(r: PlanRequest) -> float:
    """The host optimum for one request, computed without the device."""
    q, card, n = r.q, np.asarray(r.card, np.float64), r.q.n
    if r.cost == "max":
        return (dpconv_max_ref(card, n) if n <= 13
                else float(dpsub_max(card, n)[-1]))
    if r.cost == "out":
        return float(dpccp(q, card, mode="out")[0][-1])
    gamma = float(dpsub_max(card, n)[-1])        # C_cap pass 1
    if r.connected:
        return float(dpccp(q, card, mode="out", prune_gamma=gamma)[0][-1])
    return float(dpsub(card, n, mode="out", prune_gamma=gamma)[-1])


def check(cases: list, runtimes: list, records: list, counters: dict,
          platform: str, pallas_ns=(), mesh: int = 0) -> "list[str]":
    """Every reason the run fails to show the device served the plans
    (an empty list passes).  ``counters`` is the engine-stats delta over
    the serving window; ``records`` its ``DispatchRecord``s."""
    bad = []
    for c in cases:
        r, resp = c.request, c.response
        tag = (f"{c.phase}#{r.req_id} n={r.q.n} "
               f"cost={_lane_cost(r)}")
        if isinstance(resp, BaseException):
            bad.append(f"{tag}: raised {resp!r}")
            continue
        if resp.status != "exact":
            bad.append(f"{tag}: status {resp.status!r}")
        etag = (resp.explain or {}).get("engine_tag", "")
        if not etag.startswith("fused"):
            bad.append(f"{tag}: engine_tag {etag!r}")
        if resp.meta.get("engine") != "fused":
            bad.append(f"{tag}: solved by engine "
                       f"{resp.meta.get('engine')!r}")
        if not resp.cost == c.oracle:
            bad.append(f"{tag}: cost {resp.cost!r} != oracle {c.oracle!r}")
        t = resp.tree
        if t is None or not t.validate() or t.mask != (1 << r.q.n) - 1:
            bad.append(f"{tag}: no valid join tree")
        elif np.isfinite(resp.cost):
            card = np.asarray(r.card, np.float64)
            got = t.cost_max(card) if r.cost == "max" else t.cost_out(card)
            if (got != resp.cost if r.cost == "max" else
                    abs(got - resp.cost) > 1e-9 * max(1.0, abs(resp.cost))):
                bad.append(f"{tag}: tree costs {got!r}, reported "
                           f"{resp.cost!r}")
        if (r.cost == "max" and r.q.n in pallas_ns
                and resp.meta.get("backend") != "pallas"):
            bad.append(f"{tag}: backend {resp.meta.get('backend')!r}")
    for rt in runtimes:
        ladder = {k: v for k, v in rt.fstats.as_dict().items() if v}
        if ladder:
            bad.append(f"failure ladder used: {ladder}")
        if rt.breakers.opens:
            bad.append(f"{rt.breakers.opens} breaker opens")
    for k in ("host_extractions", "exec_cache_misses"):
        if counters.get(k):
            bad.append(f"engine {k} = {counters[k]} while serving")
    if not records:
        bad.append("no dispatch ran on the device")
    for rec in records:
        where = rec.devices[0] if rec.devices else None
        if where != platform:
            bad.append(f"dispatch {rec.seq} ({rec.cost} n={rec.n}) "
                       f"ran on {where!r}")
        if (rec.cost.startswith("max") and rec.n in pallas_ns
                and rec.backend != "pallas"):
            bad.append(f"dispatch {rec.seq} (max n={rec.n}) on backend "
                       f"{rec.backend!r}")
        if mesh and rec.shards == mesh and len(set(rec.devices[1])) != mesh:
            bad.append(f"dispatch {rec.seq} on devices {rec.devices}")
    if mesh and not any(rec.shards == mesh for rec in records):
        bad.append(f"no dispatch ran on the {mesh}-device mesh")
    return bad


def run(srv: PlanServer, phases: list, platform: str, pallas_ns=(),
        mesh: int = 0, log=print) -> "tuple[list[str], dict]":
    """Prewarm, serve, compare with the oracles and check.  Returns the
    failures and a summary of the counts."""
    warm = prewarm(srv, phases)
    log(f"prewarm: {warm['compiled']} executables in "
        f"{warm['seconds']:.2f} s")
    for m in sorted(engine_mod.compiled_buckets(),
                    key=lambda m: m["compile_s"], reverse=True):
        n, B, C, backend = m["key"][:4]
        log(f"  bucket cost={m['key'][6]} n={n} B={B} backend={backend} "
            f"shards={m['shards']} compile_s={m['compile_s']:.3f} "
            f"memory={m['memory']}")
    before = engine_mod.stats().as_dict()
    mark = engine_mod.dispatch_mark()
    t0 = time.perf_counter()
    cases, runtimes = serve(srv, phases)
    serve_s = time.perf_counter() - t0
    records = engine_mod.dispatches_since(mark)
    after = engine_mod.stats().as_dict()
    counters = {k: after[k] - before[k] for k in after}
    for c in cases:
        c.oracle = oracle(c.request)
    failures = check(cases, runtimes, records, counters, platform,
                     pallas_ns=pallas_ns, mesh=mesh)
    by_phase: dict = {}
    for c in cases:
        by_phase[c.phase] = by_phase.get(c.phase, 0) + 1
    execute_s: dict = {}                 # device wall seconds per bucket
    for r in records:
        k = f"{r.cost} n={r.n} B={r.B} {r.backend} shards={r.shards}"
        execute_s[k] = execute_s.get(k, 0.0) + r.execute_s
    summary = {"served": len(cases), "phases": by_phase,
               "serve_s": serve_s, "engine": counters,
               "dispatches": len(records),
               "execute_s": execute_s,
               "devices": sorted({r.devices for r in records}),
               "ladder": {k: sum(rt.fstats.as_dict()[k] for rt in runtimes)
                          for k in runtimes[0].fstats.as_dict()},
               "breaker_opens": sum(rt.breakers.opens for rt in runtimes),
               "exact_vs_oracle": sum(
                   not isinstance(c.response, BaseException)
                   and c.response.cost == c.oracle for c in cases)}
    return failures, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", type=int, default=0, choices=(0, 4),
                    help="serve only the 4-chip solve-mesh path")
    args = ap.parse_args(argv)
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX sees no TPU (platform {dev.platform!r}); "
              "nothing served", file=sys.stderr)
        return 2
    if interpret_requested():
        print(f"chip_smoke: {INTERPRET_ENV}=1 would run the kernels in "
              "interpret mode; unset it", file=sys.stderr)
        return 2
    cache = engine_mod.use_compile_cache(ROOT)   # before the first compile
    if len(devs) < max(args.mesh, 1):
        print(f"chip_smoke: --mesh {args.mesh} needs {args.mesh} chips, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {cache}", flush=True)
    sz = Sizes()
    t0 = time.perf_counter()
    if args.mesh:
        # the mesh lane serves fresh queries only, so no layer-cache
        # variants; one request per chunk keeps the compiles to six
        srv = PlanServer(max_batch=1, enable_layer_cache=False,
                         batch_policy=BatchPolicy(max_batch=1,
                                                  solve_shards=args.mesh))
        phases, pallas_ns = mesh_phases(sz), ()
    else:
        srv = PlanServer(max_batch=sz.group)
        phases, pallas_ns = one_chip_phases(sz), sz.pallas_ns
        missing = [n for n in pallas_ns if not srv.solver._use_pallas(n)]
        if missing:
            print(f"chip_smoke: the policy keeps n={missing} off the "
                  "Pallas tier", file=sys.stderr)
            return 1
    failures, summary = run(srv, phases, "tpu", pallas_ns=pallas_ns,
                            mesh=args.mesh)
    for k, v in summary.items():
        print(f"{k}: {v}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"wall_s: {time.perf_counter() - t0:.1f}")
    if failures:
        for f in failures:
            print(f"FAIL {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
