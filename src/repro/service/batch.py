"""Batched plan solving: stack same-``n`` queries, sweep the lattice once.

The DPconv inner loops are dense computations over the (2^n,) subset
lattice; with B queries of the same ``n`` the per-query tables stack to
(B, 2^n) and every lattice sweep (zeta transforms, ranked convolution,
Moebius, the (min,+) value pass) broadcasts over the batch axis — one
compiled program serves the whole micro-batch.  This module adds the
serving-side concerns:

* grouping a mixed micro-batch by ``(n, cost)`` and restoring request
  order — the batch lane carries ``cost="max"`` (DPconv[max]),
  ``cost="cap"`` (the fused two-pass C_cap lattice program) and
  ``cost="out"`` (the connectivity-masked DPccp-semantics C_out
  program) chunks alike;
* shape bucketing: each group is split into descending power-of-two
  chunks (11 -> [8, 2, 1] with cap 16), so the engine compiles
  O(log max_batch) batch shapes per ``n`` and no work is wasted on
  padding rows; size-1 chunks take the single-query path;
* the backend tier: mid-size lattices (``pallas_min_n <= n <=
  pallas_max_n``) run their transforms through the Pallas TPU kernels
  (``repro.kernels.ops``) on an int32 DP — exact for feasibility counts
  < 2^31, i.e. n <= 15 — while smaller/larger ``n`` stay on the XLA int64
  butterflies (exact to n = 26).  ``backend="auto"`` takes the Pallas
  tier on a TPU only; elsewhere the kernels run only when interpret mode
  is asked for (``repro.kernels.ops.INTERPRET_ENV``).
* the engine tier (``BatchPolicy.engine``, default ``"fused"``): each
  chunk's ENTIRE solve — search, gate construction, layered DP, and the
  Alg. 2 extraction scan — runs as one compiled lattice program with an
  AOT executable cache (``repro.core.engine``), so a chunk costs one
  device dispatch instead of ~n host-synced feasibility passes, and no
  per-solve host recursion.  ``engine="host"`` keeps the per-round host
  loop (parity reference, dp_fn experiments).
* the probe strategy (``BatchPolicy.gamma_batch``): G > 1 folds (G+1)-ary
  gamma probing into the fused while-loop body — G gates on a leading
  axis, ~log_{G+1} instead of ~log_2 rounds per solve, still one
  dispatch.  Fewer sequential rounds buys latency on parallel-rich
  hardware; on the CPU it shows in the rounds-per-solve counter
  (``tests/test_lattice_parity.py`` holds the reduction).

Parity: whatever the tier, results are bit-identical in cost AND tree to
single-query ``repro.core.dpconv.optimize`` — the candidate arrays and
search brackets are the same, feasibility is exact integer counting in
both dtypes, and the extraction witness rule matches the host extractors
(asserted by tests/test_service_batch.py and
tests/test_lattice_parity.py).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import engine as engine_mod
from repro.core.dpconv import PlanResult, optimize, optimize_batch
from repro.core.layered import layered_feasibility_dp_jit
from repro.kernels.ops import mobius_batch_op, ranked_conv_op, zeta_batch_op


@dataclasses.dataclass(frozen=True)
class BatchPolicy:
    max_batch: int = 16
    pallas_min_n: int = 12      # Pallas int32 tier lower bound
    pallas_max_n: int = 15      # exactness bound: 2^{2n} < 2^31
    backend: str = "auto"       # "auto" | "xla" | "pallas"
    # "auto" engages the Pallas tier only on a TPU; "pallas" forces it
    # anywhere (tests, which ask for interpret mode on the CPU).
    engine: str = "fused"       # "fused" | "host"
    # "fused" (default) runs each chunk's whole solve as ONE device
    # dispatch (repro.core.engine over the lattice-program layer);
    # "host" is the per-round host loop — kept as the parity reference
    # and for dp_fn-style experimentation.
    gamma_batch: int = 1        # fused probe width: 1 = binary search,
    # G > 1 = (G+1)-ary gamma probing inside the fused while loop
    solve_shards: int = 1       # solve-mesh width: D > 1 shard_maps each
    # fused sweep over D devices (repro.core.mesh.make_solve_mesh) —
    # per-device layer memory drops 1/D, which is what lifts the fused
    # cap/out ceilings past n = 13 (engine.sharded_ceiling)
    shard_min_n: int = 14       # engage the mesh only at n >= this:
    # below the single-device ceiling the per-layer collectives cost
    # more than the memory relief buys

    def __post_init__(self):
        if self.engine not in ("fused", "host"):
            raise ValueError(f"unknown engine {self.engine!r}")
        if self.backend not in ("auto", "xla", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.gamma_batch < 1:
            raise ValueError("gamma_batch must be >= 1")
        if self.solve_shards < 1:
            raise ValueError("solve_shards must be >= 1")


def _pow2_chunks(b: int, cap: int):
    """Decompose b into descending power-of-two chunk sizes <= cap, so
    jit only ever sees O(log cap) batch shapes per ``n`` and no padding
    work is wasted (5 -> [4, 1], 11 -> [8, 2, 1] with cap 8).  A
    non-power-of-two cap is clamped down so the contract holds for any
    BatchPolicy.max_batch."""
    cap = 1 << (cap.bit_length() - 1)
    out = []
    while b:
        c = min(1 << (b.bit_length() - 1), cap)
        out.append(c)
        b -= c
    return out


def pallas_dp_fn(n: int, direct_layers: int = 4):
    """Feasibility-pass backend running zeta/Moebius — and the
    middle-layer ranked convolutions — on the Pallas kernels.

    The gate is cast to int32 (feasibility is {0,1}-counting; exact while
    counts < 2^31, enforced by BatchPolicy.pallas_max_n) and the layered
    DP runs with the batched kernel wrappers as its transform backend.
    """
    def dp_fn(gate: jnp.ndarray, final_layer_shortcut: bool) -> jnp.ndarray:
        g = gate.astype(jnp.int32)
        dp = layered_feasibility_dp_jit(
            g, n, direct_layers, final_layer_shortcut,
            zeta_fn=zeta_batch_op, mobius_fn=mobius_batch_op,
            ranked_conv_fn=ranked_conv_op)
        return dp.astype(jnp.float64)
    return dp_fn


def _unpack(item):
    """items are (q, card[, cost[, tag[, seed]]]) — cost defaults to
    "max", ``tag`` is an opaque attribution label (the server passes the
    topology class) threaded back through ``last_timings``, and ``seed``
    is the layer cache's warm-start payload for this query (None cold;
    ``{"opt": float}`` collapses the max/cap search bracket,
    ``{"vals": (2^n,) f64, "ok": (2^n,) bool}`` replays cached
    sub-table values inside the out sweep).  Seeds are perf hints: the
    solvers produce bit-identical results with or without them."""
    q, card = item[0], item[1]
    cost = item[2] if len(item) > 2 else "max"
    tag = item[3] if len(item) > 3 else ""
    seed = item[4] if len(item) > 4 else None
    return q, card, cost, tag, seed


@dataclasses.dataclass
class SolveHandle:
    """A submitted-but-not-yet-collected batched solve.

    ``submit`` captures the work; ``collect`` executes it (and is where
    ``last_timings`` is refreshed).  The split exists for the async
    runtime (``repro.service.runtime``): its executor can carry the
    handle onto a worker thread and run ``collect`` there, so the
    scheduler keeps admitting requests and forming the NEXT micro-batch
    while the current dispatch executes — batch formation overlaps the
    in-flight solve instead of serializing behind it.
    """
    items: list
    extract_tree: bool = True
    results: "list | None" = None
    timings: "list | None" = None        # this solve's last_timings slice


class BatchedSolver:
    """Groups micro-batch items by ``(n, cost)`` and dispatches the
    batched lattice programs."""

    def __init__(self, policy: "BatchPolicy | None" = None,
                 lane: int = 0):
        import threading
        self.policy = policy or BatchPolicy()
        if self.policy.solve_shards > 1:
            # the server lifts its lane ceilings for this width, so a
            # mesh narrower than the policy would serve past them
            import jax
            have = len(jax.devices())
            if self.policy.solve_shards > have:
                raise ValueError(
                    f"solve_shards={self.policy.solve_shards} but only "
                    f"{have} {jax.default_backend()} device(s) are visible")
        # one solver models ONE solve lane (the N-lane runtime owns one
        # BatchedSolver per lane); the async runtime's worker thread and
        # a sync front end (plan_one / serve) on the same server may
        # both reach solve(), so the lane is a real lock — it also
        # keeps last_timings snapshots from interleaving (an interleaved
        # snapshot would feed another solve's durations into the
        # router's EWMA).  RLock: collect() holds it across solve()
        # plus the timings snapshot.
        self._lock = threading.RLock()
        self.lane = lane            # engine-dispatch attribution label
        self.batches_run = 0
        self.queries_batched = 0
        # cumulative solver-lane totals (all chunks ever solved): the
        # benchmark reports batch-lane throughput from these, independent
        # of the Python serving overhead around the solver
        self.total_solve_s = 0.0
        self.total_solved = 0
        # (n, queries, seconds, engine, cost, tag_counts) per chunk of
        # the last solve() call — the server feeds these to the router's
        # latency model per-``n``, per-engine AND per-topology-class
        # (one mixed micro-batch spans several n's; fused/host-loop
        # latencies differ by the per-round dispatch overhead; and a
        # clique chunk must not pollute a chain chunk's coefficient)
        self.last_timings: list = []

    def _use_pallas(self, n: int) -> bool:
        p = self.policy
        if p.backend == "pallas":
            # even when forced, never exceed the int32 exactness bound —
            # beyond it overflowed counts would silently corrupt plans
            return n <= p.pallas_max_n
        if p.backend == "auto":
            import jax
            return (jax.default_backend() == "tpu"
                    and p.pallas_min_n <= n <= p.pallas_max_n)
        return False

    def _dp_fn(self, n: int):
        if self._use_pallas(n):
            return pallas_dp_fn(n)
        return None                      # core default: XLA layered DP

    def _shards(self, n: int) -> int:
        """Solve-mesh width for one chunk: the policy's width, engaged
        only at ``n >= shard_min_n``."""
        p = self.policy
        if p.solve_shards <= 1 or n < p.shard_min_n:
            return 1
        return p.solve_shards

    def _solve_chunk(self, qs, cards, n, cost, extract_tree,
                     seeds=None):
        """One same-(n, cost) chunk through the routed engine tier.

        ``seeds`` — per-query warm-start payloads (see ``_unpack``),
        threaded into the fused engine only: the host tiers have no
        seed slot and results must not depend on them anyway."""
        engine = self.policy.engine
        G = self.policy.gamma_batch
        # the Pallas tier serves max chunks; the cap and out programs'
        # (min,+) passes run on XLA only
        backend = ("pallas" if cost == "max" and self._use_pallas(n)
                   else "xla")
        shards = self._shards(n)
        seeds = seeds or [None] * len(qs)
        seed_kw: dict = {}
        if engine == "fused" and any(s is not None for s in seeds):
            if cost == "out":
                if any(s and s.get("ok") is not None for s in seeds):
                    size = 1 << n
                    sv = np.zeros((len(qs), size), np.float64)
                    so = np.zeros((len(qs), size), bool)
                    for b, s in enumerate(seeds):
                        if s and s.get("ok") is not None:
                            sv[b] = s["vals"]
                            so[b] = s["ok"]
                    seed_kw = {"seed_vals": sv, "seed_ok": so}
            else:
                opts = [s.get("opt") if s else None for s in seeds]
                if any(o is not None for o in opts):
                    seed_kw = {"seed_opt": opts}
        # the batch lane carries four costs; "out" chunks run DPccp
        # semantics (connected csg/cmp pairs, no cross products), and
        # "cap_conn" is the cap lane with the no-cross-products pass 2
        # (PlanRequest.connected): solved as cost="cap" + connected=True,
        # but grouped/priced/cached under its own lane-cost label
        method = "dpccp" if cost == "out" else "dpconv"
        solve_cost, conn_kw = (("cap", {"connected": True})
                               if cost == "cap_conn" else (cost, {}))
        if len(qs) == 1:
            # BatchPolicy.engine is "fused" | "host", and all three
            # optimize entry points (dpconv_max, ccap, dpccp) understand
            # both values
            kw = {"engine": engine}
            if engine == "fused" and shards > 1:
                kw["shards"] = shards
            if engine == "fused" and cost != "out":
                kw["gamma_batch"] = G   # out's (min,+) sweep never probes
                if cost == "max":   # cap's (min,+) pass is XLA-only
                    kw["backend"] = backend
            if seed_kw:             # single-query slice of the batch seed
                if "seed_opt" in seed_kw:
                    kw["seed_opt"] = seed_kw["seed_opt"][0]
                else:
                    kw["seed_vals"] = seed_kw["seed_vals"][0]
                    kw["seed_ok"] = seed_kw["seed_ok"][0]
            res = optimize(qs[0], cards[0], cost=solve_cost, method=method,
                           extract_tree=extract_tree, **kw, **conn_kw)
            res.meta["backend"] = kw.get("backend", "xla")
            res.meta["batched"] = False
            res.meta["chunk"] = 1
            return [res]
        if cost == "out":
            # optimize_batch runs the whole chunk as ONE fused dispatch;
            # with engine="host" — or when a disconnected/hyperedge chunk
            # member voids the DPccp search space — it loops per-query
            # host enumerations: B independent solves, accounted as
            # chunk-1 solves like the host cap pipeline
            results = optimize_batch(qs, cards, cost="out",
                                     method="dpccp",
                                     extract_tree=extract_tree,
                                     engine=engine, shards=shards,
                                     **seed_kw)
            if not results[0].meta.get("batched"):
                for res in results:
                    res.meta["backend"] = "xla"
                    res.meta["batched"] = False
                    res.meta["chunk"] = 1
                return results
        elif solve_cost == "cap":
            if engine == "fused":
                results = optimize_batch(qs, cards, cost="cap",
                                         extract_tree=extract_tree,
                                         gamma_batch=G, shards=shards,
                                         **conn_kw, **seed_kw)
            else:
                # the host cap pipeline has no lockstep form: these are
                # B independent solves sharing only the wall-clock
                # window, so they must NOT be accounted as one batched
                # solve (per-solve counters weight by 1/chunk)
                results = [optimize(q, c, cost="cap",
                                    extract_tree=extract_tree,
                                    engine="host", **conn_kw)
                           for q, c in zip(qs, cards)]
                for res in results:
                    res.meta["backend"] = backend
                    res.meta["batched"] = False
                    res.meta["chunk"] = 1
                return results
        elif engine == "fused":
            results = optimize_batch(qs, cards, cost="max",
                                     extract_tree=extract_tree,
                                     engine="fused", backend=backend,
                                     gamma_batch=G, shards=shards,
                                     **seed_kw)
        else:
            results = optimize_batch(qs, cards, cost="max",
                                     extract_tree=extract_tree,
                                     engine="host", dp_fn=self._dp_fn(n))
        self.batches_run += 1
        self.queries_batched += len(qs)
        for res in results:
            res.meta["backend"] = backend
            # all chunk members share one solve; consumers averaging
            # per-solve counters weight by 1/chunk
            res.meta["chunk"] = len(qs)
        return results

    # ------------------------------------------------- submit / collect
    def submit(self, items: list, extract_tree: bool = True
               ) -> SolveHandle:
        """Stage a batched solve without running it.  Pair with
        ``collect`` — possibly from another thread — to execute it; the
        runtime uses this split to overlap batch formation with the
        executing dispatch."""
        return SolveHandle(items=list(items), extract_tree=extract_tree)

    def collect(self, handle: SolveHandle) -> list:
        """Execute (once) and return a submitted solve's results.  The
        handle's ``timings`` snapshots this solve's ``last_timings``
        rows, so concurrent collectors don't race on the shared list."""
        with self._lock:
            if handle.results is None:
                handle.results = self.solve(
                    handle.items, extract_tree=handle.extract_tree)
                handle.timings = list(self.last_timings)
        return handle.results

    def solve(self, items: list, extract_tree: bool = True) -> list:
        """``items``: list of (q, card[, cost[, tag[, seed]]]) tuples;
        cost is "max", "cap", "cap_conn" or "out" (the lattice
        batch-lane costs), ``seed`` the optional layer-cache warm-start
        payload.  Returns PlanResults aligned with the input order."""
        # dispatch_lane stamps this solver's lane onto every
        # DispatchRecord the chunk solves emit — the N-lane runtime owns
        # one BatchedSolver per lane, so engine profiling splits cleanly
        # per lane without threading a label through every optimize call
        with self._lock, engine_mod.dispatch_lane(self.lane):
            return self._solve_locked(items, extract_tree)

    def _solve_locked(self, items: list, extract_tree: bool) -> list:
        import time

        groups: dict = {}
        for idx, item in enumerate(items):
            q, card, cost, tag, seed = _unpack(item)
            groups.setdefault((q.n, cost), []).append(
                (idx, q, card, tag, seed))
        out: list = [None] * len(items)
        self.last_timings = []
        for (n, cost), group in sorted(groups.items()):
            lo = 0
            for chunk in _pow2_chunks(len(group), self.policy.max_batch):
                part = group[lo:lo + chunk]
                lo += chunk
                idxs = [g[0] for g in part]
                qs = [g[1] for g in part]
                cards = [np.asarray(g[2], np.float64) for g in part]
                seeds = [g[4] for g in part]
                tags: dict = {}
                for g in part:
                    tags[g[3]] = tags.get(g[3], 0) + 1
                t0 = time.perf_counter()   # timing: measured-duration (chunk solve)
                results = self._solve_chunk(qs, cards, n, cost,
                                            extract_tree, seeds=seeds)
                for idx, res in zip(idxs, results):
                    out[idx] = res
                dt = time.perf_counter() - t0  # timing: measured-duration
                self.total_solve_s += dt
                self.total_solved += chunk
                # attribute to the engine that actually ran, not the
                # policy ask — a fused-policy out chunk can fall back to
                # the host enumerator (disconnected/hyperedge member),
                # whose #ccp-scaling latency must not price the fused
                # EWMA coefficient
                eng = results[0].meta.get("engine", self.policy.engine)
                self.last_timings.append((n, chunk, dt, eng, cost, tags))
        return out
