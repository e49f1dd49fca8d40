"""Fused on-device DPconv engines (DESIGN.md §Fused-engine).

The host-loop solvers (``dpconv_max`` / ``dpconv_max_batch`` / ``ccap``)
dispatch one feasibility sweep per search round and sync the verdict back
to the host between rounds: ~n device round trips per solve, each paying
dispatch latency plus Python gate rebuilding.  At serving batch sizes
that overhead dominates the actual lattice arithmetic.

This module is the *execution tier* over the lattice-program layer
(``repro.core.lattice``): it pads batched queries into power-of-two
shape buckets, AOT-compiles the whole-solve programs, caches the
executables, and counts every device execution.  The programs themselves
— lockstep (G+1)-ary search, scan-form layered DP, the (min,+) C_cap
value pass, the connectivity-masked C_out sweep, and the Alg. 2
extraction scan — are built by ``lattice.build_max_program`` /
``lattice.build_cap_program`` / ``lattice.build_out_program``; one
batched solve is ONE dispatch for every cost function and probe
strategy, including tree extraction (no per-solve host recursion: the
host only assembles ``JoinTree`` objects from the returned split
arrays).

Executables are cached by ``(n, B_bucket, C_bucket, backend,
direct_layers, extract, cost, gamma_batch, shards, mesh-fingerprint)``
as ahead-of-time compiled artifacts (``jit(...).lower(...).compile()``),
so the serving tier never re-traces in steady state — and sharded /
single-device builds (or the same width on different devices) can never
alias one cache slot; ``prewarm`` compiles the buckets a configured
server can hit before traffic arrives (killing the cold-bucket p99
spike), and ``stats()`` exposes dispatch/solve/round counters that
``benchmarks/serve_bench.py`` asserts on.

Exactness: identical to the host paths — all feasibility values are
exact integer counts (int64 up to n = 26 on the XLA backend, int32 up to
n = 15 on the Pallas backend), the G = 1 probe sequence is the host's
lockstep pivot sequence, every f64 crosses to the device and back as
int64 bits and the (min,+) pass reproduces DPsub[out]'s f64 operations
bit for bit (``f64bits``: a TPU's own f64 keeps ~48 significand bits),
and the extraction scan applies the host extractors'
witness rule, so optima, C_out values and join trees are bit-identical
(tests/test_engine.py, tests/test_lattice_parity.py, and the
serve_bench parity sweep).
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import f64bits, jointree, lattice
from repro.core.bitset import popcounts
from repro.core.lattice import BACKENDS  # noqa: F401  (re-export)
from repro.obs import metrics as obs_metrics


# ----------------------------------------------------------------- telemetry
class EngineStats:
    """Engine counters, registry-backed and thread-safe.

    Counts now live as ``engine.<field>`` counters in a
    ``MetricsRegistry`` (the process-default one for the module-global
    instance), so increments from the runtime's worker-thread executor
    are atomic instead of racing ``+=`` on a bare dataclass.  Field
    reads (``stats().dispatches``) and ``as_dict()`` keep the exact
    shape every existing caller expects.
    """

    FIELDS = (
        "dispatches",          # device executions (counted at exe call)
        "solves",              # batched solves served
        "queries",             # real (un-padded) queries planned
        "rounds",              # total while-loop rounds across solves
        "exec_cache_hits",     # executable reused without re-tracing
        "exec_cache_misses",   # shape-bucket combos compiled
        "prewarmed",           # executables compiled by prewarm()
        "host_extractions",    # per-solve host recursions (must stay 0)
    )

    def __init__(self, registry: "obs_metrics.MetricsRegistry | None"
                 = None):
        self.registry = registry or obs_metrics.MetricsRegistry()
        self._c = {f: self.registry.counter("engine." + f)
                   for f in self.FIELDS}

    def inc(self, field: str, k: int = 1) -> None:
        self._c[field].inc(k)

    def __getattr__(self, name):
        # only reached for names not set in __init__ — the counter reads
        if name in EngineStats.FIELDS:
            return self._c[name].value
        raise AttributeError(name)

    def as_dict(self) -> dict:
        return {f: self._c[f].value for f in self.FIELDS}

    def reset(self) -> None:
        for c in self._c.values():
            c.reset()


@dataclasses.dataclass
class DispatchRecord:
    """Per-dispatch profile: one row per device execution, ring-buffered.

    The serving runtime marks the ring before handing work to the
    solver (``dispatch_mark``) and collects the records that landed
    while it waited (``dispatches_since``), attributing compile/execute
    split, while-loop rounds and XLA flops/bytes to the request spans
    that were blocked on that dispatch.
    """
    seq: int                   # monotone id (ring position survives wrap)
    cost: str                  # "max" | "cap" | "cap_conn" | "out[_seeded]"
    n: int
    B: int                    # padded batch bucket
    C: int                    # candidate bucket (0 for the out program)
    backend: str
    key: tuple                 # full executable-cache bucket key
    aot_cache_hit: bool        # executable reused (no compile this call)
    compile_s: float           # 0.0 on a cache hit
    execute_s: float           # blocked-until-ready device wall time
    rounds: int = 0            # while-loop rounds (filled post-solve)
    flops: "float | None" = None  # XLA cost analysis, whole program;
    bytes_accessed: "float | None" = None  # None where XLA reports none
    shards: int = 1            # solve-mesh width (1 = single device)
    devices: tuple = ()        # mesh device ids ((platform, ids) pair)
    lane: "int | None" = None  # serving lane that issued the dispatch

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["key"] = list(self.key)
        d["devices"] = list(self.devices)
        return d


_STATS = EngineStats(obs_metrics.default_registry())
_EXEC_CACHE: dict = {}
_EXEC_META: dict = {}          # key -> {"compile_s", "flops", ...}
_PROFILE: collections.deque = collections.deque(maxlen=512)
_PROFILE_LOCK = threading.Lock()
_PROFILE_SEQ = 0
# one trace at a time: programs traced concurrently in one process lower
# to modules that differ from run to run, so the persistent compile
# cache never finds them again; compiles still overlap
_TRACE_LOCK = threading.Lock()


def stats() -> EngineStats:
    return _STATS


def reset_stats() -> None:
    _STATS.reset()


def dispatch_mark() -> int:
    """Current profile sequence number; pass to ``dispatches_since``."""
    with _PROFILE_LOCK:
        return _PROFILE_SEQ


def dispatches_since(mark: int) -> "list[DispatchRecord]":
    """Profile records appended after ``mark`` (oldest first), as far
    back as the ring still holds them."""
    with _PROFILE_LOCK:
        return [r for r in _PROFILE if r.seq > mark]


def _profile_append(rec: DispatchRecord) -> None:
    global _PROFILE_SEQ
    with _PROFILE_LOCK:
        _PROFILE_SEQ += 1
        rec.seq = _PROFILE_SEQ
        _PROFILE.append(rec)
    h = _STATS.registry.histogram
    h("engine.execute_s").observe(rec.execute_s)
    if not rec.aot_cache_hit:
        h("engine.compile_s").observe(rec.compile_s)
    if rec.lane is not None:   # per-lane dimension on the dispatch count
        _STATS.registry.counter(f"engine.dispatches.lane{rec.lane}").inc()


_LANE_LOCAL = threading.local()


class dispatch_lane:
    """Context manager attributing engine dispatches to a serving lane.

    The lane is an N-lane-runtime concept the solver call chain has no
    business threading through every ``optimize`` signature, so it rides
    a thread-local instead: each lane's executor (or the batched solver
    it owns) wraps its solve in ``with engine.dispatch_lane(k)`` and
    every ``DispatchRecord`` produced inside carries ``lane=k`` — the
    flight recorder and the per-lane ``engine.dispatches.lane<k>``
    counters can then explain which lane ran what.  Reentrant-safe by
    save/restore; thread-safe because each executor thread has its own
    slot."""

    def __init__(self, lane: "int | None"):
        self.lane = lane

    def __enter__(self):
        self._prev = getattr(_LANE_LOCAL, "lane", None)
        _LANE_LOCAL.lane = self.lane
        return self

    def __exit__(self, *exc):
        _LANE_LOCAL.lane = self._prev
        return False


def current_lane() -> "int | None":
    return getattr(_LANE_LOCAL, "lane", None)


def clear_executable_cache() -> None:
    _EXEC_CACHE.clear()
    _EXEC_META.clear()


_COMPILE_FAULT_HOOK = None


def set_compile_fault_hook(hook) -> None:
    """Chaos/test seam for AOT compilation: ``hook(n=..., B=..., C=...,
    backend=..., cost=...)`` is called on every executable-cache MISS,
    before tracing starts, and may raise to model a compile failure
    (``repro.service.faults`` wires its injector here).  ``None``
    clears.  Warm buckets never hit the seam — exactly like the real
    failure mode, which only exists on the compile path."""
    global _COMPILE_FAULT_HOOK
    _COMPILE_FAULT_HOOK = hook


# ------------------------------------------------------------------ results
@dataclasses.dataclass
class FusedSolve:
    """One fused batched solve: B optima (+trees) from one dispatch."""
    optima: np.ndarray             # (B,) optimal C_max values
    trees: list                    # JoinTree | None per query
    rounds: int                    # while-loop iterations (lockstep)
    passes: int                    # rounds + extraction pass, host parity
    dispatches: int = 1            # device executions measured (1 fused)
    dp: "np.ndarray | None" = None  # (B, 2^n) extraction feasibility table
    extraction: str = "device"     # where Alg. 2 ran
    seeded: int = 0                # rows whose search bracket was seeded


@dataclasses.dataclass
class FusedOutSolve:
    """One fused batched connected-C_out solve (DPccp semantics): B
    optima + trees from one dispatch over the connectivity-masked
    (min,+) lattice program."""
    couts: np.ndarray              # (B,) optimal C_out, no cross products
    trees: list                    # JoinTree | None per query
    dispatches: int = 1
    dp: "np.ndarray | None" = None  # (B, 2^n) value table (+inf outside
    extraction: str = "device"      # the connected sets)
    seeded: int = 0                # rows carrying cached sub-table seeds


@dataclasses.dataclass
class FusedCapSolve:
    """One fused batched C_cap solve: both passes + extraction, one
    dispatch."""
    gammas: np.ndarray             # (B,) caps (= slack * optimal C_max)
    couts: np.ndarray              # (B,) optimal C_out under the cap
    trees: list                    # JoinTree | None per query
    rounds: int                    # pass-1 search rounds (lockstep)
    dispatches: int = 1
    extraction: str = "device"
    seeded: int = 0                # rows whose search bracket was seeded


# ----------------------------------------------------------- program cache
def _next_pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


_SOLVE_MESHES: dict = {}


def solve_mesh(shards: int):
    """The cached 1-D solve mesh for ``shards`` devices (one per width —
    meshes are hashable but building one touches device state, so the
    engine owns the lookup)."""
    m = _SOLVE_MESHES.get(shards)
    if m is None:
        from repro.launch.mesh import make_solve_mesh
        m = _SOLVE_MESHES[shards] = make_solve_mesh(shards)
    return m


def _mesh_identity(shards: int) -> tuple:
    """The device/mesh identity appended to every executable-cache key
    (and stamped on ``DispatchRecord.devices``): sharded and
    single-device executables — or the same width on *different*
    devices — must never alias.  Single-device solves are keyed by the
    default device's identity for the same reason."""
    from repro.launch.mesh import mesh_fingerprint
    if shards > 1:
        return mesh_fingerprint(solve_mesh(shards))
    d = jax.devices()[0]
    return (d.platform, (int(d.id),))


def sharded_ceiling(base_n: int, shards: int) -> int:
    """How far a D-way solve mesh lifts a fused-tier ``n`` ceiling.

    The ceiling is per-device memory on the dominant (min,+) layer
    tensor ``C(n,k)·2^k`` ≈ 3^n/√n; sharding divides it by D, and each
    +1 in n multiplies it by 3, so D devices buy ~log₃(D) ≈ log₂(D)/1.58
    extra relations — claim a conservative +1 per doubling, clamped at
    the int32/extraction tier bound n = 15.
    """
    if shards <= 1:
        return base_n
    return min(base_n + max(0, int(shards).bit_length() - 1), 15)


def get_executable(n: int, B: int, C: int, backend: str = "xla",
                   direct_layers: int = 4, extract: bool = True,
                   cost: str = "max", gamma_batch: int = 1,
                   shards: int = 1):
    """AOT-compiled whole-solve executable for one shape bucket.

    Keyed by ``(n, B_bucket, C_bucket, backend, direct_layers, extract,
    cost, gamma_batch, shards, mesh-fingerprint)``; a hit returns the
    compiled artifact with zero tracing work — the steady-state serving
    path never re-enters the tracer.
    """
    return _executable(n, B, C, backend, direct_layers, extract, cost,
                       gamma_batch, shards)[0]


def _executable(n: int, B: int, C: int, backend: str, direct_layers: int,
                extract: bool, cost: str, gamma_batch: int,
                shards: int = 1):
    """Cache lookup + compile with profiling: returns ``(exe, meta,
    hit)`` where ``meta`` carries the bucket key, one-time compile
    seconds, XLA flops/bytes and the lattice program card."""
    shards = max(1, int(shards))
    devs = _mesh_identity(shards)
    key = (n, B, C, backend, direct_layers, bool(extract), cost,
           gamma_batch, shards, devs)
    exe = _EXEC_CACHE.get(key)
    if exe is not None:
        _STATS.inc("exec_cache_hits")
        return exe, _EXEC_META[key], True
    if _COMPILE_FAULT_HOOK is not None:
        _COMPILE_FAULT_HOOK(n=n, B=B, C=C, backend=backend, cost=cost)
    _STATS.inc("exec_cache_misses")
    mesh = solve_mesh(shards) if shards > 1 else None
    t0 = time.perf_counter()  # timing: measured-duration (compile wall)
    # every f64 crosses to the device as int64 bits (``f64bits``): the
    # chip would otherwise keep only ~48 of the 53 significand bits
    bits = jnp.int64
    args = [
        jax.ShapeDtypeStruct((B, 1 << n), bits),      # cards
        jax.ShapeDtypeStruct((B, C), bits),           # cand
        jax.ShapeDtypeStruct((B,), jnp.int32),   # lo0 (warm-start floor)
        jax.ShapeDtypeStruct((B,), jnp.int32),   # hi0
    ]
    # "<cost>_seeded" labels select the layer-cache warm-start variants:
    # same AOT signature, but the search runs the one-probe seed
    # verification (``_fused_search(verify_seed=True)``).  A distinct
    # label keeps each in its own executable-cache slot so the cold
    # programs never recompile.
    seeded = cost.endswith("_seeded") and cost != "out_seeded"
    base_cost = cost[: -len("_seeded")] if seeded else cost
    if base_cost == "max":
        fn = lattice.build_max_program(n, direct_layers, backend, extract,
                                       gamma_batch, shards=shards,
                                       mesh=mesh, seeded=seeded)
    elif base_cost == "cap":
        fn = lattice.build_cap_program(n, direct_layers, backend, extract,
                                       gamma_batch, shards=shards,
                                       mesh=mesh, seeded=seeded)
        args.append(jax.ShapeDtypeStruct((B, C), bits))   # caps
    elif base_cost == "cap_conn":
        # the no-cross-products cap: pass 2 under connected-split masks
        # (the same ``conn`` input the out program consumes)
        fn = lattice.build_cap_program(n, direct_layers, backend, extract,
                                       gamma_batch, connected=True,
                                       shards=shards, mesh=mesh,
                                       seeded=seeded)
        args.append(jax.ShapeDtypeStruct((B, C), bits))   # caps
        args.append(jax.ShapeDtypeStruct((B, 1 << n), jnp.bool_))
    elif cost == "out":
        # the connected C_out program has no search loop and no candidate
        # table: its inputs are the cardinality tables and the per-query
        # connected-subset masks.  Callers key it with the canonical
        # (C=0, backend="xla", gamma_batch=1) tuple — the (min,+) sweep
        # is f64-only and probes nothing.
        fn = lattice.build_out_program(n, extract, shards=shards,
                                       mesh=mesh)
        args = [
            jax.ShapeDtypeStruct((B, 1 << n), bits),
            jax.ShapeDtypeStruct((B, 1 << n), jnp.bool_),
        ]
    elif cost == "out_seeded":
        # the layer-cache variant of the out program: two extra inputs
        # carry cached sub-table values and their validity mask.  A
        # distinct cost label keeps it in its own executable-cache slot —
        # the cold out program's AOT signature never changes.
        fn = lattice.build_out_program(n, extract, shards=shards,
                                       mesh=mesh, seeded=True)
        args = [
            jax.ShapeDtypeStruct((B, 1 << n), bits),
            jax.ShapeDtypeStruct((B, 1 << n), jnp.bool_),
            jax.ShapeDtypeStruct((B, 1 << n), bits),
            jax.ShapeDtypeStruct((B, 1 << n), jnp.bool_),
        ]
    else:
        raise ValueError(f"unknown fused cost {cost!r}")
    with _TRACE_LOCK:
        lowered = jax.jit(fn).lower(*args)
    exe = lowered.compile()
    meta = {"key": key, "shards": shards, "devices": devs,
            # timing: measured-duration (AOT compile)
            "compile_s": time.perf_counter() - t0,
            "program": lattice.program_card(n, cost, backend=backend,
                                            gamma_batch=gamma_batch,
                                            extract=bool(extract),
                                            shards=shards),
            **_cost_analysis(exe), "memory": _memory_analysis(exe)}
    _EXEC_CACHE[key] = exe
    _EXEC_META[key] = meta
    return exe, meta, False


def _cost_analysis(exe) -> dict:
    """XLA's whole-program ``flops`` and ``bytes_accessed`` for one
    executable.  A figure XLA does not report is None, never 0, so a
    reader cannot mistake a missing analysis for a free program."""
    try:
        ca = exe.cost_analysis() or {}
    except (NotImplementedError, jax.errors.JaxRuntimeError):
        ca = {}

    def get(k):
        return float(ca[k]) if ca.get(k) is not None else None
    return {"flops": get("flops"), "bytes_accessed": get("bytes accessed")}


def _memory_analysis(exe) -> "dict | None":
    """The executable's device-memory footprint in bytes, as XLA's
    ``memory_analysis()`` reports it (None where it reports nothing)."""
    try:
        m = exe.memory_analysis()
    except (NotImplementedError, jax.errors.JaxRuntimeError):
        return None
    if m is None:
        return None
    return {"argument": int(m.argument_size_in_bytes),
            "output": int(m.output_size_in_bytes),
            "temp": int(m.temp_size_in_bytes),
            "generated_code": int(m.generated_code_size_in_bytes)}


def use_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, where it is
    set, wins (JAX reads it itself, so nothing is set here); otherwise
    the cache lives at ``<root>/.jax_cache`` — one fixed path, because
    the path is part of what a later run must find again.  Call before
    the first compile.  Library code and tests never call this."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def compiled_buckets() -> "list[dict]":
    """One dict per compiled executable: its bucket ``key``, compile
    seconds, XLA cost figures, ``memory`` bytes and lattice program
    card."""
    return [dict(m) for m in list(_EXEC_META.values())]


def candidate_bucket(n: int) -> int:
    """The canonical candidate-table width for lattice size ``n``.

    Candidate tables are always padded to this single per-``n`` bucket
    (``2^n - n - 1`` distinct |S| >= 2 cardinalities at most, rounded up
    to a power of two).  Padding costs a trivially larger (B, C) gather
    buffer — the layered DP's work is independent of C — and buys the
    serving tier a *closed* executable space keyed by (n, B_bucket)
    alone: ``prewarm`` can compile every bucket a configured server will
    ever hit, so no arrival pattern can run into a cold candidate
    bucket (the p99 spike serve_bench's cold-latency row measures).
    """
    return _next_pow2(max((1 << n) - n - 1, 1))


PREWARM_THREADS = 4            # concurrent bucket compiles in ``prewarm``


def prewarm_jobs(ns, max_batch: int = 16, backend: str = "xla",
                 direct_layers: int = 4, costs=("max",),
                 gamma_batch: int = 1, extract: bool = True,
                 shards: int = 1) -> list:
    """The executable buckets a server configured for ``ns`` can hit:
    for each ``n``, every power-of-two batch bucket up to ``max_batch``
    (including the chunk-1 tier) at the canonical candidate bucket, as
    ``get_executable`` argument tuples for ``compile_jobs``."""
    jobs = []
    for n in ns:
        b = 1
        while b <= max_batch:
            for cost in costs:
                if cost in ("out", "out_seeded"):  # no candidates/probes
                    jobs.append((n, b, 0, "xla", 4, extract, cost, 1,
                                 shards))
                else:
                    jobs.append((n, b, candidate_bucket(n), backend,
                                 direct_layers, extract, cost, gamma_batch,
                                 shards))
            b *= 2
    return jobs


def compile_jobs(jobs: list) -> dict:
    """Compile ``prewarm_jobs`` buckets before traffic arrives, up to
    ``PREWARM_THREADS`` at once: XLA compiles outside the GIL, and on a
    TPU host a cold prewarm is otherwise minutes of one busy core.
    Returns ``{"compiled": k, "seconds": s}``; already-cached buckets
    are free."""
    t0 = time.perf_counter()  # timing: measured-duration (prewarm wall)
    before = _STATS.exec_cache_misses
    if jobs:
        with ThreadPoolExecutor(min(len(jobs), PREWARM_THREADS)) as pool:
            list(pool.map(lambda j: get_executable(*j), jobs))
    compiled = _STATS.exec_cache_misses - before
    _STATS.inc("prewarmed", compiled)
    return {"compiled": compiled,
            # timing: measured-duration (prewarm)
            "seconds": time.perf_counter() - t0}


# -------------------------------------------------------------- entry point
def _run(exe, *args, record: "DispatchRecord | None" = None):
    """The single device-execution site: every XLA invocation the engine
    ever makes goes through here, so ``stats().dispatches`` is a real
    execution count (the dispatches-per-solve acceptance check would
    catch a future change that sneaks in a second call per solve).

    With a ``record``, the call blocks until the outputs are ready so
    ``execute_s`` is real device wall time (the fused solvers consume
    the outputs on the host immediately anyway), and the record lands
    in the profile ring.
    """
    _STATS.inc("dispatches")
    t0 = time.perf_counter()  # timing: measured-duration (execute wall)
    out = exe(*args)
    if record is not None:
        jax.block_until_ready(out)
        record.execute_s = time.perf_counter() - t0  # timing: measured-duration
        _profile_append(record)
    return out


def _record(cost: str, n: int, Bp: int, C: int, backend: str,
            meta: dict, hit: bool) -> DispatchRecord:
    return DispatchRecord(seq=0, cost=cost, n=n, B=Bp, C=C,
                          backend=backend, key=meta["key"],
                          aot_cache_hit=hit,
                          compile_s=0.0 if hit else meta["compile_s"],
                          execute_s=0.0, flops=meta["flops"],
                          bytes_accessed=meta["bytes_accessed"],
                          shards=meta.get("shards", 1),
                          devices=meta.get("devices", ()),
                          lane=current_lane())


def candidate_table(card: np.ndarray, n: int) -> np.ndarray:
    """Sorted unique candidate thresholds for one query — exactly the
    host path's array (ascending; gamma < c(V) is never feasible)."""
    size = 1 << n
    pc = popcounts(n)
    cand = np.unique(card[pc >= 2])
    return cand[cand >= card[size - 1]]


def _pad_candidates(cards: np.ndarray, n: int):
    """Pad B candidate tables into the (B_bucket, candidate_bucket(n))
    buffer: rows repeat their last (always-feasible) candidate so
    per-row brackets never leave the real range; padded batch rows
    replay query 0 with a collapsed bracket.  The candidate axis always
    uses the single canonical per-``n`` bucket — see
    ``candidate_bucket`` for why."""
    B = cards.shape[0]
    cands = [candidate_table(cards[b], n) for b in range(B)]
    Bp = _next_pow2(B)
    C = candidate_bucket(n)
    cand_pad = np.ones((Bp, C), np.float64)
    hi0 = np.zeros(Bp, np.int32)
    for b, c in enumerate(cands):
        cand_pad[b, :len(c)] = c
        cand_pad[b, len(c):] = c[-1]
        hi0[b] = len(c) - 1
    cards_pad = cards
    if Bp != B:
        cards_pad = np.concatenate(
            [cards, np.repeat(cards[:1], Bp - B, axis=0)], axis=0)
    return cards_pad, cand_pad, hi0, Bp, C


def _seed_bracket(cand_pad: np.ndarray, hi0: np.ndarray, seed_opt,
                  B: int):
    """Encode cached optima as warm-start hypotheses in the brackets.

    ``seed_opt`` is a length-B sequence of cached C_max optima (None or
    non-finite = no seed for that row).  A seed only engages when it
    matches a candidate byte-exactly within the row's live range; the
    row is then encoded ``lo0 = -(idx + 1)`` with the FULL bracket
    preserved in ``hi0``, and the seeded program variant VERIFIES the
    hypothesis on device with one dual feasibility probe before
    collapsing (``lattice._fused_search(verify_seed=True)``).  A
    verified seed exits the search loop with zero further rounds; a
    stale seed (matching some candidate that is not the optimum —
    feasible-but-not-minimal or infeasible) only shrinks the bracket
    and the search converges to the true optimum.  Correctness never
    depends on the cache — it only prices rounds.  Returns ``(lo0,
    hi0, rows_seeded)``.
    """
    lo0 = np.zeros_like(hi0)
    hits = 0
    if seed_opt is None:
        return lo0, hi0, hits
    for b in range(min(B, len(seed_opt))):
        v = seed_opt[b]
        if v is None or not np.isfinite(v):
            continue
        row = cand_pad[b]
        idx = int(np.searchsorted(row[:hi0[b] + 1], v))
        if idx <= hi0[b] and row[idx] == v:
            lo0[b] = -(idx + 1)
            hits += 1
    return lo0, hi0, hits


def _trees_from_arrays(nodes: np.ndarray, lidx: np.ndarray,
                       B: int) -> list:
    """Assemble JoinTree objects from the device split arrays — a linear
    pass, no submask search, no recursion."""
    return [jointree.tree_from_split_arrays(nodes[b], lidx[b])
            for b in range(B)]


def fused_dpconv_max(cards: np.ndarray, n: int, direct_layers: int = 4,
                     extract_tree: bool = True, backend: str = "xla",
                     gamma_batch: int = 1,
                     shards: int = 1, seed_opt=None) -> FusedSolve:
    """Solve B same-``n`` DPconv[max] instances in ONE device dispatch.

    ``cards`` is (B, 2^n).  Optima and trees are bit-identical to B
    host-loop ``dpconv_max`` calls; the B searches advance in lockstep
    inside the compiled while loop.  ``gamma_batch = G > 1`` probes G
    thresholds per round on a leading gate axis — (G+1)-ary search,
    ~log_{G+1} instead of ~log_2 rounds, still one dispatch and the same
    optima/trees.  ``shards = D > 1`` runs the program ``shard_map``-ped
    over the D-device solve mesh (still one dispatch, same results).

    ``seed_opt`` — per-row cached optima from the layer cache (None
    entries = cold): matching rows run the ``max_seeded`` program
    variant, which VERIFIES each hypothesis with one dual feasibility
    probe and only then collapses the bracket (``_seed_bracket`` /
    ``lattice._fused_search(verify_seed=True)``) — one round instead of
    ~log2(C) when the seed holds, a correct cold-equivalent search when
    it is stale, same dispatch count, bit-identical results either way.
    """
    cards = np.asarray(cards, np.float64)
    if cards.ndim == 1:
        cards = cards[None, :]
    B, size = cards.shape
    assert size == 1 << n and n >= 2
    assert gamma_batch >= 1
    cards_pad, cand_pad, hi0, Bp, C = _pad_candidates(cards, n)
    lo0, hi0, seeded = _seed_bracket(cand_pad, hi0, seed_opt, B)

    cost = "max_seeded" if seeded else "max"
    exe, emeta, hit = _executable(n, Bp, C, backend, direct_layers,
                                  extract_tree, cost, gamma_batch,
                                  shards)
    prof = _record(cost, n, Bp, C, backend, emeta, hit)
    disp0 = _STATS.dispatches
    rec0 = jointree.recursive_extractions()
    out = _run(exe, jnp.asarray(f64bits.to_bits(cards_pad)),
               jnp.asarray(f64bits.to_bits(cand_pad)),
               jnp.asarray(lo0), jnp.asarray(hi0), record=prof)
    trees: list = [None] * B
    dpn = None
    if extract_tree:
        opt, dp, nodes, lidx, rounds = out
        dpn = np.asarray(dp, np.float64)[:B]
        trees = _trees_from_arrays(np.asarray(nodes), np.asarray(lidx), B)
    else:
        opt, rounds = out
    opt = f64bits.from_bits(opt)[:B]
    rounds = int(rounds)
    prof.rounds = rounds

    # the "zero per-solve host recursions" invariant: tree assembly must
    # not have fallen back to the recursive Alg. 2 extractors
    _STATS.inc("host_extractions",
               jointree.recursive_extractions() - rec0)
    _STATS.inc("solves")
    _STATS.inc("queries", B)
    _STATS.inc("rounds", rounds)
    return FusedSolve(optima=opt, trees=trees, rounds=rounds,
                      passes=rounds + (1 if extract_tree else 0),
                      dispatches=_STATS.dispatches - disp0,
                      dp=dpn, extraction="device", seeded=seeded)


def fused_out(qs: list, cards: np.ndarray, n: int,
              extract_tree: bool = True,
              shards: int = 1, seed_vals=None,
              seed_ok=None) -> FusedOutSolve:
    """Solve B same-``n`` connected C_out instances (DPccp semantics —
    connected csg/cmp pairs only, no cross products) in ONE device
    dispatch.

    ``qs`` are the B query graphs (each batch row may carry a different
    topology: the connected-subset masks ship as a program input, not a
    compile-time constant), ``cards`` is (B, 2^n).  Every graph must be
    connected and simple-edge — the DPccp search space is undefined
    otherwise (``dpccp.connectivity_masks`` raises on hyperedges).
    Optima, DP tables and trees are bit-identical to B
    ``dpccp_with_tree`` calls.

    ``seed_vals``/``seed_ok`` — (B, 2^n) cached sub-table values and
    their validity mask from the layer cache: rows with seeds replay
    those entries inside the (min,+) sweep (the ``out_seeded``
    executable variant) instead of recomputing them; ``dp[S]`` is a pure
    function of the sub-problem induced on ``S``, so valid seeds are
    bit-identical to the recomputation and results never change.  Still
    ONE dispatch.
    """
    from repro.core.dpccp import connectivity_masks

    cards = np.asarray(cards, np.float64)
    if cards.ndim == 1:
        cards = cards[None, :]
    B, size = cards.shape
    assert size == 1 << n and n >= 2
    assert len(qs) == B
    conn = np.stack([connectivity_masks(q) for q in qs])
    if not conn[:, -1].all():
        raise ValueError("fused_out requires connected query graphs "
                         "(DPccp excludes cross products); route "
                         "disconnected queries to the full-lattice "
                         "pipelines")
    Bp = _next_pow2(B)
    cards_pad, conn_pad = cards, conn
    if Bp != B:
        cards_pad = np.concatenate(
            [cards, np.repeat(cards[:1], Bp - B, axis=0)], axis=0)
        conn_pad = np.concatenate(
            [conn, np.repeat(conn[:1], Bp - B, axis=0)], axis=0)

    seeded = 0
    cost = "out"
    extra = ()
    if seed_ok is not None and np.any(seed_ok):
        sv = np.zeros((Bp, size), np.float64)
        so = np.zeros((Bp, size), bool)
        sv[:B] = np.asarray(seed_vals, np.float64)
        so[:B] = np.asarray(seed_ok, bool)
        seeded = int(np.count_nonzero(so[:B].any(axis=1)))
        cost = "out_seeded"
        extra = (jnp.asarray(f64bits.to_bits(sv)), jnp.asarray(so))

    exe, emeta, hit = _executable(n, Bp, 0, "xla", 4, extract_tree,
                                  cost, 1, shards)
    prof = _record(cost, n, Bp, 0, "xla", emeta, hit)
    disp0 = _STATS.dispatches
    rec0 = jointree.recursive_extractions()
    out = _run(exe, jnp.asarray(f64bits.to_bits(cards_pad)),
               jnp.asarray(conn_pad), *extra, record=prof)
    trees: list = [None] * B
    dpn = None
    if extract_tree:
        cout, dp, nodes, lidx = out
        dpn = f64bits.from_bits(dp)[:B]
        trees = _trees_from_arrays(np.asarray(nodes), np.asarray(lidx), B)
    else:
        (cout,) = out
    _STATS.inc("host_extractions",
               jointree.recursive_extractions() - rec0)
    _STATS.inc("solves")
    _STATS.inc("queries", B)
    return FusedOutSolve(couts=f64bits.from_bits(cout)[:B],
                         trees=trees,
                         dispatches=_STATS.dispatches - disp0,
                         dp=dpn, extraction="device", seeded=seeded)


def fused_ccap(cards: np.ndarray, n: int, gamma_slack: float = 1.0,
               direct_layers: int = 4, extract_tree: bool = True,
               backend: str = "xla",
               gamma_batch: int = 1,
               qs: "list | None" = None,
               shards: int = 1, seed_opt=None) -> FusedCapSolve:
    """Solve B same-``n`` C_cap instances (Sec. 8) in ONE device
    dispatch: pass-1 gamma search, gamma-pruned (min,+) C_out pass, and
    witness-tree extraction all inside the same program.

    Caps, C_out values and trees are bit-identical to the host pipeline
    (``dpconv_max`` pass 1 + ``baselines.dpsub(mode="out",
    prune_gamma=gamma)`` + ``extract_tree_out``).

    ``qs`` switches pass 2 onto the *connected* (min,+) sweep — the
    no-cross-products cap: the B query graphs' connected-subset masks
    gate every split exactly like ``fused_out``, so the search space is
    DPccp's pruned by gamma; bit-identical to ``dpconv_max`` +
    ``dpccp(prune_gamma=gamma)`` + ``extract_tree_out``.  Requires
    connected simple-edge graphs.  A cap the connected space cannot
    attain yields ``cout = +inf`` (the host pipeline's behavior); the
    caller decides whether that is an error.

    ``seed_opt`` — per-row cached C_max optima warm-starting the pass-1
    bracket exactly as in ``fused_dpconv_max``, verification probe
    included (pass 1 IS that search; at the default slack the gamma it
    yields equals the cached value bitwise, so max- and cap-lane solves
    of the same canonical query seed each other).
    """
    cards = np.asarray(cards, np.float64)
    if cards.ndim == 1:
        cards = cards[None, :]
    B, size = cards.shape
    assert size == 1 << n and n >= 2
    cards_pad, cand_pad, hi0, Bp, C = _pad_candidates(cards, n)
    lo0, hi0, seeded = _seed_bracket(cand_pad, hi0, seed_opt, B)

    extra = ()
    cost = "cap"
    if qs is not None:
        from repro.core.dpccp import connectivity_masks

        assert len(qs) == B
        conn = np.stack([connectivity_masks(q) for q in qs])
        if not conn[:, -1].all():
            raise ValueError("the connected C_cap pass requires "
                             "connected query graphs (DPccp excludes "
                             "cross products)")
        conn_pad = conn if Bp == B else np.concatenate(
            [conn, np.repeat(conn[:1], Bp - B, axis=0)], axis=0)
        extra = (jnp.asarray(conn_pad),)
        cost = "cap_conn"
    if seeded:
        cost += "_seeded"

    exe, emeta, hit = _executable(n, Bp, C, backend, direct_layers,
                                  extract_tree, cost, gamma_batch,
                                  shards)
    prof = _record(cost, n, Bp, C, backend, emeta, hit)
    disp0 = _STATS.dispatches
    rec0 = jointree.recursive_extractions()
    caps = cand_pad * np.float64(gamma_slack)     # host f64, exact IEEE
    out = _run(exe, jnp.asarray(f64bits.to_bits(cards_pad)),
               jnp.asarray(f64bits.to_bits(cand_pad)),
               jnp.asarray(lo0), jnp.asarray(hi0),
               jnp.asarray(f64bits.to_bits(caps)), *extra, record=prof)
    trees = [None] * B
    if extract_tree:
        gamma, cout, nodes, lidx, rounds = out
        trees = _trees_from_arrays(np.asarray(nodes), np.asarray(lidx), B)
    else:
        gamma, cout, rounds = out
    prof.rounds = int(rounds)
    _STATS.inc("host_extractions",
               jointree.recursive_extractions() - rec0)
    _STATS.inc("solves")
    _STATS.inc("queries", B)
    _STATS.inc("rounds", int(rounds))
    return FusedCapSolve(gammas=f64bits.from_bits(gamma)[:B],
                         couts=f64bits.from_bits(cout)[:B],
                         trees=trees, rounds=int(rounds),
                         dispatches=_STATS.dispatches - disp0,
                         extraction="device", seeded=seeded)
