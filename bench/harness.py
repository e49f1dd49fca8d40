"""The benchmark's measuring machinery: build the plan server a
configuration describes, warm it up, drive its async entry point through
a timed window, and hold every answer to the plain reference.

``bench/run.py`` is the command; this module holds what it, its tests and
the probe scripts share.  Everything one configuration, traffic mix or
metric needs is found by name:

    bench/configs/<config>.json     server settings and the deployment
    bench/traffic/<traffic>.json    generator parameters and the loop
    bench/metrics/<metric>.py       ``read(ctx)`` -> number or None
    bench/costs/<cost>.py           the cost function's plain reference

A cost's reference gives four functions of a ``gen.Query``:
``solve(q, dtype)`` -> (optimum, DP table in ``dtype``, the numbers an
answer reports beside its value, which a sampled answer must match
exactly); ``extract(q, table)`` -> a tree that realises the table;
``tree_problems(tree, q, meta)`` -> why ``tree``, answered with ``meta``,
is not a plan of the query under the cost (empty if it is); and
``tree_cost(tree, q)``.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import math
import os
import time

import numpy as np

from bench.traffic import gen

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COSTS = os.path.join(BENCH, "costs")

# the limits every run's answers are held to; PERF.md gives the readings
# of sound runs and of the control that each was set between
LIMITS = {
    "lost": 0,            # requests never answered, or refused with an error
    "bad_trees": 0,       # answers that are not a join tree of the query
    "opt_gap": 1e-11,     # |cost - reference optimum| / optimum, sampled
    "tree_gap": 1e-11,    # |cost of the returned tree - cost| / cost
    # share of the window's requests that failed: raised, not exact, or
    # answered by the failure ladder's host or GOO rung, as where the
    # program's own plan-cost recheck caught a wrong fused answer
    "failed_share": 0.1,
}
GRACE_S = 60.0            # how long past the window an answer may come
SETUP_WAIT_S = 120.0      # how long set-up waits for its warm-up answers


# ------------------------------------------------------------- definitions
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict                  # the workloads entry of BENCHMARK.json
    config: dict                 # bench/configs/<config>.json
    mix: gen.Mix                 # bench/traffic/<traffic>.json
    end_to_end: list             # metric entries this cell reports
    per_layer: list


def load_cell(name: str, root: str = ROOT) -> Cell:
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = load_json(os.path.join(root, cfg["file"]))
    mix = gen.Mix.from_dict(load_json(
        os.path.join(root, "bench", "traffic", w["traffic"] + ".json")))
    for cost in {config["cost"], mix.cost}:
        load_cost(cost)          # a cost without its reference ends here

    def mine(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]
    return Cell(name, w, config, mix, mine(bm["end_to_end"]),
                mine(bm["per_layer"]))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    return _load(os.path.join(BENCH, "metrics", metric + ".py"),
                 "bench_metric_" + metric.replace(".", "_")).read


def load_cost(cost: str):
    """The reference of one cost function, ``<COSTS>/<cost>.py``; stops
    the run, naming the file, where there is none."""
    path = os.path.join(COSTS, cost + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no reference for cost {cost!r}: {path} is "
                         "missing")
    return _load(path, "bench_cost_" + cost)


# ------------------------------------------------------------------ server
def build_server(config: dict):
    """The ``PlanServer`` a configuration file describes: its ``server``
    keyword arguments, and ``batch_policy`` for the ``BatchPolicy``."""
    from repro.service import BatchPolicy, PlanServer
    kw = dict(config.get("server", {}))
    pol = dict(config.get("batch_policy", {}))
    pol.setdefault("max_batch", kw.get("max_batch", 16))
    return PlanServer(batch_policy=BatchPolicy(**pol), **kw)


def program_query(q: gen.Query):
    from repro.core.querygraph import QueryGraph
    return QueryGraph(q.n, q.edges)


class CompileCounter:
    """Counts JAX's compile requests, persistent-cache hits and traces,
    from its monitoring events; ``snap()`` is a point to diff against."""

    def __init__(self):
        from jax import monitoring
        self.requests = self.hits = self.traces = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snap(self) -> dict:
        return {"compile_requests": self.requests, "cache_hits": self.hits,
                "compiled": self.requests - self.hits,
                "traces": self.traces}

    @staticmethod
    def diff(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


def pin_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    whatever the environment says, so that each checkout keeps its own
    and only a checkout's first run of a cell compiles.  Call before JAX
    is imported (it reads the variable then); the program's
    ``use_compile_cache`` then takes this directory."""
    import sys
    path = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path


def dir_bytes(path: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, f))
            except OSError:
                pass
    return total


# ---------------------------------------------------------------- requests
@dataclasses.dataclass
class Rec:
    """One request of the window: when it was due, sent and answered."""
    query: gen.Query
    due: float
    sent: float = 0.0
    done: "float | None" = None
    resp: object = None            # PlanResponse
    error: "BaseException | None" = None

    @property
    def latency(self) -> float:
        return (self.done - self.due) if self.done is not None else np.inf


async def _ask(srv, cost: str, rec: Rec, req_id: int) -> None:
    rec.sent = time.perf_counter()
    try:
        rec.resp = await srv.plan_async(program_query(rec.query),
                                        rec.query.card, cost=cost,
                                        req_id=req_id)
    except Exception as e:             # noqa: BLE001 — recorded, counted
        rec.error = e
    rec.done = time.perf_counter()


async def _drive(srv, mix: gen.Mix, stream: gen.Stream, seconds: float,
                 marks=()) -> tuple:
    """Issue the window's requests: on the mix's open-loop schedule, or
    from ``mix.clients`` closed-loop clients, for ``seconds``, then wait
    for their answers (at most ``GRACE_S`` past the window).  ``marks``
    are (offset, callable) pairs run at those seconds into the window.
    Returns (start, records)."""
    recs: list = []
    start = time.perf_counter()
    end = start + seconds
    tasks: list = []

    async def at_marks():
        for offset, fn in sorted(marks, key=lambda m: m[0]):
            await asyncio.sleep(max(start + offset - time.perf_counter(), 0))
            fn()
    timer = asyncio.ensure_future(at_marks())
    if mix.loop == "open":
        for due in gen.open_loop_dues(mix.rate, seconds) + start:
            now = time.perf_counter()
            if due > now:
                await asyncio.sleep(due - now)
            rec = Rec(stream.next(), float(due))
            recs.append(rec)
            tasks.append(asyncio.ensure_future(
                _ask(srv, mix.cost, rec, len(recs))))
    elif mix.loop == "closed":
        async def client():
            while time.perf_counter() < end:
                rec = Rec(stream.next(), 0.0)
                rec.due = time.perf_counter()
                recs.append(rec)
                await _ask(srv, mix.cost, rec, len(recs))
        tasks = [asyncio.ensure_future(client())
                 for _ in range(mix.clients)]
    else:
        raise ValueError(f"unknown loop {mix.loop!r}")
    _done, pending = await asyncio.wait(tasks, timeout=max(
        end + GRACE_S - time.perf_counter(), 0.1))
    for t in pending:
        t.cancel()
    if pending:
        await asyncio.wait(pending, timeout=5.0)
    await timer                  # the marks all lie inside the window
    return start, recs


def run_window(srv, mix: gen.Mix, stream: gen.Stream, seconds: float,
               marks=()) -> "Window":
    start, recs = asyncio.run(_drive(srv, mix, stream, seconds, marks))
    return Window(start, recs)


@dataclasses.dataclass
class Window:
    start: float
    recs: list

    @property
    def close(self) -> float:
        """The completion of the last request issued inside the window."""
        done = [r.done for r in self.recs if r.done is not None]
        return max(done) if done else self.start

    @property
    def elapsed(self) -> float:
        return self.close - self.start

    def failed(self, r: Rec) -> bool:
        """Raised, not exact, or answered by the failure ladder's host or
        GOO rung (the fused engine did not produce it)."""
        if r.resp is None:
            return True
        if r.resp.status != "exact":
            return True
        return r.resp.meta.get("engine") != "fused"

    def n_failed(self) -> int:
        return sum(self.failed(r) for r in self.recs)

    def plans_per_s(self) -> float:
        good = len(self.recs) - self.n_failed()
        return good / self.elapsed if self.elapsed > 0 else 0.0

    def latency_ms(self, q: float) -> float:
        return percentile([r.latency * 1e3 for r in self.recs], q)

    def lateness_ms(self) -> dict:
        late = np.array([r.sent - r.due for r in self.recs]) * 1e3
        if not len(late):
            return {"p50": 0.0, "p95": 0.0}
        return {"p50": float(np.percentile(late, 50)),
                "p95": float(np.percentile(late, 95))}


# --------------------------------------------------------------- set-up
def prewarm(srv, mix: gen.Mix) -> dict:
    """Compile exactly the (n, cost) buckets the mix will hit."""
    return srv.prewarm(sorted(set(int(n) for n in mix.n_values)),
                       costs=(mix.cost,))


def serve_pool(srv, mix: gen.Mix, seed: int) -> int:
    """Serve every template of the run's pool once, so the plan cache
    holds what a server that has been running would hold."""
    pool = gen.Stream(mix, seed).pool

    async def one_by_one():
        for q in pool:
            await _ask(srv, mix.cost, Rec(q, 0.0), -1)
    _run_setup(one_by_one())
    return len(pool)


def warm_buckets(srv, mix: gen.Mix, seed: int) -> int:
    """For each relation count, send bursts of 1, 2, 4 ... fresh queries
    up to the batch size through the window's entry point, so every batch
    bucket the window can form has run once."""
    rng = np.random.default_rng([int(seed), 3])

    async def bursts():
        sent = 0
        for i, n in enumerate(sorted(set(int(v) for v in mix.n_values))):
            size = 1
            while size <= srv.max_batch:
                topo = mix.topologies[i % len(mix.topologies)]
                qs = [gen.make_query(rng, n, topo, mix.regimes,
                                     mix.extra_edges) for _ in range(size)]
                await asyncio.gather(*(_ask(srv, mix.cost, Rec(q, 0.0), -1)
                                       for q in qs))
                sent += size
                size *= 2
        return sent
    return _run_setup(bursts())


def _run_setup(coro):
    """Run warm-up traffic; a warm-up answer that never comes ends the
    run with an error instead of hanging it."""
    return asyncio.run(asyncio.wait_for(coro, SETUP_WAIT_S))


def set_up(srv, mix: gen.Mix, seed: int) -> tuple:
    """Prewarm and warm-up traffic: (prewarm result, requests sent)."""
    warm = prewarm(srv, mix)
    sent = warm_buckets(srv, mix, seed) + serve_pool(srv, mix, seed)
    return warm, sent


class GcWatch:
    """The cyclic collector's passes while armed: how many, how many of
    them full (oldest generation), and their longest and summed pause.
    A diagnostic for stalls inside the window; the harness does not tune
    the collector."""

    def __init__(self):
        self.began = None
        self.pauses: list = []
        self.full = 0

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.began = time.perf_counter()
        elif self.began is not None:
            self.pauses.append(time.perf_counter() - self.began)
            self.full += info["generation"] == 2
            self.began = None

    def __enter__(self) -> "GcWatch":
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on)

    def summary(self) -> str:
        longest = max(self.pauses, default=0.0) * 1e3
        return (f"{len(self.pauses)} passes ({self.full} full), longest "
                f"{longest:.3f} ms, summed {sum(self.pauses) * 1e3:.3f} ms")


# ------------------------------------------------------------ correctness
def answer_tree(tree):
    """The program's join tree as nested (left, right) mask tuples."""
    if tree is None:
        return None
    if tree.left is None:
        return int(tree.mask)
    return (answer_tree(tree.left), answer_tree(tree.right))


def compare(recs: list, cost: str, sample: int, seed: int,
            answer=None) -> dict:
    """Hold the window's answers to the reference of ``cost``.  Every
    answer: it came, it is a plan of its query under the cost, and that
    tree costs what the answer says.  A sample drawn from the seed: the
    cost is the reference's optimum, and each number the reference
    reports beside it is the answer's, exactly.  ``answer(rec) -> (cost,
    tree, meta)`` replaces the program's answers (the control puts the
    reference in their place).  Returns each number compared with its
    limit."""
    ref = load_cost(cost)
    if answer is None:
        def answer(r):
            return (float(r.resp.cost), answer_tree(r.resp.tree),
                    r.resp.meta)
    lost = bad = 0
    tree_gap = 0.0
    answered = []
    for r in recs:
        if r.resp is None:
            lost += 1
            continue
        q = r.query
        value, tree, meta = answer(r)
        if tree is None or ref.tree_problems(tree, q, meta):
            bad += 1
            continue
        tree_gap = max(tree_gap, _gap(ref.tree_cost(tree, q), value))
        answered.append((r, value, meta))
    opt_gap = 0.0
    rng = np.random.default_rng([int(seed), 4])
    pick = rng.permutation(len(answered))[:sample]
    for i in sorted(pick):
        r, value, meta = answered[i]
        opt, _dp, want = ref.solve(r.query)
        gap = _gap(value, float(opt))
        if any(meta.get(k) != v for k, v in want.items()):
            gap = math.inf     # a reported number off by any amount
        opt_gap = max(opt_gap, gap)
    values = {"lost": lost, "bad_trees": bad, "opt_gap": opt_gap,
              "tree_gap": tree_gap}
    # a gap that is not a finite number fails its limit, and is printed
    # as a number the result's JSON line can hold
    return {k: {"value": v if math.isfinite(v) else 1e308,
                "limit": LIMITS[k]} for k, v in values.items()}


def window_checks(win: "Window", mix: gen.Mix, seed: int) -> dict:
    """What a run holds its window to: ``compare`` over every request,
    and the share of the requests that failed."""
    checks = compare(win.recs, mix.cost, mix.check_sample, seed)
    share = win.n_failed() / len(win.recs) if win.recs else 0.0
    checks["failed_share"] = {"value": share,
                              "limit": LIMITS["failed_share"]}
    return checks


def control_answer(cost: str, dtype=np.float32):
    """The control: the reference, run in the precision below the
    configuration's, answering in the program's place."""
    ref = load_cost(cost)

    def answer(r):
        opt, dp, meta = ref.solve(r.query, dtype)
        return float(opt), ref.extract(r.query, dp), meta
    return answer


def sample(recs: list, k: int, seed: int) -> list:
    """``k`` of the window's requests, drawn from the seed."""
    rng = np.random.default_rng([int(seed), 5])
    return [recs[i] for i in sorted(rng.permutation(len(recs))[:k])]


def _gap(a: float, b: float) -> float:
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(b), 1e-300)


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def percentile(values, q: float) -> float:
    """numpy's linear percentile, where an infinite value (a request
    never answered) makes every percentile that reaches it infinite
    rather than NaN."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return float("inf")
    pos = q / 100.0 * (len(v) - 1)
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if lo == hi or v[hi] == v[lo]:
        return float(v[lo])
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


# ------------------------------------------------------------- a whole run
LAYER_SPANS = ("admit", "fast_path", "queue_wait")


def layer_snapshot(srv) -> dict:
    """(count, sum of seconds) of the program's own span and dispatch
    histograms: the runtime's ``trace.<span>_s`` and the engine's
    ``engine.execute_s`` (one observation per ``DispatchRecord``)."""
    from repro.core import engine as engine_mod
    out = {}
    for name in LAYER_SPANS:
        h = srv.registry.histogram(f"trace.{name}_s")
        out[name] = (h.count, h.sum)
    h = engine_mod.stats().registry.histogram("engine.execute_s")
    out["execute"] = (h.count, h.sum)
    return out


def annotate_runtime(rt) -> None:
    """Put the harness's host spans on the profiler's clock: wrap the
    runtime's admission (``submit``), batch closing, completion and
    worker-thread solve in ``TraceAnnotation``s named ``bench.*``."""
    import jax
    for name in ("submit", "_close_bucket", "_finalize", "_execute"):
        fn = getattr(rt, name)
        label = "bench." + name.strip("_")

        def wrapped(*a, _fn=fn, _label=label, **k):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **k)
        setattr(rt, name, wrapped)


class Profile:
    """The traced run's profiler session over part of the window."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.mark = None

    def start(self) -> None:
        import shutil

        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        # no Python-call tracing: it records every function call and
        # slows the host several-fold; the harness's annotations and the
        # device's operations are what the reduction reads
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.mark = jax.profiler.TraceAnnotation("bench.traced")
        self.mark.__enter__()

    def stop(self) -> None:
        import jax
        if self.mark is not None:
            self.mark.__exit__(None, None, None)
            self.mark = None
            jax.profiler.stop_trace()


def stall_report(win: "Window", incidents, rt_t0: float, records: list,
                 k: int = 5) -> list:
    """Lines that locate a stall inside the window: the longest spells
    without a completion, the longest dispatches (as far back as the
    engine's ring of dispatch records reaches), and every incident the
    runtime recorded in the window (watchdog, error, quarantine...),
    timed in seconds from the window's start."""
    done = sorted([win.start] + [r.done for r in win.recs
                                 if r.done is not None])
    gaps = sorted(((b - a, a - win.start) for a, b in zip(done, done[1:])),
                  reverse=True)[:k]
    lines = ["longest spells without a completion: " + ", ".join(
        f"{g:.4f} s at {at:.4f} s" for g, at in gaps)]
    slow = sorted(records, key=lambda r: -(r.execute_s + r.compile_s))[:k]
    lines.append(f"longest dispatches (of the last {len(records)}): "
                 + ", ".join(f"n={r.n} B={r.B} {r.cost} shards={r.shards} "
                             f"devices={mesh_size(r)} execute "
                             f"{r.execute_s:.4f} s compile {r.compile_s:.4f}"
                             f" s" for r in slow))
    meshes: dict = {}
    for r in records:
        key = (r.shards, mesh_size(r))
        meshes[key] = meshes.get(key, 0) + 1
    lines.append(f"dispatches by mesh (of the last {len(records)}): " + (
        ", ".join(
            f"shards={s} over {d} devices: {c}"
            for (s, d), c in sorted(meshes.items())) or "none"))
    def at(i):
        t = i["info"].get("at", i["at"])
        return None if t is None else t - rt_t0
    mine = [i for i in incidents if at(i) is None or at(i) >= 0]
    for i in mine[:40]:
        info = {key: v for key, v in i["info"].items() if key != "at"}
        lines.append(f"incident {i['kind']} at {at(i)} s: {info}")
    if len(mine) > 40:
        lines.append(f"... {len(mine) - 40} more incidents")
    return lines


def mesh_size(r) -> int:
    """How many distinct devices a dispatch ran on (its record's
    ``devices`` is a (platform, device ids) pair)."""
    return len(set(r.devices[1])) if r.devices else 1


def peaks_for(kind: str, path: str = os.path.join(BENCH, "peaks.json")
              ) -> dict:
    table = load_json(path)
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in {path}; add its "
                       "published peaks with their source")
    return table["devices"][kind]


def measure(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
            peaks: dict, log=print, trace_dir: "str | None" = None) -> dict:
    """One benchmark run: set-up, window, comparison, metrics.  Returns
    the result object; progress and counts go to ``log``."""
    from repro.core import engine as engine_mod
    counter = CompileCounter()
    c0 = counter.snap()
    mix = cell.mix
    srv = build_server(cell.config)
    m0 = engine_mod.dispatch_mark()
    warm, sent = set_up(srv, mix, seed)
    c1 = counter.snap()
    rt = srv.async_runtime()
    profile = None
    marks = ()
    quiet: dict = {}
    if trace:
        annotate_runtime(rt)
        profile = Profile(trace_dir or os.path.join(ROOT, ".bench_trace"))
        # the profiler stalls the host for seconds when it stops, so the
        # trace takes the window's end and the span and counter readers
        # take the window up to the trace's start
        length = min(3.0, 0.5 * seconds)
        t_on = max(seconds - length - 1.0, 0.25 * seconds)

        def start_profile():
            quiet.update(layer_snapshot(srv))
            profile.start()
        marks = ((t_on, start_profile), (t_on + length, profile.stop))
    setup_s = time.perf_counter() - t0
    log(f"setup: {setup_s:.3f} s; prewarm compiled {warm['compiled']} "
        f"executables in {warm['seconds']:.3f} s; {sent} warm-up requests")
    # each bucket's first execution after its load falls in set-up
    log("set-up dispatches, in order: " + ", ".join(
        f"{r.cost} B={r.B} shards={r.shards} execute {r.execute_s:.4f} s"
        for r in sorted(engine_mod.dispatches_since(m0),
                        key=lambda r: r.seq)))
    before = layer_snapshot(srv)
    mark, rt_t0 = engine_mod.dispatch_mark(), rt.clock.now()
    with GcWatch() as gcw:
        win = run_window(srv, mix, gen.Stream(mix, seed), seconds, marks)
    if profile is not None:
        profile.stop()
    after = quiet or layer_snapshot(srv)
    c2 = counter.snap()
    rt.close()
    dev = _device()
    mem = memory_peaks(cell.entry["chips"])
    peak = max(a + b for a, b in mem)
    log(f"compiles: setup {counter.diff(c0, c1)}; window "
        f"{counter.diff(c1, c2)}; engine exec_cache_misses "
        f"{engine_mod.stats().exec_cache_misses}")
    log(f"peak bytes (in use, reserved) per device: {mem}")
    for b in engine_mod.compiled_buckets():
        log(f"memory_analysis {b['module']}: {b['memory']}")
    ladder = {k: v for k, v in rt.fstats.as_dict().items() if v}
    log(f"failure ladder and watchdog: {ladder or 'all 0'}")
    for line in stall_report(win, rt.recorder.incidents, rt_t0,
                             engine_mod.dispatches_since(mark)):
        log(line)
    late = win.lateness_ms()
    log(f"window: {len(win.recs)} requests in {win.elapsed:.4f} s; "
        f"failed {win.n_failed()}; generator lateness p50 "
        f"{late['p50']:.4f} ms p95 {late['p95']:.4f} ms max "
        f"{max((r.sent - r.due for r in win.recs), default=0.0) * 1e3:.4f}"
        f" ms; collector {gcw.summary()}")
    checks = window_checks(win, mix, seed)
    ctx = {"window": win, "setup_s": setup_s, "peaks": peaks,
           "layers": {k: (after[k][0] - before[k][0],
                          after[k][1] - before[k][1]) for k in after},
           "trace": None}
    result = {"correct": correct(checks), "attempted": len(win.recs),
              "failed": win.n_failed()}
    if trace:
        from bench import trace_reduce
        path = trace_reduce.newest_trace(profile.dir)
        events = trace_reduce.load_events(path)
        red = trace_reduce.reduce(events, dev.platform)
        ctx["trace"] = red
        log(f"trace: {path}; {len(events)} events; {len(red.ops)} device "
            f"ops; busy {red.busy_s:.6f} s of {red.window_s:.6f} s")
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = load_reader(m["name"])(ctx)
        if v is None:
            continue
        if not math.isfinite(v):       # a request never answered: the
            log(f"metric {m['name']}: {v} (left out)")   # run is not
            continue                                      # correct
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = {
        "platform": dev.platform if dev is not None else "none",
        "kind": dev.device_kind if dev is not None else "none",
        "count": device_count(),
        "memory_peak_bytes": peak}
    if trace:
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": red.top_ops(),
                               "idle_gaps": red.top_gaps()}
    result["checks"] = checks
    return result


def require_tpu(chips: int):
    """The first TPU device, or exit: nothing is measured off the chip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX sees no TPU (platform "
                         f"{devs[0].platform!r}); nothing measured")
    if len(devs) < chips:
        raise SystemExit(f"bench: needs {chips} TPU chips, JAX sees "
                         f"{len(devs)}; nothing measured")
    return devs[0]


def _device():
    import jax
    return jax.devices()[0]


def memory_peaks(chips: int) -> list:
    """(peak bytes in use, peak bytes reserved) of each of the cell's
    ``chips`` devices, where the backend reports them (0 where it does
    not).  A TPU holds an executable's temporaries in its reservation,
    outside the bytes in use, so a chip's peak is the two together."""
    import jax
    return [(int(m.get("peak_bytes_in_use", 0)),
             int(m.get("peak_bytes_reserved", 0)))
            for m in (d.memory_stats() or {}
                      for d in jax.devices()[:chips])]


def device_count() -> int:
    import jax
    return len(jax.devices())
