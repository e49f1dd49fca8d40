"""The benchmark's arithmetic, on the CPU: the trace reduction, the
roofline bytes, the table of peaks, the traffic generator, the window's
rate and percentile arithmetic, and the definitions in BENCHMARK.json.
Nothing here needs a chip, and nothing loads the TPU's library."""
import hashlib
import json
import math
import os
import re
import types

import numpy as np
import pytest

from bench import harness
from bench import trace_reduce as T
from bench.roofline import hlo, share
from bench.roofline import zeta as zeta_roofline
from bench.traffic import gen

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
US = 1000.0                       # synthetic traces are written in us


# ------------------------------------------------------------ trace_reduce
def test_cpu_trace_fixture():
    """A trace recorded on the CPU (a matmul and a cumsum program, three
    times each under ``bench.execute`` inside ``bench.traced``, each
    followed by a 2 ms ``bench.finalize``): the reduction finds the
    operations by their ``hlo_op`` statistic."""
    events = T.load_events(os.path.join(FIXTURES, "cpu_trace.xplane.pb"))
    red = T.reduce(events, "cpu")
    assert red.devices == 1
    assert len(red.ops) == 33                      # 11 ops x 3 rounds
    assert red.window_s == pytest.approx(8139796e-9, abs=1e-15)
    assert red.busy_s == pytest.approx(1173377e-9, abs=1e-15)
    assert red.idle_share == pytest.approx(1 - 1173377 / 8139796)
    assert red.op_s["dot_general.1"] == pytest.approx(206668e-9,
                                                      abs=1e-15)
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s)
    # the sleeps are the long gaps, and the reduction names them
    (what, secs), = red.top_gaps(1)
    assert what == "bench.finalize"
    assert secs > 3 * 2e-3


def _ev(plane, line, name, start_us, dur_us):
    return T.Event(plane, line, name, start_us * US, dur_us * US)


DEV = "/device:TPU:0"
ZETA_LOCAL = (
    '%_zeta_jit.510 = s32[256,256]{1,0:T(8,128)S(1)} custom-call('
    's32[256,256]{1,0:T(8,128)S(1)} %reshape.1139), '
    'custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={s32[256,256]{1,0}}, '
    'frontend_attributes={kernel_metadata={}}')
ZETA_PAIR = (
    '%_zeta_jit.511 = s32[256,256]{1,0:T(8,128)S(1)} custom-call('
    's32[256,256]{1,0:T(8,128)S(1)} %_zeta_jit.510, '
    's32[256,256]{1,0:T(8,128)S(1)} %_zeta_jit.510), '
    'custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={s32[256,256]{1,0}, s32[256,256]{1,0}}, '
    'frontend_attributes={kernel_metadata={}}')


def _tpu_trace():
    """Device ops on a TPU's "XLA Ops" line (a while loop with two ops
    nested inside it, two overlapping ops, one op running past the
    window), and the harness's host spans."""
    host = "/host:CPU"
    return [
        _ev(host, "python3", "bench.traced", 50, 930),
        _ev(host, "python3", "bench.execute", 390, 130),
        _ev(host, "python3", "bench.finalize", 880, 80),
        _ev(DEV, "XLA Ops", "%fusion.1 = s32[2,32768]{1,0} fusion(x)",
            100, 200),
        _ev(DEV, "XLA Ops", ZETA_LOCAL, 200, 200),
        _ev(DEV, "XLA Ops", "%while.27 = (u32[], s32[1]) while(t)",
            500, 400),
        _ev(DEV, "XLA Ops", ZETA_PAIR, 550, 100),
        _ev(DEV, "XLA Ops", "%copy.3 = s32[2]{0} copy(a)", 700, 100),
        _ev(DEV, "XLA Ops", "%copy.4 = s32[2]{0} copy(b)", 950, 50),
        _ev(DEV, "XLA Modules", "jit_fn(1)", 100, 900),
    ]


def test_tpu_trace_busy_idle_and_self_time():
    red = T.reduce(_tpu_trace(), "tpu")
    assert red.window_s == pytest.approx(930e-6)
    # union inside [50, 980): [100, 400) + [500, 900) + [950, 980)
    assert red.busy_s == pytest.approx(730e-6)
    assert red.idle_share == pytest.approx(1 - 730 / 930)
    ops = dict(red.top_ops())
    assert ops["fusion.1 s32[2,32768]"] == pytest.approx(200e-6)
    assert ops["_zeta_jit (Pallas kernel)"] == pytest.approx(300e-6)
    # the while's own time excludes the two ops nested in it
    assert ops["while.27 (tuple)"] == pytest.approx(200e-6)
    assert ops["copy.3 s32[2]"] == pytest.approx(100e-6)
    assert ops["copy.4 s32[2]"] == pytest.approx(50e-6)   # not clipped
    assert "jit_fn(1)" not in ops                   # modules are not ops
    gaps = dict(red.top_gaps())
    assert gaps == pytest.approx({"no harness span": 50e-6,
                                  "bench.execute": 100e-6,
                                  "bench.finalize": 50e-6})


def test_busy_is_averaged_over_devices():
    evs = _tpu_trace() + [_ev("/device:TPU:1", "XLA Ops",
                              "%copy.9 = s32[2]{0} copy(c)", 100, 93)]
    red = T.reduce(evs, "tpu")
    assert red.devices == 2
    assert red.busy_s == pytest.approx((730e-6 + 93e-6) / 2)


def test_short_gaps_are_not_looked_up():
    evs = [_ev("/host:CPU", "python3", "bench.traced", 0, 100),
           _ev(DEV, "XLA Ops", "%a.1 = s32[2]{0} copy(x)", 0, 50),
           _ev(DEV, "XLA Ops", "%a.2 = s32[2]{0} copy(x)", 55, 45)]
    red = T.reduce(evs, "tpu")
    assert red.top_gaps() == [["between ops (under 10 us)",
                               pytest.approx(5e-6)]]


@pytest.mark.parametrize("events,platform", [
    # a TPU plane whose operations are on a line of another name
    ([_ev("/host:CPU", "python3", "bench.traced", 0, 100),
      _ev(DEV, "Ops", "%a.1 = s32[2]{0} copy(x)", 0, 50)], "tpu"),
    # a trace with no TPU plane at all, read as a TPU trace
    ([_ev("/host:CPU", "python3", "bench.traced", 0, 100)], "tpu"),
    # a platform the reduction has no form for
    (_tpu_trace(), "gpu")])
def test_a_trace_without_its_device_ops_is_an_error(events, platform):
    """On the chip the device operations come from the TPU planes'
    "XLA Ops" lines alone: a trace without them stops the run rather
    than reading host events in their place."""
    with pytest.raises(ValueError):
        T.reduce(events, platform)


# --------------------------------------------------------------- roofline
def test_parse_call_reads_a_tpu_instruction():
    call = hlo.parse_call(ZETA_LOCAL)
    assert call == {"name": "_zeta_jit.510", "result": ("s32", (256, 256)),
                    "operands": [("s32", (256, 256))]}
    assert hlo.parse_call(ZETA_PAIR)["operands"] == [("s32", (256, 256))] * 2
    assert hlo.parse_call("%copy.3 = s32[2]{0} copy(a)") is None
    assert zeta_roofline.matches(call)
    assert zeta_roofline.bytes_needed(call) == 2 * 256 * 256 * 4
    assert zeta_roofline.bytes_needed(hlo.parse_call(ZETA_PAIR)) == 0


def _pallas_calls(jaxpr) -> list:
    """Every ``pallas_call`` in a jaxpr, nested jaxprs included, as the
    ``{"name", "result", "operands"}`` the roofline functions take."""
    names = {"int32": "s32", "float32": "f32"}
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append({
                "name": zeta_roofline.PREFIX + f".{len(out)}",
                "result": (names[str(eqn.outvars[0].aval.dtype)],
                           tuple(eqn.outvars[0].aval.shape)),
                "operands": [(names[str(v.aval.dtype)], tuple(v.aval.shape))
                             for v in eqn.invars]})
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    out += _pallas_calls(inner)
                elif hasattr(sub, "eqns"):
                    out += _pallas_calls(sub)
    return out


@pytest.mark.parametrize("batch,n", [(1, 12), (2, 15), (4, 13)])
def test_zeta_bytes_from_the_callers_shapes(batch, n):
    """The fused program transforms (B, 2^n) int32 tables through
    ``zeta_batch_op``: a local pass and one pair pass per high block
    bit.  The bytes charged are one read and one write of the table,
    whatever the number of passes."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import mobius_batch_op, zeta_batch_op
    for op in (zeta_batch_op, mobius_batch_op):
        jaxpr = jax.make_jaxpr(lambda f, op=op: op(f, interpret=True))(
            jax.ShapeDtypeStruct((batch, 1 << n), jnp.int32))
        calls = _pallas_calls(jaxpr.jaxpr)
        rows_per_element = (1 << n) // 256
        assert len(calls) == 1 + (rows_per_element // 8).bit_length() - 1
        table = batch * (1 << n) * 4
        assert sum(zeta_roofline.bytes_needed(c) for c in calls) == 2 * table


def _reduced(events, lo=0.0, hi=1e12):
    return types.SimpleNamespace(ops=events, lo_ns=lo, hi_ns=hi)


def test_roofline_share():
    evs = [_ev(DEV, "XLA Ops", ZETA_LOCAL, 0, 1.0)] + [
        _ev(DEV, "XLA Ops", ZETA_PAIR, 1.0 + i, 1.0) for i in range(4)]
    ctx = {"trace": _reduced(evs), "peaks": {"hbm_bytes_per_s": 819e9}}
    need = 2 * 256 * 256 * 4
    assert share(ctx, zeta_roofline) == pytest.approx(
        need / 819e9 / 5e-6 * 100)
    # a call cut by the window's edge is left out, bytes and time alike
    ctx["trace"] = _reduced(evs, lo=1.5 * US)
    assert share(ctx, zeta_roofline) is None
    ctx["trace"] = _reduced([_ev(DEV, "XLA Ops", "%c.1 = s32[2]{0} copy(x)",
                                 0, 1)])
    assert share(ctx, zeta_roofline) is None         # nothing to read
    assert share({"trace": None}, zeta_roofline) is None


# ------------------------------------------------------------------ peaks
def test_peaks_lookup():
    p = harness.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    for kind in ("TPU v4", "cpu", ""):
        with pytest.raises(KeyError, match="not in"):
            harness.peaks_for(kind)


# -------------------------------------------------------------- generator
SEEDS = (7, 3000000001, 2 ** 31 + 12345, 2 ** 62 + 3)
# the cells' mixes, and two test mixes that exercise the generator's open
# loop and its template pool with relabelled repeats
MIXES = {"clique_n15": os.path.join(harness.BENCH, "traffic"),
         "clique_n17": os.path.join(harness.BENCH, "traffic"),
         "mix_open": FIXTURES, "mix_templates": FIXTURES}


def load_mix(name: str) -> gen.Mix:
    return gen.Mix.from_dict(harness.load_json(
        os.path.join(MIXES[name], name + ".json")))


def _digest(q: gen.Query) -> list:
    return [q.n, len(q.edges),
            hashlib.sha256(repr(q.edges).encode()).hexdigest()[:12],
            hashlib.sha256(np.ascontiguousarray(q.card).tobytes())
            .hexdigest()[:16]]


def gen_digest() -> dict:
    """What the generator draws for fixed seeds, for every traffic mix:
    the first requests, the template pool, and the open-loop schedule."""
    out = {}
    for name in MIXES:
        mix = load_mix(name)
        for seed in SEEDS:
            s = gen.Stream(mix, seed)
            rec = {"pool": [_digest(q) for q in s.pool[:4]],
                   "first": [_digest(s.next()) for _ in range(4)]}
            if mix.loop == "open":
                d = gen.open_loop_dues(mix.rate, 10.0)
                rec["dues"] = [len(d)] + [float(x) for x in d[:3]]
            out[f"{name}/{seed}"] = rec
    return out


def test_generator_reproduces_fixture():
    with open(os.path.join(FIXTURES, "generator.json")) as f:
        want = json.load(f)
    assert gen_digest() == want


def test_every_seed_gets_the_same_schedule():
    """Seeds change the data, not the work: each block of fresh queries
    covers the (n, topology) grid once, in the same order for every
    seed, with other graphs and cardinalities."""
    mix = load_mix("mix_open")
    block = len(mix.n_values) * len(mix.topologies)

    def shapes(seed):
        s = gen.Stream(mix, seed)
        return [s.next() for _ in range(2 * block)]
    a, b = shapes(11), shapes(2 ** 40 + 1)
    kinds = [(q.n, q.edges == gen.star(q.n)) for q in a]
    assert kinds == [(q.n, q.edges == gen.star(q.n)) for q in b]
    assert len(set(kinds[:block])) == block
    assert all(not np.array_equal(x.card, y.card) for x, y in zip(a, b))
    dues = gen.open_loop_dues(mix.rate, 10.0)
    assert len(dues) == round(mix.rate * 10.0)
    assert np.all(np.diff(dues) > 0) and 0 < dues[0] and dues[-1] <= 10.0


def test_template_sizes_and_fresh_share_do_not_depend_on_the_seed():
    mix = load_mix("mix_templates")

    def pool(seed):
        return [(q.n, len(q.edges)) for q in gen.Stream(mix, seed).pool]
    a, b = gen.Stream(mix, 3), gen.Stream(mix, 2 ** 40 + 3)
    assert [q.n for q in a.pool] == [q.n for q in b.pool]
    assert pool(3) != pool(2 ** 40 + 3)          # other graphs, same sizes
    fresh = {id(q) for s in (a, b) for q in s.pool}
    for s in (a, b):
        qs = [s.next() for _ in range(3 * gen.BLOCK)]
        # a template is handed out as itself or relabelled (a new object
        # with the same relation count); fresh queries are new objects
        n_fresh = sum(id(q) not in fresh and not any(
            q.n == t.n and sorted(q.card) == sorted(t.card) for t in s.pool)
            for q in qs)
        assert n_fresh == 3 * round(gen.BLOCK * mix.fresh_frac)


def test_relabel_is_the_same_query():
    rng = np.random.default_rng(5)
    q = gen.make_query(rng, 9, "sparse", ("warehouse",), (0, 2))
    perm = rng.permutation(9)
    r = gen.relabel(q, perm)
    mask = sum(1 << int(perm[i]) for i in (0, 3, 4))
    assert r.card[mask] == q.card[0b11001]
    assert len(r.edges) == len(q.edges)


@pytest.mark.parametrize("seed", SEEDS)
def test_mesh_mix_routes_to_the_fused_out_lane(seed):
    """The four-chip cell's queries (n = 15, connected, simple, density
    at most 0.5) take the batch lane's DPccp route under the ceiling a
    4-wide solve mesh lifts to; every one is the source's star."""
    from repro.core import engine
    from repro.service.router import Router, RouterConfig
    cell = harness.load_cell("acyclic_out.mesh4")
    assert engine.sharded_ceiling(13, 4) == 15
    router = Router(RouterConfig(
        fused_out_max_n=engine.sharded_ceiling(13, 4)))
    stream = gen.Stream(cell.mix, seed)
    for _ in range(6):
        q = stream.next()
        assert q.n == 15 and len(q.edges) == 14
        assert len(set(q.edges)) == 14 and all(u < v for u, v in q.edges)
        pq = harness.program_query(q)
        assert pq.is_connected(pq.full_mask) and not pq.hyperedges
        assert 2 * len(q.edges) / (15 * 14) <= 0.5
        route = router.route(pq, cell.mix.cost)
        assert (route.lane, route.method) == ("batch", "dpccp"), route
        assert q.edges == gen.star(15)


# ----------------------------------------------------- window arithmetic
def _resp(status="exact", engine="fused"):
    return types.SimpleNamespace(status=status, meta={"engine": engine})


def _rec(due, sent, done, resp):
    r = harness.Rec(query=None, due=due, sent=sent, done=done)
    r.resp = resp
    return r


def test_window_rates_and_percentiles():
    recs = [_rec(100.1, 100.1, 100.3, _resp()),
            _rec(100.2, 100.25, 100.9, _resp(engine="host")),
            _rec(100.4, 100.4, 100.6, _resp()),
            _rec(100.5, 100.6, 101.5, _resp(status="degraded")),
            _rec(100.6, 100.6, None, None)]
    win = harness.Window(start=100.0, recs=recs)
    # the window closes at the last completion, not at the clock's end
    assert win.close == 101.5
    assert win.elapsed == pytest.approx(1.5)
    # failed: the host rung, a degraded answer, no answer
    assert [win.failed(r) for r in recs] == [False, True, False, True, True]
    assert win.n_failed() == 3
    assert win.plans_per_s() == pytest.approx(2 / 1.5)
    # latency runs from the due time, not the send time; an unanswered
    # request is an infinite one and stays in the tail
    lat = [200.0, 700.0, 200.0, 1000.0]
    assert win.latency_ms(50) == pytest.approx(
        float(np.percentile(lat + [np.inf], 50)))
    assert math.isinf(win.latency_ms(95))
    late = win.lateness_ms()
    assert late["p50"] == pytest.approx(0.0, abs=1e-9)
    assert late["p95"] == pytest.approx(
        float(np.percentile([0, 50, 0, 100, 0], 95)))


def test_metric_readers():
    recs = [_rec(1.0, 1.0, 1.5, _resp()), _rec(1.2, 1.2, 2.0, _resp())]
    win = harness.Window(start=1.0, recs=recs)
    ctx = {"window": win, "setup_s": 42.5, "trace": None,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "layers": {"admit": (2, 0.004), "fast_path": (0, 0.0),
                      "queue_wait": (1, 0.003), "execute": (2, 0.08)}}
    read = {m: harness.load_reader(m) for m in (
        "plans_per_s", "plans_per_s.mesh4", "setup_s", "dispatch_ms.clique",
        "device_idle.clique", "zeta_roofline")}
    assert read["plans_per_s"](ctx) == pytest.approx(2 / 1.0)
    assert read["plans_per_s.mesh4"](ctx) == read["plans_per_s"](ctx)
    assert read["setup_s"](ctx) == 42.5
    assert read["dispatch_ms.clique"](ctx) == pytest.approx(40.0)
    # nothing to read: no dispatch, no trace
    ctx["layers"]["execute"] = (0, 0.0)
    for m in ("dispatch_ms.clique", "device_idle.clique", "zeta_roofline"):
        assert read[m](ctx) is None
    ctx["trace"] = T.reduce(_tpu_trace(), "tpu")
    assert read["device_idle.clique"](ctx) == pytest.approx(
        (1 - 730 / 930) * 100)


# --------------------------------------------------------- BENCHMARK.json
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def chip_problems(workloads: list, configs: dict) -> list:
    """Why the cells' chip counts break the four-chip rule (an empty list
    if they do not): a cell takes 1 chip or 4; at most half the cells,
    rounded down, and one always, take 4; and a cell's configuration
    splits its solve over as many chips as the cell takes
    (``batch_policy.solve_shards``), so that no cell pays for four chips
    and uses one."""
    bad = [f"{w['name']}: {w['chips']} chips" for w in workloads
           if w["chips"] not in (1, 4)]
    four = [w["name"] for w in workloads if w["chips"] == 4]
    if len(four) > max(1, len(workloads) // 2):
        bad.append(f"{len(four)} of {len(workloads)} cells take 4 chips")
    for w in workloads:
        shards = configs[w["config"]].get("batch_policy", {}).get(
            "solve_shards", 1)
        if shards != w["chips"]:
            bad.append(f"{w['name']}: {w['chips']} chips, solve_shards "
                       f"{shards}")
    return bad


def _cell(name, chips, config="c1"):
    return {"name": name, "chips": chips, "config": config}


ONE = {"batch_policy": {}}
FOUR = {"batch_policy": {"solve_shards": 4}}


@pytest.mark.parametrize("workloads,configs,problems", [
    ([_cell("a", 1)], {"c1": ONE}, 0),
    # one four-chip cell is always allowed, alone or beside another
    ([_cell("a", 4, "c4")], {"c4": FOUR}, 0),
    ([_cell("a", 1), _cell("b", 4, "c4")], {"c1": ONE, "c4": FOUR}, 0),
    # two four-chip cells need four cells in all
    ([_cell("a", 1), _cell("b", 4, "c4"), _cell("c", 4, "c4")],
     {"c1": ONE, "c4": FOUR}, 1),
    ([_cell("a", 1), _cell("b", 1), _cell("c", 4, "c4"),
      _cell("d", 4, "c4")], {"c1": ONE, "c4": FOUR}, 0),
    # only 1 or 4 chips
    ([_cell("a", 2, "c4")], {"c4": FOUR}, 2),
    # a four-chip cell whose configuration solves on one chip, and a
    # one-chip cell whose configuration asks for a mesh
    ([_cell("a", 1), _cell("b", 4)], {"c1": ONE}, 1),
    ([_cell("a", 1, "c4")], {"c4": FOUR}, 1)])
def test_four_chip_rule(workloads, configs, problems):
    assert len(chip_problems(workloads, configs)) == problems


def test_benchmark_definitions():
    bm = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bm["configs"]}
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    layer = {m["name"]: m for m in bm["per_layer"]}
    for m in list(e2e.values()) + list(layer.values()):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))
    for m in layer.values():
        assert m["moves"] in e2e
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    files = {c["name"]: harness.load_json(os.path.join(harness.ROOT,
                                                       c["file"]))
             for c in configs.values()}
    assert chip_problems(bm["workloads"], files) == []
    for w in bm["workloads"]:
        assert NAME.match(w["name"])
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"] in configs
        assert cell.config["chips"] == w["chips"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        # a per-layer metric's cell reports the metric it moves
        assert all(m["moves"] in names for m in cell.per_layer)
        assert cell.mix.cost == cell.config["cost"]
    for c in configs.values():
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"]
        # every configuration's cost has its reference
        assert os.path.isfile(os.path.join(harness.COSTS,
                                           cfg["cost"] + ".py"))
