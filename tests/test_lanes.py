"""N-lane serving runtime: lane-affine placement, deadline-driven work
stealing, per-lane accounting, and hedged half-open breaker probes —
all on ``VirtualClock`` with injected durations, so every scheduling
decision replays bit-for-bit.

The scale-out contract mirrors the single-lane one: lanes change WHEN
a solve runs and WHERE its AOT executable lives, never WHAT it
computes — asserted here by bitwise response parity between a 1-lane
and a 4-lane runtime on the same request stream.
"""
import dataclasses

import numpy as np

from repro.core.querygraph import chain, make_cardinalities, star
from repro.service import (PlanRequest, PlanServer, RuntimeConfig,
                           VirtualClock, faults)

DUR = {"admit": 0.0, "solve": 1.0, "single": 0.01}


def _dur(kind, info):
    if kind == "solve" and info.get("n") == 6:
        return 0.2                  # small-n buckets solve fast
    return DUR[kind]


def _mk(lanes, max_batch=8, **cfg_kw):
    srv = PlanServer(max_batch=max_batch)
    clk = VirtualClock()
    cfg = RuntimeConfig(max_batch=max_batch, lanes=lanes, **cfg_kw)
    return srv, clk, srv.make_runtime(clock=clk, config=cfg,
                                      duration_fn=_dur)


def _reqs(n, count, cost="max", topo=chain, seed0=0):
    q = topo(n)
    return [PlanRequest(q=q, card=make_cardinalities(q, seed=seed0 + i),
                        cost=cost, req_id=seed0 + i)
            for i in range(count)]


# ------------------------------------------------------------- affinity
def test_lane_affinity_keeps_a_bucket_home():
    """Same (n, cost) bucket -> same lane, even across idle periods
    where round-robin seeding would otherwise rotate: re-placing a
    bucket pays its AOT compile again on the new lane."""
    srv, clk, rt = _mk(lanes=3)
    for r in _reqs(6, 3):
        rt.submit(r)
        rt.drain()                  # every backlog back to zero between
    lanes = rt.stats.lane_dispatches
    assert sum(lanes.values()) == 3
    assert len(lanes) == 1          # one home lane served all three
    home = next(iter(lanes))
    assert rt._affinity[(6, "max")] == home
    assert srv.registry.counter(
        f"runtime.lane{home}.dispatches").value == 3


# ------------------------------------------------------------- stealing
def test_steal_rescues_promised_deadline():
    """A deadline-promised work whose home lane is busy runs on a free
    lane instead of missing: the promise is kept, the steal is
    counted, and nothing is downgraded."""
    srv, clk, rt = _mk(lanes=2)
    big = _reqs(7, 1)[0]            # 1.0 s solve
    small = dataclasses.replace(
        _reqs(6, 1, seed0=50)[0], latency_budget=0.5)   # 0.2 s solve
    rt._affinity[(7, "max")] = 0    # pin both buckets to lane 0
    rt._affinity[(6, "max")] = 0
    rt.submit(big)
    rt.flush()                      # lane 0 now busy until t = 1.0
    t = rt.submit(small)
    assert t.deadline is not None and not t.downgraded
    rt.drain()
    assert rt.stats.steals == 1 and rt.stats.lane_steals == {1: 1}
    assert t.done and t.response is not None and not t.downgraded
    assert t.completed_at <= t.deadline
    assert rt.stats.deadline_misses == 0
    assert rt.stats.lane_dispatches == {0: 1, 1: 1}


def test_no_steal_without_deadline():
    """Best-effort works wait out their home lane's backlog — stealing
    exists to keep promises, not to defeat AOT-cache affinity."""
    srv, clk, rt = _mk(lanes=2)
    rt._affinity[(7, "max")] = 0
    rt._affinity[(6, "max")] = 0
    rt.submit(_reqs(7, 1)[0])
    rt.flush()
    rt.submit(_reqs(6, 1, seed0=60)[0])     # no budget
    rt.drain()
    assert rt.stats.steals == 0
    assert rt.stats.lane_dispatches == {0: 2}


# ------------------------------------------- accounting + bitwise parity
def test_lane_counters_sum_and_bitwise_parity_vs_single_lane():
    """Four buckets spread over four lanes; per-lane dispatch counters
    sum to the total batch count; every response is bit-identical to
    the 1-lane runtime on the same stream."""
    stream = (_reqs(6, 3, cost="max") + _reqs(7, 3, cost="max")
              + _reqs(6, 3, cost="cap", topo=star, seed0=20)
              + _reqs(7, 3, cost="cap", topo=star, seed0=30))

    def run(lanes):
        srv, clk, rt = _mk(lanes=lanes)
        tickets = [rt.submit(r) for r in stream]
        rt.drain()
        return rt, [t.response for t in tickets]

    rt1, resp1 = run(1)
    rt4, resp4 = run(4)
    assert rt1.stats.lane_dispatches == {0: rt1.stats.batches}
    lanes4 = rt4.stats.lane_dispatches
    assert sum(lanes4.values()) == rt4.stats.batches == rt1.stats.batches
    assert len(lanes4) > 1          # the buckets actually spread out
    for a, b in zip(resp1, resp4):
        assert a is not None and b is not None
        assert float(a.cost) == float(b.cost)       # bit-identical
        assert repr(a.tree) == repr(b.tree)
    assert rt4.stats.as_dict()["lanes"] == {
        str(k): {"dispatches": lanes4[k],
                 "steals": rt4.stats.lane_steals.get(k, 0)}
        for k in sorted(lanes4)}


def test_four_lanes_scale_modeled_throughput():
    """Six buckets, one full micro-batch each, distinct cardinalities so
    nothing caches or coalesces: on virtual time with constant solve
    durations, four lanes finish the stream at least 1.5x sooner than
    one, with bit-identical answers."""
    stream = []
    for n in (6, 7, 8):
        for cost, topo in (("max", chain), ("cap", star)):
            stream += _reqs(n, 8, cost=cost, topo=topo,
                            seed0=1000 + len(stream))

    def run(lanes):
        srv = PlanServer(max_batch=8)
        rt = srv.make_runtime(
            clock=VirtualClock(),
            config=RuntimeConfig(max_batch=8, lanes=lanes),
            duration_fn=lambda kind, info: DUR[kind])
        tickets = [rt.submit(r) for r in stream]
        rt.drain()
        return tickets, max(t.completed_at for t in tickets)

    t1, makespan1 = run(1)
    t4, makespan4 = run(4)
    assert makespan1 >= 1.5 * makespan4
    for a, b in zip(t1, t4):
        assert float(a.response.cost) == float(b.response.cost)
        assert repr(a.response.tree) == repr(b.response.tree)


# ------------------------------------------------------- hedged probes
def _half_open_setup(lanes, plan=None):
    srv = PlanServer(max_batch=8)
    clk = VirtualClock()
    cfg = RuntimeConfig(
        max_batch=8, lanes=lanes,
        breaker=faults.BreakerConfig(failure_threshold=1, cooldown_s=0.1))
    inj = faults.FaultInjector(plan) if plan is not None else None
    rt = srv.make_runtime(clock=clk, config=cfg, duration_fn=_dur,
                          injector=inj)
    warm = _reqs(6, 1, seed0=70)[0]
    t0 = rt.submit(warm)
    rt.drain()
    key = rt._breaker_key(t0.route, warm.q.n)
    rt.breakers.on_failure(key)             # threshold 1: lane opens
    assert rt.breakers.state(key) == "open"
    clk.advance(0.2)                        # past cooldown -> half-open
    return srv, clk, rt, key


def test_hedged_probe_winner_answers_and_loser_settles_breaker():
    """A half-open probe on a 2-lane runtime races a host-exact shadow
    on the other lane: the first finisher answers, the dropped loser
    still reports its breaker outcome (an unreported probe would wedge
    the lane half-open forever)."""
    srv, clk, rt, key = _half_open_setup(lanes=2)
    req = _reqs(6, 1, seed0=80)[0]
    t = rt.submit(req)
    assert rt.stats.hedges == 1
    rt.drain()
    assert t.done and t.response is not None and t.status == "exact"
    assert rt.fstats.zombie_completions == 1    # the dropped loser
    assert rt.breakers.state(key) == "closed"   # probe settled the lane
    ref = PlanServer().serve([req])[0][0]
    assert float(t.response.cost) == float(ref.cost)
    assert repr(t.response.tree) == repr(ref.tree)


def test_hedged_probe_survives_probe_failure():
    """The probe leg dies on a still-broken lane; its shadow answers
    the ticket anyway (no failure-ladder descent for the request), and
    the failed probe re-opens the breaker."""
    plan = faults.FaultPlan(seed=0, specs=(
        # after=1: skip the warm-up solve, kill the probe dispatch
        faults.FaultSpec("dispatch", "raise", rate=1.0, after=1,
                         max_fires=1),))
    srv, clk, rt, key = _half_open_setup(lanes=2, plan=plan)
    req = _reqs(6, 1, seed0=90)[0]
    t = rt.submit(req)
    assert rt.stats.hedges == 1
    rt.drain()
    assert t.done and t.response is not None
    assert rt.breakers.state(key) == "open"     # failed probe re-opened
    ref = PlanServer().serve([req])[0][0]
    assert float(t.response.cost) == float(ref.cost)


def test_single_lane_probe_is_not_hedged():
    """lanes = 1 has no lane to spare: the probe stays solo (the
    pre-scale-out behavior, bit for bit)."""
    srv, clk, rt, key = _half_open_setup(lanes=1)
    t = rt.submit(_reqs(6, 1, seed0=95)[0])
    assert rt.stats.hedges == 0
    rt.drain()
    assert t.done and t.response is not None
    assert rt.breakers.state(key) == "closed"
