"""Replica-cluster tests (``repro.service.cluster``).

The deterministic loopback harness drives the REAL protocol code —
every frame JSON-round-trips through the wire codec, every op runs
against real ``PlanServer`` replicas on one shared ``VirtualClock`` —
so the chaos schedules (partition, mid-flight replica death, slow
replica) replay bit-for-bit.  Covers: consistent-hash routing (ring
determinism, isomorph co-location), the shared plan-cache tier's
publish -> cluster-wide relabeling-aware hit round trip, failover /
hedging / dead-replica bookkeeping, client-side tenant ceilings, and a
small real-process TCP smoke (spawned ``ReplicaCluster`` with prewarm
manifest shipping).
"""
import dataclasses

import numpy as np
import pytest

from repro.core.querygraph import (chain, make_cardinalities,
                                   permute_card, relabel, star)
from repro.service import (ClusterClient, HashRing, LoopbackTransport,
                           PlanServer, ReplicaCluster, ReplicaState,
                           RuntimeConfig, VirtualClock, faults)
from repro.service import net as net_mod
from repro.service.batch import BatchPolicy
from repro.service.canon import canonicalize
from repro.service.server import PlanRequest


def _host_server() -> PlanServer:
    return PlanServer(enable_batch=False,
                      batch_policy=BatchPolicy(engine="host"))


def _loopback(n=3, injector=None, **cfg_kw):
    """n loopback replicas on one shared VirtualClock."""
    clk = VirtualClock()
    states = {}
    for i in range(n):
        srv = _host_server()
        rt = srv.make_runtime(clock=clk,
                              config=RuntimeConfig(max_batch=1, **cfg_kw),
                              duration_fn=lambda kind, info: 1e-3)
        states[f"r{i}"] = ReplicaState(srv, replica_id=f"r{i}",
                                       runtime=rt)
    transport = LoopbackTransport(states, clock=clk, injector=injector)
    client = ClusterClient(transport, sorted(states))
    return clk, states, transport, client


def _query(seed=0, n=6, topo=chain):
    q = topo(n)
    return q, make_cardinalities(q, seed=seed)


def _isomorph(q, card, seed=0):
    p = [int(x) for x in np.random.default_rng(seed).permutation(q.n)]
    return relabel(q, p), permute_card(np.asarray(card, np.float64),
                                       q.n, p)


# ------------------------------------------------------------- hash ring
def test_ring_deterministic_and_covering():
    ids = [f"r{i}" for i in range(4)]
    a, b = HashRing(ids), HashRing(ids)
    keys = [f"key-{i}" for i in range(200)]
    assert [a.owner(k) for k in keys] == [b.owner(k) for k in keys]
    assert set(a.owner(k) for k in keys) == set(ids)   # all get load


def test_ring_partition_of_fresh_stream_spreads_over_four_replicas():
    """A fresh out-cost stream keyed by canonical form: no ring owner
    of a 4-replica ring holds so much of it that the modeled partition
    makespan (one unit per request) is under 1.5x faster than one
    replica serving everything."""
    from repro.service import WorkloadSpec, make_workload
    reqs = make_workload(WorkloadSpec(
        n_requests=32, seed=0, n_range=(10, 11), fresh_frac=1.0,
        cost_mix=(("out", 1.0),),
        topologies=("chain", "star", "cycle", "sparse")))
    ring = HashRing([f"r{i}" for i in range(4)])
    load: dict = {}
    for r in reqs:
        owner = ring.owner(canonicalize(r.q, r.card).key)
        load[owner] = load.get(owner, 0) + 1
    assert len(reqs) >= 1.5 * max(load.values())


def test_ring_successors_distinct_owner_first():
    ring = HashRing([f"r{i}" for i in range(5)], vnodes=32)
    for k in ("alpha", "beta", "gamma"):
        order = ring.successors(k)
        assert order[0] == ring.owner(k)
        assert sorted(order) == sorted(ring.replica_ids)


def test_ring_rejects_empty_and_isomorphs_colocate():
    with pytest.raises(ValueError):
        HashRing([])
    ring = HashRing([f"r{i}" for i in range(4)])
    q, card = _query(seed=3, n=7, topo=star)
    q2, card2 = _isomorph(q, card, seed=9)
    k1, k2 = canonicalize(q, card).key, canonicalize(q2, card2).key
    assert k1 == k2                         # canonical key is the shard
    assert ring.owner(k1) == ring.owner(k2)


# --------------------------------------------------------- loopback e2e
def test_loopback_plan_parity_and_owner_affinity_hit():
    clk, states, transport, client = _loopback(3)
    q, card = _query(seed=1)
    resp = client.plan(q, card, cost="max", req_id=1)
    ref = _host_server().plan_one(q, card, cost="max")
    assert resp.status == "exact"
    assert float(resp.cost).hex() == float(ref.cost).hex()
    assert resp.tree == ref.tree
    assert not resp.cache_hit
    # the repeat routes to the same ring owner and hits its cache
    again = client.plan(q, card, cost="max", req_id=2)
    assert again.cache_hit
    assert float(again.cost).hex() == float(ref.cost).hex()
    owner = client.ring.owner(canonicalize(q, card).key)
    assert states[owner].server.cache.stats.hits >= 1


def test_shared_cache_publish_then_cluster_wide_isomorph_hit():
    clk, states, transport, client = _loopback(3)
    q, card = _query(seed=2, n=7)
    owner = client.ring.owner(canonicalize(q, card).key)
    # spread mode forces a non-owner to solve -> publish to the owner
    spread = ClusterClient(transport, sorted(states), affinity=False)
    resp = spread.plan(q, card, cost="max", req_id=1)
    assert resp.status == "exact"
    assert spread.stats["publishes"] == 1
    entry_count = states[owner].server.cache.stats.remote_inserts
    assert entry_count == 1
    # ANY isomorph served anywhere in the cluster now hits: the
    # affinity client canonicalizes, routes to the owner, and the
    # published canonical plan answers the relabeled query
    q2, card2 = _isomorph(q, card, seed=5)
    hit = client.plan(q2, card2, cost="max", req_id=2)
    assert hit.cache_hit and hit.status == "exact"
    assert float(hit.cost).hex() == float(resp.cost).hex()
    assert states[owner].server.cache.stats.cross_hits >= 1
    # the relabeled tree is a valid tree over the relabeled query
    ref = _host_server().plan_one(q2, card2, cost="max")
    assert float(hit.cost).hex() == float(ref.cost).hex()
    assert hit.tree == ref.tree


def test_partition_failover_recovers_exact():
    plan = faults.FaultPlan(seed=3, specs=(
        faults.FaultSpec("net", "raise", rate=1.0, max_fires=1),))
    clk, states, transport, client = _loopback(
        3, injector=faults.FaultInjector(plan))
    q, card = _query(seed=4)
    resp = client.plan(q, card, cost="max", req_id=1)
    assert resp.status == "exact"
    ref = _host_server().plan_one(q, card, cost="max")
    assert float(resp.cost).hex() == float(ref.cost).hex()
    assert client.stats["net_errors"] == 1
    assert client.stats["failovers"] == 1
    assert client.stats["replica_deaths"] == 0
    assert not client.dead                  # partition is not a death


def test_replica_death_midflight_failover_and_avoidance():
    plan = faults.FaultPlan(seed=5, specs=(
        faults.FaultSpec("replica", "raise", rate=1.0, max_fires=1),))
    clk, states, transport, client = _loopback(
        3, injector=faults.FaultInjector(plan))
    q, card = _query(seed=6)
    owner = client.ring.owner(canonicalize(q, card).key)
    resp = client.plan(q, card, cost="max", req_id=1)
    assert resp.status == "exact"
    assert client.stats["replica_deaths"] == 1
    assert client.dead == {owner} and transport.dead == {owner}
    calls_before = transport.calls
    again = client.plan(q, card, cost="max", req_id=2)
    # the dead owner is skipped outright: one call, served by the
    # successor's cache (it solved the failed-over first request)
    assert again.cache_hit and again.status == "exact"
    assert transport.calls == calls_before + 1
    assert client.stats["replica_deaths"] == 1


def test_slow_replica_hang_counts_hedge_and_charges_clock():
    plan = faults.FaultPlan(seed=7, specs=(
        faults.FaultSpec("net", "hang", rate=1.0, max_fires=1,
                         hang_s=0.5),))
    clk, states, transport, client = _loopback(
        3, injector=faults.FaultInjector(plan))
    t0 = clk.now()
    q, card = _query(seed=8)
    resp = client.plan(q, card, cost="max", req_id=1)
    assert resp.status == "exact"
    assert client.stats["hedges"] == 1
    assert client.stats["failovers"] == 0
    assert clk.now() >= t0 + 0.5        # the slow replica DID the work
    # hang-lost responses are the ambiguous case: the slow replica
    # executed, so its cache holds the plan even though the client
    # never saw that response
    hung_rid = client.ring.successors(canonicalize(q, card).key)[0]
    assert states[hung_rid].server.cache.stats.misses >= 1


def test_all_replicas_dead_raises_typed_error():
    plan = faults.FaultPlan(seed=9, specs=(
        faults.FaultSpec("replica", "raise", rate=1.0),))
    clk, states, transport, client = _loopback(
        2, injector=faults.FaultInjector(plan))
    q, card = _query(seed=10)
    with pytest.raises(faults.ReplicaDeadError):
        client.plan(q, card, cost="max", req_id=1)
    assert client.stats["replica_deaths"] == 2


def test_client_ceiling_presheds_before_the_network():
    clk, states, transport, client = _loopback(2)
    client.ceilings.update("noisy", 0.9)     # replicas deny 90%
    q, card = _query(seed=11)
    calls0 = transport.calls
    resps = [client.plan(q, card, cost="max", tenant="noisy", req_id=i)
             for i in range(10)]
    shed = [r for r in resps if r.status == "error"]
    assert client.stats["client_shed"] == len(shed) == 9
    assert all(isinstance(r.error, faults.ShedError) for r in shed)
    assert all(r.error.context.get("client") for r in shed)
    # only the single admitted request crossed the transport
    assert transport.calls == calls0 + 1
    # untenanted traffic is never ceiling-limited
    ok = client.plan(q, card, cost="max", req_id=99)
    assert ok.status == "exact"


def test_plan_many_preserves_order():
    clk, states, transport, client = _loopback(2)
    reqs = []
    for i in range(6):
        q, card = _query(seed=20 + i, n=5)
        reqs.append(PlanRequest(q=q, card=card, cost="max", req_id=i))
    resps = client.plan_many(reqs, threads=1)
    assert [r.req_id for r in resps] == list(range(6))
    assert all(r.status == "exact" for r in resps)


def test_loopback_chaos_replays_bit_identical():
    """Same seeded plan, same stream -> identical stats and answers."""
    plan = faults.FaultPlan(seed=13, specs=(
        faults.FaultSpec("net", "raise", rate=0.3),
        faults.FaultSpec("net", "hang", rate=0.1, hang_s=0.2),))

    def run():
        clk, states, transport, client = _loopback(
            3, injector=faults.FaultInjector(plan))
        out = []
        for i in range(8):
            q, card = _query(seed=30 + i % 3, n=5)
            try:
                r = client.plan(q, card, cost="max", req_id=i)
                out.append((r.status, float(r.cost).hex()))
            except faults.NetworkError as e:
                out.append(("raised", e.code))
        return out, dict(client.stats)

    a, b = run(), run()
    assert a == b


# ------------------------------------------------- real processes (TCP)
def test_tcp_cluster_two_replicas_smoke():
    """Spawned server processes behind the asyncio line protocol: plan
    parity, the stats op, and replica-0's prewarm manifest shipped to
    the peer."""
    cluster = ReplicaCluster(2, config={"engine": "host",
                                        "enable_batch": False,
                                        "prewarm_ns": (6,),
                                        "prewarm_costs": ("max",)})
    procs = []
    try:
        client = cluster.start()
        procs = list(cluster.procs)
        assert len(cluster.endpoints) == 2
        assert cluster.manifest, "replica 0 recorded no prewarm manifest"
        reqs = []
        for i in range(4):
            q, card = _query(seed=40 + i, n=6)
            reqs.append(PlanRequest(q=q, card=card, cost="max",
                                    req_id=i))
        resps = client.plan_many(reqs, threads=2)
        for req, resp in zip(reqs, resps):
            ref = _host_server().plan_one(req.q, req.card, cost="max")
            assert resp.status == "exact"
            assert float(resp.cost).hex() == float(ref.cost).hex()
        # the peer accepted the manifest (its server replays the same
        # buckets) and both replicas answer the stats op
        stats = cluster.stats()
        assert set(stats) == {"r0", "r1"}
        for rid, out in stats.items():
            assert out["ok"], rid
            peer_manifest = client.transport.call(
                rid, {"op": "manifest"})["manifest"]
            assert peer_manifest == cluster.manifest
    finally:
        cluster.stop()
    assert procs and all(not p.is_alive() for p in procs)


def test_fused_replica_engine_is_refused():
    """Replicas are host-engine CPU processes; a fused-engine replica
    would contend for the parent's chip or quietly solve on the CPU, so
    the cluster refuses it before starting any process."""
    with pytest.raises(ValueError, match="not supported"):
        ReplicaCluster(2, config={"engine": "fused"})
