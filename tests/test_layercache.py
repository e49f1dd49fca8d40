"""Layer-granular plan-fragment cache tests (incremental planning).

Covers the cross-request reuse tier (``repro.service.layercache``) and
the cache-correctness bugfix sweep that rides with it:

* search fragments — a cached C_max optimum collapses the feasibility
  binary-search bracket for repeats AND for C_cap pass 1 (cross-lane);
* value fragments — solved C_out sub-tables transfer to supergraph
  queries that contain the same canonical subproblem under any
  relabeling (``canon.subset_signature``'s fragment-canonical space);
* the prime contract, property-tested: seeds are pure perf hints —
  seeded solves are **bitwise identical** to cold solves, across
  topologies, cost functions, relabelings and stale seeds;
* degraded-plan poisoning (the bugfix): a best-effort GOO plan cached
  under the primary key is never served to an exact-capable request,
  and a fresh exact solve replaces the degraded entry — exercised
  through the async runtime's budget-reroute path;
* the quarantine TTL boundary (the audit): refused on ``[t0, t0+ttl)``,
  admitted at exactly ``t0 + ttl``.
"""
import collections
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import engine as engine_mod
from repro.core.dpconv import optimize
from repro.core.querygraph import (QueryGraph, chain, clique, cycle,
                                   make_cardinalities, permute_card,
                                   relabel, star)
from repro.service import (PlanRequest, PlanServer, RuntimeConfig,
                           VirtualClock, WorkloadSpec, make_workload)
from repro.service import faults
from repro.service import layercache as layercache_mod
from repro.service.batch import BatchPolicy
from repro.service.canon import (canonicalize, induced_subproblem,
                                 subset_expand, subset_fingerprint,
                                 subset_signature)
from repro.service.layercache import LayerCache

TOPOLOGIES = {"chain": chain, "star": star, "clique": clique}
DUR = {"admit": 0.0, "solve": 1.0, "single": 0.01}


def _solve(q, card, cost, **seed_kw):
    """Mirror the server's exact fused routes, with optional seeds."""
    if cost == "max":
        return optimize(q, card, cost="max", engine="fused", **seed_kw)
    if cost == "cap":
        return optimize(q, card, cost="cap", engine="fused", **seed_kw)
    return optimize(q, card, cost="out", method="dpccp", engine="fused",
                    **seed_kw)


def _seed_kw(seed, cost):
    if seed is None:
        return {}
    if "opt" in seed and cost in ("max", "cap"):
        return {"seed_opt": float(seed["opt"])}
    if "vals" in seed and cost == "out":
        return {"seed_vals": seed["vals"], "seed_ok": seed["ok"]}
    return {}


def _same_tree(a, b) -> bool:
    return repr(a.tree) == repr(b.tree)


# -------------------------------------------------------- fragment store
def test_search_fragment_roundtrip_and_cross_lane():
    """A C_max optimum inserted under the canonical key seeds BOTH the
    max repeat and the C_cap pass-1 search of the same form."""
    q = clique(6)
    card = make_cardinalities(q, seed=1)
    form = canonicalize(q, card)
    lc = LayerCache()
    assert lc.seed_for(form, "max") is None
    assert lc.stats.search_misses == 1

    cold = _solve(form.q, form.card, "max")
    lc.observe(form, "max", cold.cost, cold.meta)
    assert lc.stats.search_inserts == 1
    for cost in ("max", "cap"):
        seed = lc.seed_for(form, cost)
        assert seed == {"opt": float(cold.cost)}
    assert lc.stats.search_hits == 2
    # the plan cache would key (form, cost, method) and miss max->cap;
    # the search fragment is keyed by form alone — that IS the feature
    assert lc.seed_for(canonicalize(q, card * 2.0), "max") is None


def test_value_fragment_transfers_to_relabeled_subgraph():
    """A solved chain(7) C_out table seeds a later chain(6) query that
    is its leave-one-out induced subproblem under a random relabeling —
    the layer-granular reuse the plan cache cannot express."""
    big = chain(7)
    card_big = make_cardinalities(big, seed=3)
    form_big = canonicalize(big, card_big)
    cold_big = _solve(form_big.q, form_big.card, "out")
    lc = LayerCache()
    lc.observe(form_big, "out", cold_big.cost, cold_big.meta,
               dp=cold_big.meta["dp_table"])
    assert lc.stats.value_inserts == form_big.q.n + 1

    # chain(7) restricted to its first 6 relations IS chain(6) with the
    # truncated cardinality table; relabel it to hide the provenance
    small = chain(6)
    card_small = card_big[: 1 << 6].copy()
    rng = np.random.default_rng(7)
    perm = rng.permutation(6)
    q2 = relabel(small, perm)
    card2 = permute_card(card_small, 6, perm)
    form2 = canonicalize(q2, card2)
    seed = lc.seed_for(form2, "out")
    assert seed is not None and lc.stats.value_hits >= 1
    ok = np.asarray(seed["ok"])
    pc = np.array([bin(i).count("1") for i in range(1 << 6)])
    assert not ok[pc < 2].any()      # recurrence starts at layer 2
    assert ok[(1 << 6) - 1]          # the full subset is covered
    # seeded values replay the cold table bitwise wherever claimed
    cold2 = _solve(form2.q, form2.card, "out")
    dp2 = cold2.meta["dp_table"]
    assert np.array_equal(np.asarray(seed["vals"])[ok], dp2[ok])
    warm2 = _solve(form2.q, form2.card, "out", **_seed_kw(seed, "out"))
    assert float(warm2.cost) == float(cold2.cost)
    assert _same_tree(warm2, cold2)


def test_value_store_lru_eviction():
    lc = LayerCache(value_capacity=4)
    for s in range(3):
        q = chain(5)
        card = make_cardinalities(q, seed=100 + s)
        form = canonicalize(q, card)
        r = _solve(form.q, form.card, "out")
        lc.observe(form, "out", r.cost, r.meta, dp=r.meta["dp_table"])
    assert lc.stats.evictions > 0


# -------------------------------------------------- bitwise parity (prop)
@settings(max_examples=12, deadline=None)
@given(top=st.sampled_from(sorted(TOPOLOGIES)),
       n=st.integers(min_value=5, max_value=7),
       card_seed=st.integers(min_value=0, max_value=10_000),
       cost=st.sampled_from(["max", "out", "cap"]),
       perm_seed=st.integers(min_value=0, max_value=10_000))
def test_seeded_solve_bitwise_equals_cold(top, n, card_seed, cost,
                                          perm_seed):
    """The prime contract: for random share patterns (same canonical
    problem re-arriving under a random relabeling), a layer-cache-seeded
    solve returns the bitwise-identical optimum and join tree of the
    cold solve, on every fused lane."""
    q = TOPOLOGIES[top](n)
    card = make_cardinalities(q, seed=card_seed)
    form = canonicalize(q, card)
    cold = _solve(form.q, form.card, cost)
    lc = LayerCache()
    lc.observe(form, cost, cold.cost, cold.meta,
               dp=cold.meta.get("dp_table"))

    perm = np.random.default_rng(perm_seed).permutation(n)
    q2, card2 = relabel(q, perm), permute_card(card, n, perm)
    form2 = canonicalize(q2, card2)
    assert form2.key == form.key     # canonicalization absorbs the perm
    seed = lc.seed_for(form2, cost)
    assert seed is not None
    warm = _solve(form2.q, form2.card, cost, **_seed_kw(seed, cost))
    assert float(warm.cost) == float(cold.cost)   # bitwise, not approx
    assert _same_tree(warm, cold)
    if cost == "out":
        assert np.array_equal(warm.meta["dp_table"],
                              cold.meta["dp_table"])


def test_stale_search_seed_is_ignored():
    """A wrong cached optimum must not change the result: the seeded
    program VERIFIES the hypothesis with a dual feasibility probe, so a
    stale seed — below the optimum (infeasible candidate), above it
    (feasible-but-not-minimal candidate), or foreign (not a candidate at
    all) — shrinks the bracket at worst and the search converges to the
    bitwise-cold answer on both search lanes."""
    q = clique(6)
    card = make_cardinalities(q, seed=11)
    form = canonicalize(q, card)
    cand = engine_mod.candidate_table(form.card, form.q.n)
    for cost in ("max", "cap"):
        cold = _solve(form.q, form.card, cost)
        stales = (float(cand[0]),        # smallest candidate: infeasible
                  float(cand[-1]),       # largest: feasible, not minimal
                  float(cold.cost) * 3.0,          # foreign value
                  np.inf)                          # non-finite: no seed
        for stale in stales:
            warm = _solve(form.q, form.card, cost, seed_opt=stale)
            assert float(warm.cost) == float(cold.cost), (cost, stale)
            assert _same_tree(warm, cold)


def test_seed_bracket_collapses_rounds():
    """Engine-level: a correct cached optimum costs exactly ONE round —
    the dual verification probe — instead of the cold ~log2(C) search;
    the while loop itself contributes zero rounds."""
    q = clique(8)
    card = make_cardinalities(q, seed=5)
    form = canonicalize(q, card)
    engine_mod.reset_stats()
    cold = _solve(form.q, form.card, "max")
    cold_rounds = engine_mod.stats().rounds
    engine_mod.reset_stats()
    warm = _solve(form.q, form.card, "max", seed_opt=float(cold.cost))
    assert engine_mod.stats().rounds == 1 < cold_rounds
    assert float(warm.cost) == float(cold.cost)
    assert _same_tree(warm, cold)


# ------------------------------------------------------- service wiring
def _server(**kw):
    kw.setdefault("batch_policy", BatchPolicy(engine="fused"))
    return PlanServer(**kw)


def test_server_threads_seeds_and_reports_provider():
    """Serving the same stream twice on one server scores layer hits on
    the second pass, keeps responses bitwise stable, publishes stats on
    the metrics registry, and never leaks a dp table into responses."""
    spec = WorkloadSpec(n_requests=24, seed=2, n_range=(6, 7),
                        pool_size=4, cost_mix=(("max", 0.5),
                                               ("out", 0.3),
                                               ("cap", 0.2)))
    reqs = make_workload(spec)
    srv = _server(enable_cache=False)
    a, _ = srv.serve(list(reqs), closed_loop=True)
    b, _ = srv.serve(list(reqs), closed_loop=True)
    st_ = srv.layers.stats
    assert st_.search_hits > 0 and st_.seeded_solves > 0
    for ra, rb in zip(a, b):
        assert float(ra.cost) == float(rb.cost)
        assert repr(ra.tree) == repr(rb.tree)
        assert "dp_table" not in ra.meta and "dp_table" not in rb.meta
    snap = srv.registry.snapshot()
    prov = snap["providers"]["layercache"]
    assert prov["search_hits"] == st_.search_hits
    assert prov["seeded_solves"] == st_.seeded_solves


def test_server_cross_lane_max_then_cap_warm_start():
    q = clique(6)
    card = make_cardinalities(q, seed=9)
    srv = _server(enable_cache=False)
    r_max = srv.plan_one(q, card, cost="max")
    r_cap = srv.plan_one(q, card, cost="cap")
    assert srv.layers.stats.search_hits >= 1
    assert srv.layers.stats.seeded_solves >= 1
    ref = optimize(q, card, cost="cap", engine="host")
    assert float(r_cap.cost) == float(ref.cost)
    assert float(r_max.cost) == float(
        optimize(q, card, cost="max", engine="host").cost)


# --------------------------------------- degraded-plan poisoning bugfix
def _runtime(srv):
    clk = VirtualClock()
    rt = srv.make_runtime(clock=clk, config=RuntimeConfig(max_batch=8),
                          duration_fn=lambda kind, info: DUR[kind])
    return clk, rt


def test_degraded_plan_never_served_to_exact_capable_runtime():
    """The poisoning fix, through the runtime's budget-reroute path: a
    deadline-pressed request caches its GOO plan under the PRIMARY key
    tagged degraded; a later exact-capable request for the same query
    must miss through, solve exactly, and replace the entry — after
    which even pressed requests are served the exact plan."""
    reqs = make_workload(WorkloadSpec(n_requests=24, seed=0,
                                      n_range=(6, 7), pool_size=6))
    base = next(r for r in reqs if r.cost == "max" and r.q.n >= 6)
    pressed = dataclasses.replace(base, latency_budget=1e-12,
                                  req_id=901)
    srv = _server()
    clk, rt = _runtime(srv)
    t1 = rt.submit(pressed)
    rt.drain()
    assert t1.done and t1.response.status == "degraded"

    t2 = rt.submit(dataclasses.replace(base, req_id=902))
    rt.drain()
    assert t2.done and t2.response.status == "exact"
    assert not t2.response.cache_hit          # missed THROUGH the entry
    assert srv.cache.stats.degraded_skips >= 1
    assert float(t2.response.cost) <= float(t1.response.cost)

    # the exact solve replaced the degraded entry: a pressed repeat now
    # fast-paths onto the exact plan instead of the stale GOO one
    t3 = rt.submit(dataclasses.replace(pressed, req_id=903))
    rt.drain()
    assert t3.done and t3.response.cache_hit
    assert t3.response.status == "exact"
    assert float(t3.response.cost) == float(t2.response.cost)


def test_degraded_insert_never_clobbers_exact():
    """Order reversed: once an exact plan is cached, a later degraded
    solve for the same key must not overwrite it."""
    reqs = make_workload(WorkloadSpec(n_requests=24, seed=0,
                                      n_range=(6, 7), pool_size=6))
    base = next(r for r in reqs if r.cost == "max" and r.q.n >= 6)
    srv = _server()
    r_exact = srv.serve([base], closed_loop=True)[0][0]
    assert r_exact.status == "exact"
    pressed = dataclasses.replace(base, latency_budget=1e-12,
                                  req_id=904)
    r2 = srv.serve([pressed], closed_loop=True)[0][0]
    # the pressed repeat is served straight from the exact entry
    assert r2.cache_hit and r2.status == "exact"


def test_model_trace_replay_seeded_equals_cold():
    """Reuse on model-trace traffic: the einsum replay pool's repeated
    layers, served with the plan cache off so every request solves,
    hit the fragment store and seed solves, and the seeded responses
    are bitwise identical to cold ones.  A deadline-pressed replay with
    the plan cache on never serves a degraded plan to a request that
    carries no budget."""
    from repro.service import make_einsum_workload
    from repro.service.workload import einsum_replay_pool

    spec = WorkloadSpec(n_requests=24, seed=5,
                        cost_mix=(("max", 0.55), ("out", 0.30),
                                  ("cap", 0.15)),
                        relabel_frac=0.4)
    reqs = make_einsum_workload(spec, contractions=einsum_replay_pool())
    cold, _ = _server(enable_cache=False,
                      enable_layer_cache=False).serve(list(reqs),
                                                      closed_loop=True)
    srv = _server(enable_cache=False)
    srv.serve(list(reqs), closed_loop=True)   # fills the fragment store
    srv.layers.stats = layercache_mod.LayerCacheStats()
    seeded, _ = srv.serve(list(reqs), closed_loop=True)
    st_ = srv.layers.stats
    assert st_.search_hits + st_.value_hits > 0
    assert st_.seeded_solves > 0
    for c, s in zip(cold, seeded):
        assert float(c.cost) == float(s.cost)
        assert repr(c.tree) == repr(s.tree)

    pressed = make_einsum_workload(
        dataclasses.replace(spec, seed=6, budget_frac=0.3, budget_s=1e-6),
        contractions=einsum_replay_pool())
    resps, _ = _server().serve(list(pressed), closed_loop=True)
    assert any(r.status == "degraded" for r in resps)
    assert all(r.status != "degraded"
               for req, r in zip(pressed, resps)
               if req.latency_budget is None)


# ------------------------------------------------ quarantine TTL bound
def test_quarantine_ttl_boundary_half_open():
    """Refused on [t0, t0+ttl); admitted at exactly t0+ttl — 'refused
    until the TTL expires', with the boundary pinned on VirtualClock."""
    clk = VirtualClock()
    qt = faults.Quarantine(clk, ttl_s=5.0)
    qt.add("k", reason="test")
    assert qt.active("k")                     # t0: refused
    clk.advance_to(5.0 - 1e-9)
    assert qt.active("k")                     # just inside: refused
    clk.advance_to(5.0)
    assert not qt.active("k")                 # exactly t0+ttl: admitted
    assert qt.expired == 1
    assert not qt.active("k")                 # and the entry is gone
    assert qt.snapshot()["live"] == 0


# ---------------------------------------------- persistence (save/load)
def _populated_cache():
    """A cache holding one search fragment and chain(6)'s n+1 value
    fragments — both store kinds, heterogeneous fragment lengths."""
    lc = LayerCache()
    qm = clique(6)
    card_m = make_cardinalities(qm, seed=21)
    form_m = canonicalize(qm, card_m)
    cold_m = _solve(form_m.q, form_m.card, "max")
    lc.observe(form_m, "max", cold_m.cost, cold_m.meta)
    qo = chain(6)
    card_o = make_cardinalities(qo, seed=22)
    form_o = canonicalize(qo, card_o)
    cold_o = _solve(form_o.q, form_o.card, "out")
    lc.observe(form_o, "out", cold_o.cost, cold_o.meta,
               dp=cold_o.meta["dp_table"])
    return lc, form_m, form_o


def test_save_load_roundtrip_replays_both_fragment_kinds(tmp_path):
    lc, form_m, form_o = _populated_cache()
    path = str(tmp_path / "layers.npz")
    saved = lc.save(path)
    assert saved == len(lc) > 1

    lc2 = LayerCache()
    assert lc2.load(path) == saved
    assert len(lc2) == len(lc)
    # search fragments replay exactly
    assert lc2.seed_for(form_m, "max") == lc.seed_for(form_m, "max")
    # value fragments replay bitwise, heterogeneous lengths intact
    a = lc.seed_for(form_o, "out")
    b = lc2.seed_for(form_o, "out")
    assert a is not None and b is not None
    assert np.array_equal(a["ok"], b["ok"])
    assert a["vals"][a["ok"]].tobytes() == b["vals"][b["ok"]].tobytes()
    # loading on top of live entries counts only NEW keys
    assert lc.load(path) == 0


def test_load_is_best_effort_on_missing_version_and_corruption(tmp_path):
    lc, _, _ = _populated_cache()
    path = str(tmp_path / "layers.npz")
    lc.save(path)

    assert LayerCache().load(str(tmp_path / "nope.npz")) == 0
    # version mismatch: well-formed archive, wrong stamp
    with np.load(path) as z:
        stale = {k: z[k] for k in z.files}
    stale["version"] = np.int64(layercache_mod.STORE_VERSION + 1)
    vpath = str(tmp_path / "stale.npz")
    np.savez_compressed(vpath, **stale)
    assert LayerCache().load(vpath) == 0
    # a version-1 store (no value fingerprints) is a cold start, and so
    # is a current stamp on an archive that lacks them
    v1 = {k: v for k, v in stale.items() if k != "value_fps"}
    v1["version"] = np.int64(1)
    v1path = str(tmp_path / "v1.npz")
    np.savez_compressed(v1path, **v1)
    assert LayerCache().load(v1path) == 0
    v1["version"] = np.int64(layercache_mod.STORE_VERSION)
    np.savez_compressed(v1path, **v1)
    assert LayerCache().load(v1path) == 0
    # truncated/garbage file
    cpath = tmp_path / "corrupt.npz"
    cpath.write_bytes(open(path, "rb").read()[:40])
    assert LayerCache().load(str(cpath)) == 0
    (tmp_path / "text.npz").write_text("not an archive")
    assert LayerCache().load(str(tmp_path / "text.npz")) == 0
    # inconsistent internal shapes (search keys/vals disagree)
    bad = dict(stale)
    bad["version"] = np.int64(layercache_mod.STORE_VERSION)
    bad["search_vals"] = np.zeros(len(bad["search_keys"]) + 3)
    bpath = str(tmp_path / "bad.npz")
    np.savez_compressed(bpath, **bad)
    assert LayerCache().load(bpath) == 0


def test_load_respects_configured_capacities(tmp_path):
    lc = LayerCache()
    for s in range(3):
        q = chain(6)
        card = make_cardinalities(q, seed=300 + s)
        form = canonicalize(q, card)
        cold = _solve(form.q, form.card, "out")
        lc.observe(form, "out", cold.cost, cold.meta,
                   dp=cold.meta["dp_table"])
    path = str(tmp_path / "layers.npz")
    saved = lc.save(path)
    assert saved > 4
    small = LayerCache(value_capacity=4)
    small.load(path)
    assert len(small._values) == 4


# ------------------------------------------------- admission heuristic
def test_admission_gate_stops_one_off_topologies():
    """A signature whose probes never hit stops inserting after
    ``admission_min_probes`` — ad-hoc shapes can't churn the LRU."""
    lc = LayerCache(admission_min_probes=4, admission_floor=0.5)
    forms = []
    for s in range(5):
        q = clique(5)
        card = make_cardinalities(q, seed=400 + s)
        forms.append(canonicalize(q, card))
    sig = forms[0].signature
    assert all(f.signature == sig for f in forms)   # one topology class
    # 3 probes, all misses: history below min_probes still admits
    for f in forms[:3]:
        assert lc.seed_for(f, "max") is None
    cold = _solve(forms[0].q, forms[0].card, "max")
    lc.observe(forms[0], "max", cold.cost, cold.meta)
    assert lc.stats.search_inserts == 1
    assert lc.stats.admission_skips == 0
    # the 4th all-miss probe crosses min_probes at hit rate 1/4 < 0.5:
    # the gate closes
    assert lc.seed_for(forms[3], "max") is None
    before = lc.stats.search_inserts
    cold4 = _solve(forms[3].q, forms[3].card, "max")
    lc.observe(forms[3], "max", cold4.cost, cold4.meta)
    assert lc.stats.search_inserts == before        # nothing inserted
    assert lc.stats.admission_skips == 1
    assert lc.seed_for(forms[4], "max") is None     # and nothing leaks


def test_admission_gate_keeps_paying_topologies_and_can_be_disabled():
    lc = LayerCache(admission_min_probes=4, admission_floor=0.5)
    q = chain(6)
    card = make_cardinalities(q, seed=500)
    form = canonicalize(q, card)
    assert lc.seed_for(form, "max") is None
    cold = _solve(form.q, form.card, "max")
    lc.observe(form, "max", cold.cost, cold.meta)
    # repeats hit: the signature's history is 1 miss + 5 hits, so the
    # gate stays open past min_probes and later inserts still land
    for _ in range(5):
        assert lc.seed_for(form, "max") is not None
    card2 = make_cardinalities(q, seed=501)
    form2 = canonicalize(q, card2)
    assert form2.signature == form.signature
    cold2 = _solve(form2.q, form2.card, "max")
    lc.observe(form2, "max", cold2.cost, cold2.meta)
    assert lc.stats.search_inserts == 2
    assert lc.stats.admission_skips == 0
    # admission_min_probes <= 0 disables the gate outright
    off = LayerCache(admission_min_probes=0, admission_floor=0.5)
    for s in range(6):
        qq = clique(5)
        cc = make_cardinalities(qq, seed=600 + s)
        ff = canonicalize(qq, cc)
        assert off.seed_for(ff, "max") is None
        sol = _solve(ff.q, ff.card, "max")
        off.observe(ff, "max", sol.cost, sol.meta)
    assert off.stats.search_inserts == 6
    assert off.stats.admission_skips == 0


# ------------------------------------------------- fingerprint prefilter
FP_TOPOLOGIES = {"chain": chain, "star": star, "cycle": cycle,
                 "clique": clique}


def _probe_masks(n: int) -> list:
    full = (1 << n) - 1
    return [full] + [full ^ (1 << i) for i in range(n)]


def _pure_dp(card) -> np.ndarray:
    """A stand-in C_out table that, like the real one, is a pure
    function of each subset's induced sub-problem (here of ``c(S)``
    alone), so fragments transfer consistently between queries."""
    return np.log1p(np.asarray(card, np.float64)) * 3.0 + 0.125


def _reference_probe(lc, form):
    """The value probe as it was before the fingerprint check: every
    subset's canonical signature computed, looked up in ``_values``.
    Reads the store without touching it; returns (payload, hits,
    misses)."""
    n = form.q.n
    vals = np.zeros(1 << n, np.float64)
    ok = np.zeros(1 << n, bool)
    hits = misses = 0
    for mask in _probe_masks(n):
        if ok[mask]:
            continue
        sf = subset_signature(form.q, form.card, mask)
        frag = lc._values.get(sf.key)
        if frag is None:
            misses += 1
            continue
        hits += 1
        expand = subset_expand(sf.rels)
        vals[expand] = frag[layercache_mod._perm_masks(sf.perm)]
        ok[expand] = True
    if not hits:
        return None, hits, misses
    ok[layercache_mod._popcounts(n) < 2] = False
    return {"vals": vals, "ok": ok}, hits, misses


def _assert_index_consistent(lc):
    assert set(lc._value_fp) == set(lc._values)
    assert lc._fp_count == collections.Counter(lc._value_fp.values())
    assert all(c > 0 for c in lc._fp_count.values())


def _assert_probe_matches_reference(lc, form):
    """Soundness at every probed subset, then ``seed_for`` against the
    reference probe: payload bit for bit, same hit and miss counts."""
    for mask in _probe_masks(form.q.n):
        if subset_signature(form.q, form.card, mask).key in lc._values:
            fp = subset_fingerprint(form.q, form.card, mask)
            assert lc._fp_count[fp] > 0, "the fingerprint rejected a match"
    ref, hits, misses = _reference_probe(lc, form)
    h0, m0 = lc.stats.value_hits, lc.stats.value_misses
    lc._probe_memo.clear()              # probe afresh, not from the memo
    got = lc.seed_for(form, "out")
    assert lc.stats.value_hits - h0 == hits
    assert lc.stats.value_misses - m0 == misses
    if ref is None:
        assert got is None
        return 0
    assert got is not None
    assert got["ok"].tobytes() == ref["ok"].tobytes()
    assert got["vals"].tobytes() == ref["vals"].tobytes()
    return hits


def _relabeled_leave_one_out(q, card, i, perm_seed):
    """The sub-problem of ``(q, card)`` without relation ``i``, as a
    query of its own under a random relabeling."""
    q_sub, card_sub, _ = induced_subproblem(q, card,
                                            q.full_mask ^ (1 << i))
    perm = np.random.default_rng(perm_seed).permutation(q.n - 1)
    return canonicalize(relabel(q_sub, perm),
                        permute_card(card_sub, q.n - 1, perm))


@pytest.mark.parametrize("top", sorted(FP_TOPOLOGIES))
@settings(max_examples=6, deadline=None)
@given(n=st.integers(min_value=5, max_value=9),
       card_seed=st.integers(min_value=0, max_value=10_000),
       perm_seed=st.integers(min_value=0, max_value=10_000),
       capacity=st.integers(min_value=3, max_value=24))
def test_fingerprint_prefilter_never_rejects_a_match(tmp_path_factory, top,
                                                     n, card_seed,
                                                     perm_seed, capacity):
    """Soundness of the fingerprint check: through inserts, LRU
    evictions at a small capacity, ``clear`` and a save/load round
    trip, every probed subset whose signature is stored passes the
    check, and ``seed_for`` returns the reference probe's payload bit
    for bit with the same hit and miss counts."""
    q = FP_TOPOLOGIES[top](n)
    card = make_cardinalities(q, seed=card_seed)
    form = canonicalize(q, card)
    lc = LayerCache(value_capacity=capacity, admission_min_probes=0)
    lc.observe(form, "out", 0.0, {}, dp=_pure_dp(form.card))
    # a distractor of the same shape: fresh cardinalities, more evictions
    other = canonicalize(q, make_cardinalities(q, seed=card_seed + 1))
    lc.observe(other, "out", 0.0, {}, dp=_pure_dp(other.card))
    lc.observe(form, "out", 0.0, {}, dp=_pure_dp(form.card))
    _assert_index_consistent(lc)

    perm = np.random.default_rng(perm_seed).permutation(n)
    whole = canonicalize(relabel(q, perm), permute_card(card, n, perm))
    probes = [whole] + [_relabeled_leave_one_out(q, card, i, perm_seed + i)
                        for i in range(n)]
    hits = sum(_assert_probe_matches_reference(lc, f) for f in probes)
    assert hits >= 1                   # the whole query's set is stored
    _assert_index_consistent(lc)

    path = str(tmp_path_factory.mktemp("fp") / "layers.npz")
    lc.save(path)
    back = LayerCache(value_capacity=capacity, admission_min_probes=0)
    assert back.load(path) == len(lc._values)
    _assert_index_consistent(back)
    assert back._value_fp == lc._value_fp
    for f in probes:
        _assert_probe_matches_reference(back, f)

    lc.clear()
    assert not lc._value_fp and not lc._fp_count
    for f in probes:
        assert _assert_probe_matches_reference(lc, f) == 0


def test_fingerprint_is_relabeling_invariant_with_hyperedges():
    """Equal signature keys give equal fingerprints on a hypergraph,
    whose hyperedge count is part of the fingerprint."""
    q = QueryGraph(6, ((0, 1), (1, 2), (2, 3), (3, 4), (4, 5)),
                   ((0b000011, 0b100000), (0b000100, 0b011000)))
    card = np.random.default_rng(3).uniform(1.0, 1e6, 1 << 6)
    perm = (3, 0, 5, 1, 4, 2)
    q2, card2 = relabel(q, perm), permute_card(card, 6, perm)
    for mask in range(1, 1 << 6):
        mask2 = sum(1 << perm[i] for i in range(6) if (mask >> i) & 1)
        a = subset_signature(q, card, mask)
        b = subset_signature(q2, card2, mask2)
        assert a.key == b.key
        assert (subset_fingerprint(q, card, mask)
                == subset_fingerprint(q2, card2, mask2))
    # a hyperedge fully inside the subset is counted; with the same
    # edges and cardinalities, dropping it changes the fingerprint
    plain = QueryGraph(6, q.edges, q.hyperedges[1:])
    assert (subset_fingerprint(q, card, 0b111111)
            != subset_fingerprint(plain, card, 0b111111))


def test_fresh_star_probes_skip_the_signature(monkeypatch):
    """Fresh cardinalities can never hit: against a store filled from
    other fresh star queries, ``seed_for`` computes no subset signature,
    answers all n+1 subsets by fingerprint, and the ``layercache``
    provider reports the count.  After evictions the fingerprint index
    equals the one recomputed from the keys that remain."""
    n = 10
    srv = _server(enable_cache=False)
    lc = srv.layers
    lc.value_capacity = 30
    lc.admission_min_probes = 0
    expected_fp = {}
    for s in range(4):
        q = star(n)
        form = canonicalize(q, make_cardinalities(q, seed=700 + s))
        for mask in _probe_masks(n):
            expected_fp[subset_signature(form.q, form.card, mask).key] = \
                subset_fingerprint(form.q, form.card, mask)
        lc.observe(form, "out", 0.0, {}, dp=_pure_dp(form.card))
    assert lc.stats.evictions == 4 * (n + 1) - 30
    assert len(lc._values) == 30
    assert lc._fp_count == collections.Counter(
        expected_fp[k] for k in lc._values)
    _assert_index_consistent(lc)

    calls = []
    real = layercache_mod.subset_signature

    def counting(*args, **kw):
        calls.append(args[2])
        return real(*args, **kw)

    monkeypatch.setattr(layercache_mod, "subset_signature", counting)
    for s in range(3):
        q = star(n)
        form = canonicalize(q, make_cardinalities(q, seed=800 + s))
        before = lc.stats.value_prefilter_misses
        misses = lc.stats.value_misses
        assert lc.seed_for(form, "out") is None
        assert lc.stats.value_prefilter_misses - before == n + 1
        assert lc.stats.value_misses - misses == n + 1
    assert calls == []
    prov = srv.registry.snapshot()["providers"]["layercache"]
    assert prov["value_prefilter_misses"] == 3 * (n + 1)
    assert prov["value_misses"] == lc.stats.value_misses


def test_relabeled_value_hit_survives_save_load(tmp_path):
    """A version-2 store round trip keeps the fingerprints, so a
    relabeled sub-structure still hits after ``load``."""
    big = chain(7)
    card_big = make_cardinalities(big, seed=3)
    form_big = canonicalize(big, card_big)
    lc = LayerCache()
    lc.observe(form_big, "out", 0.0, {}, dp=_pure_dp(form_big.card))
    path = str(tmp_path / "layers.npz")
    lc.save(path)
    with np.load(path) as z:
        assert int(z["version"]) == layercache_mod.STORE_VERSION == 2
        assert len(z["value_fps"]) == len(z["value_keys"]) == 8

    back = LayerCache()
    assert back.load(path) == 8
    form2 = _relabeled_leave_one_out(big, card_big, 6, 7)
    seed = back.seed_for(form2, "out")
    assert seed is not None and back.stats.value_hits == 1
    assert back.stats.value_prefilter_misses == 0   # covered by the hit
    ok = seed["ok"]
    assert ok[(1 << 6) - 1]
    assert np.array_equal(seed["vals"][ok], _pure_dp(form2.card)[ok])
