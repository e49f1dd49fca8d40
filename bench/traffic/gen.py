"""The benchmark's traffic generator: join graphs, cardinality tables and
request streams.

A copy, kept with the benchmark so that the yardstick cannot move, of the
plan server's synthetic workload model (``make_query`` / ``make_workload``
of the program's workload module, and the graph and cardinality
constructors they call).  It departs from it so that every seed gets the
same work: a cell's schedule is fixed, and the seed draws its data.

The schedule, the same for every seed:
* fresh requests come in blocks that hold every entry of the
  ``n_values`` x ``topologies`` grid once, each block in a fixed order;
* template ``i`` of a pool takes the ``i``-th entry of the cycled grid,
  and the templates' popularity ranks follow one fixed order, so the
  hottest template has the same size for every seed;
* a stream that mixes templates and fresh queries holds exactly
  ``round(BLOCK * fresh_frac)`` fresh ones in every ``BLOCK`` requests,
  and which template each repeat asks for follows one fixed Zipf draw;
* the open loop's due times are one fixed set of exponential gaps,
  scaled to fill the window.

The data, drawn from the seed: every graph's extra edges, every
cardinality table and its regime, and which repeats are relabelled and
how.  (A schedule the seed reordered made the open-loop cells' tail
latency swing by 15-22 % from seed to seed on the chip; PERF.md.)

A query is a plain ``Query(n, edges, card)``: the harness turns it into
the program's own graph type at the boundary.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# cardinality regimes: (base_range, selectivity_range) of the selectivity
# model — OLTP-ish small tables, warehouse scale, and highly selective
REGIMES = {
    "oltp": ((1e2, 1e4), (1e-3, 1.0)),
    "warehouse": ((1e4, 1e7), (1e-5, 1e-1)),
    "selective": ((1e2, 1e6), (1e-6, 1e-3)),
    # make_cardinalities' defaults, as the paper's clique sweep draws them
    "paper": ((1e2, 1e6), (1e-4, 1.0)),
}
CAP = 1e8                      # cardinalities are clipped to [1, CAP]
GAP_SEED = 20240913            # the fixed draw of open-loop gaps
RANK_SEED = 20240914           # the fixed order of template sizes by rank
ORDER_SEED = 20240915          # the fixed schedule of a request stream
BLOCK = 100                    # requests over which fresh_frac is exact


@dataclasses.dataclass(frozen=True)
class Query:
    n: int
    edges: tuple               # sorted (u, v) pairs, u < v
    card: np.ndarray           # (2^n,) float64 cardinality of every subset


# ------------------------------------------------------------------ graphs
def clique(n: int) -> tuple:
    return tuple((u, v) for u in range(n) for v in range(u + 1, n))


def star(n: int) -> tuple:
    return tuple((0, i) for i in range(1, n))


def random_sparse(n: int, extra_edges: int, seed: int) -> tuple:
    """JOB-like sparse graph: a random spanning tree plus ``extra_edges``."""
    rng = np.random.default_rng(seed)
    edges = set()
    perm = rng.permutation(n)
    for i in range(1, n):
        u = int(perm[rng.integers(0, i)])
        v = int(perm[i])
        edges.add((min(u, v), max(u, v)))
    rest = [(u, v) for u in range(n) for v in range(u + 1, n)
            if (u, v) not in edges]
    rng.shuffle(rest)
    edges.update(rest[:extra_edges])
    return tuple(sorted(edges))


def cardinalities(n: int, edges: tuple, seed: int, base_range: tuple,
                  selectivity_range: tuple, cap: float = CAP) -> np.ndarray:
    """The selectivity model: c(S) = prod_{i in S} base_i * prod_{e in S}
    sel_e, clipped to [1, cap]; c(empty) = 1.  Built in O(n 2^n) by the
    top bit: every S in [2^t, 2^(t+1)) is S' + {t} with S' < 2^t."""
    rng = np.random.default_rng(seed)
    log_base = rng.uniform(np.log(base_range[0]), np.log(base_range[1]), n)
    log_sel = rng.uniform(np.log(selectivity_range[0]),
                          np.log(selectivity_range[1]), len(edges))
    w = np.zeros((n, n))
    for (u, v), ls in zip(edges, log_sel):
        w[v, u] = ls                   # edge to a lower bit, seen from v
        w[u, v] = ls
    logc = np.zeros(1 << n)
    for t in range(n):
        lo = 1 << t
        wsum = np.zeros(lo)            # sum of w[t, j] over bits j of S'
        for j in range(t):
            wsum[1 << j:2 << j] = wsum[:1 << j] + w[t, j]
        logc[lo:2 * lo] = logc[:lo] + log_base[t] + wsum
    card = np.exp(np.clip(logc, 0.0, np.log(cap)))
    card[0] = 1.0
    return card


def relabel(q: Query, perm: np.ndarray) -> Query:
    """The isomorphic query with relation i renamed to perm[i]."""
    edges = tuple(sorted(tuple(sorted((int(perm[u]), int(perm[v]))))
                         for u, v in q.edges))
    S = np.arange(1 << q.n, dtype=np.int64)
    Sp = np.zeros_like(S)
    for i in range(q.n):
        Sp |= ((S >> i) & 1) << int(perm[i])
    card = np.empty_like(q.card)
    card[Sp] = q.card
    return Query(q.n, edges, card)


def make_query(rng: np.random.Generator, n: int, topology: str,
               regimes: tuple, extra_edges: tuple = (0, 0)) -> Query:
    """One query of ``topology`` at ``n`` relations, its cardinality
    regime drawn from ``regimes``."""
    if topology == "clique":
        edges = clique(n)
    elif topology == "star":
        edges = star(n)
    elif topology == "sparse":
        extra = int(rng.integers(extra_edges[0], extra_edges[1] + 1))
        edges = random_sparse(n, extra, seed=int(rng.integers(2 ** 31)))
    else:
        raise ValueError(f"unknown topology {topology!r}")
    base, sel = REGIMES[str(regimes[int(rng.integers(len(regimes)))])]
    card = cardinalities(n, edges, int(rng.integers(2 ** 31)), base, sel)
    return Query(n, edges, card)


# ----------------------------------------------------------------- streams
@dataclasses.dataclass(frozen=True)
class Mix:
    """A traffic mix, as its ``bench/traffic/<name>.json`` file gives it."""
    loop: str                        # "open" (rate) | "closed" (clients)
    cost: str                        # its reference: bench/costs/<cost>.py
    n_values: tuple
    topologies: tuple
    regimes: tuple
    extra_edges: tuple = (0, 0)
    rate: float = 0.0                # open loop: offered requests/second
    clients: int = 0                 # closed loop: clients in flight
    pool_size: int = 0               # templates (0: every request fresh)
    zipf_a: float = 1.5
    fresh_frac: float = 1.0
    relabel_frac: float = 0.0
    check_sample: int = 16           # answers compared with the reference

    @classmethod
    def from_dict(cls, d: dict) -> "Mix":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(f"unknown traffic keys {unknown}")
        return cls(**{k: tuple(v) if isinstance(v, list) else v
                      for k, v in d.items()})

    def grid(self, i: int) -> tuple:
        """The stratified (n, topology) of slot ``i``."""
        k = len(self.n_values)
        return (int(self.n_values[i % k]),
                str(self.topologies[(i // k) % len(self.topologies)]))


def _pool(rng, mix: Mix) -> list:
    """The templates, most popular first: the sizes by a fixed order of
    the grid's slots, the graphs and cardinalities from the seed."""
    slots = [mix.grid(i) for i in range(mix.pool_size)]
    order = np.random.default_rng(RANK_SEED).permutation(mix.pool_size)
    return [make_query(rng, *slots[j], mix.regimes, mix.extra_edges)
            for j in order]


class Stream:
    """The request sequence of one run.  ``next()`` hands out queries in
    a fixed order, so how many a run sends depends on the server's speed
    and what each one is does not."""

    def __init__(self, mix: Mix, seed: int):
        self.mix = mix
        self.rng = np.random.default_rng([int(seed), 1])      # the data
        self.order = np.random.default_rng(ORDER_SEED)       # the schedule
        self.pool = _pool(self.rng, mix)
        w = 1.0 / np.arange(1, mix.pool_size + 1) ** mix.zipf_a
        self.weights = w / w.sum() if mix.pool_size else w
        self._slots: list = []
        self._fresh: list = []

    def fresh(self) -> Query:
        """The next fresh query: the grid's slots come in blocks of one
        slot each, every block in its own fixed order."""
        if not self._slots:
            block = len(self.mix.n_values) * len(self.mix.topologies)
            self._slots = [self.mix.grid(int(i))
                           for i in self.order.permutation(block)]
        n, topo = self._slots.pop()
        return make_query(self.rng, n, topo, self.mix.regimes,
                          self.mix.extra_edges)

    def next(self) -> Query:
        m = self.mix
        if not m.pool_size:
            return self.fresh()
        if not self._fresh:
            k = int(round(BLOCK * m.fresh_frac))
            self._fresh = list(self.order.permutation(BLOCK) < k)
        if self._fresh.pop():
            return self.fresh()
        q = self.pool[int(self.order.choice(m.pool_size, p=self.weights))]
        if self.rng.random() < m.relabel_frac:
            q = relabel(q, self.rng.permutation(q.n))
        return q


def open_loop_dues(rate: float, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of ``round(rate *
    seconds)`` requests: one fixed set of exponential gaps, scaled so the
    last falls inside the window."""
    count = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(GAP_SEED).exponential(1.0, count + 1)
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps[:count])
