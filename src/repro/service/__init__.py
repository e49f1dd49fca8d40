"""``repro.service`` — the plan-serving subsystem.

The core library (``repro.core``) answers "find the optimal join order of
ONE query"; this package answers "serve plan requests at production rate".
It sits between the algorithm layer and the consumers (``repro.planner``,
examples, benchmarks):

::

       requests (QueryGraph, card, cost, budget, SLO class, arrival)
                |                                  |
         sync: serve() / plan_one()         async: plan_async()
                |                                  |
     +----------v----------------------------------v------------+
     |  runtime.ServingRuntime   event-driven scheduler         |
     |  (Wall/Virtual clock)     fast path · coalesce · shed    |
     |                           per-(n, cost) admission queues |
     |                           EWMA-adaptive batch former     |
     +---------------------------+----------------------------+
     |  server.PlanServer        | solve path + telemetry      |
     |   +----------+   +-----------+   +------------------+   |
     |   | canon    |-->| cache     |-->| router            |  |
     |   | WL canon |   | LRU,      |   | (n, density, cost,|  |
     |   | labeling |   | relabel-  |   |  budget) ->       |  |
     |   | + key    |   | aware hits|   | method + lane     |  |
     |   +----------+   +-----------+   +---------+--------+   |
     |                                            |            |
     |                 +--------------------------+---+        |
     |                 |  batch.BatchedSolver         |        |
     |                 |  same-n stacking, (B, 2^n)   |        |
     |                 |  submit/collect overlap,     |        |
     |                 |  lattice sweeps, Pallas tier |        |
     |                 +------------------------------+        |
     +---------------------------------------------------------+
                                 |
          repro.core  (dpconv_max_batch / optimize / layered DP)
          repro.kernels (batched zeta/Moebius Pallas kernels)

* ``canon``    — isomorphism-invariant canonicalization: WL refinement +
  capped individualization gives a canonical relabeling; the cache key
  hashes the exact permuted cardinality bytes, so key equality <=> the
  requests are relabelings of each other.  Also: topology-class
  signatures for the router.
* ``cache``    — LRU plan cache in canonical label space with
  hit/miss/eviction/relabel-hit stats; cached join trees are replayed
  through the request's inverse permutation.
* ``batch``    — batched solving: same-``(n, cost)`` requests stack
  their tables to (B, 2^n) and share every DP lattice sweep; the batch
  lane carries ``cost="max"`` AND ``cost="cap"`` chunks, each solved as
  ONE fused lattice-program dispatch (``repro.core.engine``) with
  on-device tree extraction, binary or (G+1)-ary gamma probing
  (``BatchPolicy.gamma_batch``); mid-size lattices route the transforms
  through the batched Pallas kernels (int32, exact to n = 15), the rest
  use XLA int64 butterflies.  Costs and trees are bit-identical to
  single-query ``optimize``.
* ``router``   — admission policy: (n, edge density, cost fn, latency
  budget) -> (method, lane, params), with an EWMA latency model bucketed
  per (method, engine[:cap], topology-class) and deadline degradation
  exact -> approx -> GOO.
* ``runtime``  — the async deadline-aware scheduler: pluggable
  Wall/Virtual clock, per-request SLO classes, per-(n, cost) admission
  queues with an EWMA-adaptive micro-batch former, a cache-hit fast
  path that overtakes in-flight batched misses, relabeling-aware
  join-on-completion coalescing, and backpressure/deadline shedding
  with per-class telemetry.  Responses are bit-identical to the sync
  path under any interleaving.
* ``server``   — ties it together: the sync ``serve`` driver (a thin
  loop over the runtime on a VirtualClock), the awaitable
  ``plan_async`` front end, throughput counters, latency histograms,
  and ``prewarm`` (compile every fused executable bucket the
  configuration can hit before traffic arrives).
* ``workload`` — request-stream generators: synthetic (topology ×
  cardinality-regime templates, Zipf repeats, random relabelings,
  Poisson arrivals) and the einsum contraction-log replay lane
  (``make_einsum_workload``).
* ``faults``   — the resilience layer: typed ``PlanError`` taxonomy,
  seeded deterministic fault injection (``FaultPlan``/``FaultInjector``
  at the dispatch/compile/cache/worker seams), per-engine-lane circuit
  breakers (``BreakerBoard``), poisoned-key ``Quarantine``, and the
  counters behind the runtime's failure ladder (retry with deadline-
  capped backoff -> host-exact failover -> GOO best-effort with a cost
  certificate -> typed error).  Every response carries
  ``PlanResponse.status`` in {"exact", "degraded", "error"}.

Observability (``repro.obs``) threads through every layer: the server
binds a ``MetricsRegistry`` (cache/router/solver/engine/runtime
providers), the runtime mints a per-request span tree on its ``Clock``
(admit → queue_wait → coalesce/fast_path → dispatch with the engine's
compile/execute split → extract → respond) and a ``FlightRecorder``
keeps every shed/downgraded/deadline-missed request for postmortems.
``PlanRequest(explain=True)`` returns the provenance on the response.

Above the single-process stack sits the distributed serving front end:

* ``net``      — the wire layer: a tagged-JSON codec under which every
  ``PlanRequest``/``PlanResponse``/``PlanError`` round-trips bit-exactly,
  the per-replica protocol ops (``ReplicaState``), an asyncio
  line-protocol server (``NetFrontend``) and a blocking ``NetClient``.
* ``cluster``  — the replica cluster: consistent-hash routing on the
  canonical cache key (``HashRing``), the client-side router with
  failover/hedging and the shared plan-cache tier (exact solves
  published to the key's ring owner, answered cluster-wide as
  relabeling-aware hits), cross-replica prewarm manifests, the
  deterministic ``LoopbackTransport`` chaos harness, and the
  multi-process ``ReplicaCluster``.
* ``tenancy``  — per-tenant SLO quotas: deterministic token-bucket
  admission (shed / downgrade / aging-promote) on the runtime side,
  deny-rate-fed ``AdmissionCeilings`` on the cluster-client side.

Benchmark: ``benchmarks/serve_bench.py`` (``--quick`` for the CI gate in
``scripts/smoke.sh``).  Demo: ``examples/planner_demo.py``.
"""
from repro.obs import (FlightRecorder, MetricsRegistry,  # noqa: F401
                       Tracer)
from repro.service.batch import (BatchedSolver, BatchPolicy,  # noqa: F401
                                 SolveHandle)
from repro.service.cache import CachedPlan, CacheStats, PlanCache  # noqa: F401
from repro.service.canon import (CanonicalForm, canonicalize,  # noqa: F401
                                 relabel_tree, topology_signature)
from repro.service.cluster import (ClusterClient, HashRing,  # noqa: F401
                                   LoopbackTransport, ReplicaCluster,
                                   TcpTransport)
from repro.service.faults import (BreakerBoard, BreakerConfig,  # noqa: F401
                                  CacheBackendError, CompileError,
                                  EngineError, FaultInjector, FaultPlan,
                                  FaultSpec, FaultStats, NetworkError,
                                  PlanError, PlanTimeoutError, Quarantine,
                                  QuarantinedError, ReplicaDeadError,
                                  ShedError, WorkerDied)
from repro.service.net import (NetClient, NetFrontend,  # noqa: F401
                               ReplicaState, decode_request,
                               decode_response, encode_request,
                               encode_response)
from repro.service.tenancy import (AdmissionCeilings, QuotaBoard,  # noqa: F401
                                   TenantQuota)
from repro.service.router import Route, Router, RouterConfig  # noqa: F401
from repro.service.runtime import (Clock, RuntimeConfig,  # noqa: F401
                                   RuntimeStats, ServingRuntime,
                                   SLOClass, Ticket, VirtualClock,
                                   WallClock)
from repro.service.server import (LatencyHistogram, PlanRequest,  # noqa: F401
                                  PlanResponse, PlanServer, ServeStats)
from repro.service.workload import (WorkloadSpec, make_query,  # noqa: F401
                                    make_einsum_workload, make_workload)
