"""``repro.obs`` — the end-to-end observability layer.

The serving stack (``repro.service``) and the fused engines
(``repro.core.engine``) used to keep five disconnected ad-hoc stats
objects; this package gives them one story:

* ``metrics``  — a typed ``MetricsRegistry`` (thread-safe counters,
  gauges, fixed log-bucket histograms with p50/p95/p99) that every
  serving layer registers into.  The old ``stats()`` / ``as_dict()``
  objects survive as thin views over registry instruments or as
  registered snapshot providers, so nothing downstream breaks.
* ``trace``    — structured span tracing.  A ``Tracer`` produces one
  span tree per request (admit -> queue_wait -> dispatch -> extract ->
  respond, with coalesce / fast_path / shed variants), reading time
  ONLY through the runtime's ``Clock`` abstraction — span trees are
  bit-deterministic on a ``VirtualClock`` and tests assert their exact
  shapes.  ``phase`` puts one block of host work (admit, canonicalize,
  probe, close_bucket, finalize; the engine's prepare, execute, fetch)
  on the profiler's clock as a ``plan.<name>`` ``TraceAnnotation``.
* ``recorder`` — the flight recorder: a bounded ring buffer of
  completed span trees plus an always-on capture of every shed /
  downgraded / deadline-missed request, dumpable as JSON lines.
* ``export``   — renders a registry as a JSON snapshot and as
  Prometheus text format for the future distributed front end.

Wiring: ``PlanServer`` owns a ``MetricsRegistry``; ``ServingRuntime``
owns a ``Tracer`` + ``FlightRecorder`` bound to that registry and its
clock; ``repro.core.engine`` emits per-dispatch profiling records
(AOT-cache hit/miss, compile / prepare / execute / fetch split,
while-loop rounds, bucket key) that the runtime attributes to the spans
that waited on each dispatch, and every traced response carries its
phases' seconds in ``PlanResponse.timing_s``.  ``tests/test_obs.py``
holds the resulting telemetry to its contract (zero unclosed spans,
per-lane span shapes, exact shed/downgraded/missed capture).
"""
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, default_registry)
from repro.obs.recorder import FlightRecorder  # noqa: F401
from repro.obs.trace import NULL_SPAN, Span, Tracer  # noqa: F401
from repro.obs.export import (prometheus, registry_snapshot,  # noqa: F401
                              span_phase_summary)
