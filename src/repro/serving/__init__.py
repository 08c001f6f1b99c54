"""Band-elastic serving runtime (ROADMAP "serving runtime").

The paper's §6 sparsity result makes ``bands`` a *runtime* quality/latency
knob: one trained network, compiled at several band budgets, can walk the
accuracy/compute frontier under load.  This package turns that into a
serving subsystem on top of the convert-once engine (``core.plan``):

* :mod:`repro.serving.ladder` — one ``InferencePlan`` compiled into a
  **plan ladder** of band tiers whose operators are prefix-slices of the
  same exploded Ξ buffers, with bit-exact save/restore;
* :mod:`repro.serving.grid` — the ladder made 2-D: a **plan grid** of
  precompiled (batch bucket × band tier) executors (aphrodite-style
  capture buckets 1, 2, 4, multiples of 8) with pinned host staging and
  input donation, so steady-state serving does zero compiles, zero
  reshapes, and pads only to the covering bucket;
* :mod:`repro.serving.scheduler` — an async request scheduler with
  admission control, per-request deadlines, and mixed
  ``coefficients``/``bytes`` ingest queues feeding ``repro.codec``;
* :mod:`repro.serving.qos` — the band-elastic policy: queue-depth and
  deadline-slack signals pick the tier per batch, degrading bands under
  overload and recovering (with hysteresis) as the queue drains;
* :mod:`repro.serving.metrics` — per-request latency histograms (O(1)
  memory log₂ buckets), per-tier throughput, tier-switch events, ingest
  occupancy, failure counters per reason, breaker state timeline, and a
  Prometheus-style text exposition with periodic snapshot writes;
* :mod:`repro.serving.trace` — the flight recorder: a bounded ring of
  per-request spans (admission → queue → ingest-decode → batch-form →
  device-dispatch [stack, pad/stage, launch, read] → complete →
  complete/fail/shed) exported as Perfetto-loadable Chrome trace-event
  JSON; the per-batch spans also reach a ``jax.profiler`` session;
* :mod:`repro.serving.breaker` — a circuit breaker over service-level
  failures: fast-rejects (``ServiceUnavailable``) while the backend is
  evidently unhealthy, half-opens on a timer;
* :mod:`repro.serving.faults` — deterministic, seedable fault injection
  (corrupt bytes, worker kills, executor faults) driving the chaos
  suite; production runs never construct it.

``launch/serve.py`` is a thin CLI over this runtime (``--qos``,
``--tiers``, ``--deadline-ms``); ``benchmarks/fig5_throughput.py``'s
``serving`` mode measures fixed-band vs elastic under overload.
"""
from repro.serving.grid import (
    GridCell,
    GridColumn,
    PinnedPool,
    PlanGrid,
    batch_buckets,
    bucket_for,
    cover_buckets,
    validate_buckets,
)
from repro.serving.ladder import (
    DEFAULT_CAPS,
    PlanLadder,
    PlanTier,
    build_ladder,
    cap_plan,
    load_ladder,
    save_ladder,
)
from repro.serving.breaker import BreakerPolicy, CircuitBreaker
from repro.serving.faults import FaultInjector, FaultSpec, InjectedFault
from repro.serving.metrics import (
    Log2Histogram,
    MetricsWriter,
    ServeMetrics,
    percentiles,
)
from repro.serving.qos import QosPolicy, TierSelector
from repro.serving.trace import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    jax_profile,
    validate_trace,
)
from repro.serving.scheduler import (
    BandElasticScheduler,
    DeadlineExceeded,
    RequestFailed,
    SchedulerClosed,
    ServeRequest,
    ServiceUnavailable,
)

__all__ = [
    "DEFAULT_CAPS",
    "GridCell",
    "GridColumn",
    "PinnedPool",
    "PlanGrid",
    "batch_buckets",
    "bucket_for",
    "cover_buckets",
    "validate_buckets",
    "PlanLadder",
    "PlanTier",
    "build_ladder",
    "cap_plan",
    "save_ladder",
    "load_ladder",
    "Log2Histogram",
    "MetricsWriter",
    "NULL_TRACER",
    "NullTracer",
    "ServeMetrics",
    "Tracer",
    "jax_profile",
    "percentiles",
    "validate_trace",
    "QosPolicy",
    "TierSelector",
    "BandElasticScheduler",
    "BreakerPolicy",
    "CircuitBreaker",
    "DeadlineExceeded",
    "FaultInjector",
    "FaultSpec",
    "InjectedFault",
    "RequestFailed",
    "SchedulerClosed",
    "ServeRequest",
    "ServiceUnavailable",
]
