"""Unified observability layer: metrics registry, request-lifecycle tracing,
and streaming carbon/energy telemetry.

Clover's claim — carbon reduction *while* holding SLA and accuracy — is only
as credible as the measurement plane behind it.  Before this package every
serving layer reported its own ad-hoc ``stats`` dict and recomputed
per-request attribution its own way; ``repro.obs`` is the one measurement
plane they all emit into:

  * :mod:`repro.obs.metrics` — a metrics registry (counters, gauges,
    histograms with exact nearest-rank percentiles) with a shared metric-name
    CATALOG, so ``RealEngine`` (slotted and paged), ``DESBackend``,
    ``FluidBackend`` and the fleet all report under the same names and a
    backend's ``stats()`` is a *view* over its registry;
  * :mod:`repro.obs.trace` — a low-overhead span/event recorder capturing
    each request's arrival → hold/release (with the policy's reason) →
    admission → prefill chunks → decode dispatches (one span per landed
    dispatch, from its launch to its landing, with its step count and
    occupant set) → preempt/swap → completion, exportable as JSONL and as
    Chrome-trace JSON (load it in Perfetto).  Request spans carry their
    attributed joules/gCO2, so a trace is a visual audit of the carbon
    attribution, and :func:`repro.obs.trace.validate_trace` enforces the
    conservation invariant (every span closes; span-summed joules equal the
    engine total exactly);
  * :mod:`repro.obs.carbon_feed` — a measure-every-N-seconds energy/CO2
    sampler (codecarbon idiom) that integrates power against the region's
    carbon-intensity trace per window and streams per-region snapshots that
    the controller and the benchmarks both consume;
  * :mod:`repro.obs.aggregate` — the fleet-scope layer: the canonical
    label schema (``region`` / ``slo_class`` / ``kv_layout`` / ``phase``),
    bounded-memory mergeable :class:`~repro.obs.aggregate.StreamingHistogram`
    for 10^6-scale replay, and :class:`~repro.obs.aggregate.FleetRollup`
    merging per-region registries with bit-exact conservation;
  * :mod:`repro.obs.export` — OpenMetrics/Prometheus text exposition
    (round-trip validated) and a periodic JSONL snapshot writer;
  * :mod:`repro.obs.slo` — declarative latency-SLO / carbon-budget rules
    evaluated as multi-window error-budget burn rates with deterministic
    fire/clear alert state, consumed by the core controller;
  * :mod:`repro.obs.profile` — the engines' phase spans (step, admission,
    tick, prefill chunks, decode dispatch/land, swap D2H/H2D, ...): each
    opens a device-profiler annotation ``engine.<phase>`` through a factory
    the engine hands in, and feeds ``phase``-labeled latency histograms.
    Those latencies are host enqueue or wait times, not device time.

The package is deliberately jax-free (stdlib + numpy only): the DES/fluid
paths and ``scripts/check.sh``'s trace-validation step must run without
touching the device stack.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.obs.aggregate import LABEL_KEYS, FleetRollup, StreamingHistogram
from repro.obs.carbon_feed import CarbonFeed, CarbonSnapshot
from repro.obs.export import SnapshotWriter, parse_openmetrics, \
    to_openmetrics
from repro.obs.metrics import CATALOG, Counter, Gauge, Histogram, \
    MetricsRegistry
from repro.obs.profile import PHASES, PhaseProfiler
from repro.obs.slo import AlertState, BurnRatePolicy, CarbonBudget, \
    LatencyObjective, SLOEvaluator, default_rules
from repro.obs.trace import TraceRecorder, validate_chrome_events, \
    validate_trace

__all__ = ["AlertState", "BurnRatePolicy", "CATALOG", "CarbonBudget",
           "CarbonFeed", "CarbonSnapshot", "Counter", "FleetRollup",
           "Gauge", "Histogram", "LABEL_KEYS", "LatencyObjective",
           "MetricsRegistry", "PHASES", "PhaseProfiler", "SLOEvaluator",
           "SnapshotWriter", "StreamingHistogram", "Telemetry",
           "TraceRecorder", "default_rules", "parse_openmetrics",
           "to_openmetrics", "validate_chrome_events", "validate_trace"]


@dataclasses.dataclass
class Telemetry:
    """The bundle a serving backend carries: its metrics registry plus the
    optional trace recorder and carbon feed.

    Lifecycle contract: ``tracer`` and ``feed`` are *persistent* — a fleet
    probe loop reuses them across serve sessions so traces concatenate and
    the feed streams continuously.  ``registry`` is *per session* on the
    real engine (each serve session opens a fresh standard registry and
    ``stats()`` reads the last one); the single-session backends (DES /
    fluid) keep one registry for their life."""

    registry: MetricsRegistry = None
    tracer: Optional[TraceRecorder] = None
    feed: Optional[CarbonFeed] = None
    backend: str = "backend"

    def __post_init__(self) -> None:
        if self.registry is None:
            self.registry = MetricsRegistry.standard(self.backend)
