"""Phase spans: the engine's one timing point, on the profiler's clock.

EcoServe's observation (PAPERS.md) is that carbon-aware decisions need
*per-phase* attribution — prefill and decode have different power and
latency profiles, and swaps are pure overhead.  Engines wrap each hot phase
in ``with profiler.span(phase, **meta):`` with one of the canonical
:data:`PHASES`.  A span does three things:

  * it opens a device-profiler annotation named ``engine.<phase>`` (with
    ``meta`` as its arguments) through the ``annotate`` factory the engine
    hands in — ``jax.profiler.TraceAnnotation`` on the real engine, so the
    span lands on the same clock as the device trace and records nothing
    while no profiler session runs.  This package stays jax-free: without a
    factory no annotation is opened;
  * it stamps host ``perf_counter`` seconds at entry and exit (``t0``,
    ``t1``, ``seconds``), which callers read for energy accounting and the
    request-lifecycle trace;
  * while a telemetry registry is attached it observes those seconds as a
    ``phase``-labeled child of the ``phase_latency_s`` CATALOG histogram.

Every duration here is HOST time: how long the host spent enqueueing or
waiting, not how long the device computed.  On an asynchronous device a
``decode_dispatch`` span is the launch alone, and a ``decode_land`` span is
the wait for results the device finished during earlier host work.  Device
time comes from the device trace, read beside these spans.

The profiler is a tiny mutable shim rather than a registry wrapper because
engine sessions swap registries per ``submit()`` call: the engine owns ONE
profiler, repoints ``profiler.registry`` at session open, and sets it to
``None`` when no telemetry is attached.

The canonical phases, nested by containment on the host thread
(``step`` > ``admit`` / ``tick`` > the rest); each is opened once per tick
or per event, never per row or per token:

  * ``step``            — one engine scheduler pass;
  * ``admit``           — release of due arrivals + the admission loop;
  * ``tick``            — one instance tick;
  * ``prefill_chunk``   — one prefill dispatch with its host prep (slotted
    full-prompt, paged chunked);
  * ``upload``          — the event path: land in-flight decodes, push
    fresh loop state;
  * ``decode_dispatch`` — host time to *launch* decode step(s);
  * ``decode_land``     — landing a decode or first-token readback;
  * ``emit``            — the per-token client callbacks of one tick;
  * ``finish``          — completing the tick's finished requests;
  * ``swap_d2h``        — preemption KV swap-out (device→host staging);
  * ``swap_h2d``        — resume KV swap-in (host→device restore).
"""
from __future__ import annotations

import time
from typing import Callable, Optional

from repro.obs.metrics import MetricsRegistry

__all__ = ["PHASES", "PhaseProfiler"]

PHASES = ("step", "admit", "tick", "prefill_chunk", "upload",
          "decode_dispatch", "decode_land", "emit", "finish",
          "swap_d2h", "swap_h2d")

_NAMES = {p: "engine." + p for p in PHASES}


class _Span:
    """One phase span as a context manager; ``t0``/``t1``/``seconds`` hold
    its host stamps once it has closed."""

    __slots__ = ("prof", "phase", "ann", "t0", "t1")

    def __init__(self, prof: "PhaseProfiler", phase: str, ann) -> None:
        self.prof = prof
        self.phase = phase
        self.ann = ann

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "_Span":
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.prof.observe(self.phase, self.t1 - self.t0)
        return False


class PhaseProfiler:
    """Opens phase spans (see the module docstring) and routes their host
    seconds into a (swappable) registry's labeled ``phase_latency_s``
    histogram.  ``registry=None`` records no histogram sample;
    ``annotate=None`` opens no profiler annotation.

    ``role`` (disaggregated serving) adds a constant ``role`` label to
    every sample — a disagg engine owns one profiler per worker pool, so
    phase latency splits prefill-pool vs decode-pool without any change
    to the engine's span call sites."""

    __slots__ = ("registry", "role", "annotate")

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 role: Optional[str] = None,
                 annotate: Optional[Callable] = None):
        self.registry = registry
        self.role = role
        self.annotate = annotate

    def observe(self, phase: str, seconds: float) -> None:
        reg = self.registry
        if reg is None:
            return
        assert phase in PHASES, f"unknown phase {phase!r}"
        if self.role is None:
            reg.labeled("phase_latency_s", phase=phase).observe(seconds)
        else:
            reg.labeled("phase_latency_s", phase=phase,
                        role=self.role).observe(seconds)

    def span(self, phase: str, **meta) -> _Span:
        """``with profiler.span("swap_d2h") as sp: ...`` — annotates the
        block as ``engine.swap_d2h`` (``meta`` as its arguments), stamps
        it, and observes its host seconds (also if the block raises)."""
        name = _NAMES.get(phase)
        assert name is not None, f"unknown phase {phase!r}"
        ann = self.annotate
        return _Span(self, phase, None if ann is None else ann(name, **meta))
