"""repro.obs — unified observability for the serving stack.

Three pillars, one facade:

  * :mod:`repro.obs.metrics` — a typed, thread-safe
    :class:`MetricsRegistry` (Counter/Gauge/Histogram with label sets,
    ``snapshot()``) every serving layer registers into; the legacy
    ``stats`` dicts stay available verbatim as :class:`StatsView`\\ s
    mirroring into it.  :func:`count_compiles` adds JAX's own trace,
    compile and persistent-cache-hit events to a registry, by function.
  * :mod:`repro.obs.trace` — :class:`SpanTracer`: span tracing (engine
    pack/dispatch/collect, stepwise open/refill/step/poll/harvest/gather
    and serving-loop admit/idle spans; per-ticket submit -> validate ->
    admit -> splice -> draft -> refine-resubmit -> resolve lifecycle
    spans).  Every span lands on the profiler's host trace, on the device
    trace's clock, whenever a ``jax.profiler`` session is open; an enabled
    tracer also keeps Chrome-trace-event JSON (``serve.py --trace-out
    trace.json`` loads in Perfetto).
  * :mod:`repro.obs.convergence` — :class:`ConvergenceRecorder`:
    per-lane, per-round fixed-point residual curves, fed by the residual
    column the stepwise step program piggybacks onto its packed poll
    summary (zero extra fetches).

:class:`Observability` bundles the three.  The cardinal rule, enforced by
``tools/stepwise_guard.py --phase obs``: instrumentation is
PROTOCOL-NEUTRAL — an enabled Observability changes no compiled program
count (still exactly 5 stepwise traces), no blocking-poll or host-fetch
accounting, and no solve bit.  ``Observability.off()`` (what every
component defaults to) keeps a working private metrics registry and a
tracer that records no JSON (its spans still reach a profiler session),
so instrumented code never branches on "is obs on".
"""
from __future__ import annotations

import time
from typing import Callable, Optional

from repro.obs.compiles import compile_totals, count_compiles
from repro.obs.convergence import ConvergenceRecorder
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               StatsView)
from repro.obs.trace import SpanTracer, json_safe

__all__ = [
    "Observability",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "StatsView",
    "SpanTracer", "json_safe",
    "ConvergenceRecorder",
    "count_compiles", "compile_totals",
]


class Observability:
    """One bundle of (metrics registry, span tracer, convergence recorder)
    shared across a serving stack.

    Wire the SAME instance into the :class:`~repro.serving.RequestQueue`,
    :class:`~repro.serving.ServingLoop` (which forwards it to the
    :class:`~repro.serving.EngineRegistry` and through it to every
    engine and trajectory cache), and the :class:`~repro.serving.Batcher`
    — then ``metrics.snapshot()`` spans the whole stack and
    ``tracer.export(path)`` writes one coherent trace.
    """

    def __init__(self, metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[SpanTracer] = None,
                 convergence: Optional[ConvergenceRecorder] = None):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None \
            else SpanTracer(enabled=False)
        self.convergence = convergence if convergence is not None \
            else ConvergenceRecorder(self.metrics)

    @property
    def active(self) -> bool:
        """True when lifecycle tracing + convergence curves are recorded
        (metrics mirror regardless — they are cheap and always useful)."""
        return self.tracer.enabled

    @classmethod
    def enabled(cls, clock: Callable[[], float] = time.monotonic,
                max_events: int = 1_000_000) -> "Observability":
        """A fully-on bundle (span tracing + convergence curves)."""
        return cls(tracer=SpanTracer(enabled=True, clock=clock,
                                     max_events=max_events))

    @classmethod
    def off(cls) -> "Observability":
        """A private, tracing-disabled bundle — the default every
        component constructs for itself when none is wired in, so
        un-instrumented usage needs no conditionals and pays no JSON
        tracing cost (each instance gets its OWN registry; label collisions
        between unrelated components cannot happen)."""
        return cls()
