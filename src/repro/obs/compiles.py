"""JAX's own compile events as registry counters.

``count_compiles(metrics)`` adds three counters to ``metrics`` for the
rest of the process, fed by process-wide ``jax.monitoring`` listeners:

  * ``jax.traces``     — jaxpr traces (one per traced function),
  * ``jax.compiles``   — backend compiles, persistent-cache loads included,
  * ``jax.cache_hits`` — persistent compilation-cache hits.

Traces and compiles carry a ``fun_name`` label (the jitted function's
name), so a snapshot says which program recompiled; cache hits carry none
(JAX's event names no function).  The listeners are installed once per
process and fan out to every registry passed in.
"""
from __future__ import annotations

import threading
import weakref
from typing import Dict

from repro.obs.metrics import MetricsRegistry

__all__ = ["count_compiles", "compile_totals", "COUNTERS"]

_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.traces",
    "/jax/core/compile/backend_compile_duration": "jax.compiles",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
COUNTERS = ("jax.traces", "jax.compiles", "jax.cache_hits")

_sinks: "weakref.WeakSet[MetricsRegistry]" = weakref.WeakSet()
_lock = threading.Lock()
_installed = False


def _inc(name: str, labels: Dict) -> None:
    for metrics in list(_sinks):
        metrics.counter(name).inc(**labels)


def _on_duration(event: str, duration: float, **kw) -> None:
    name = _DURATION_EVENTS.get(event)
    if name is not None:
        fun = kw.get("fun_name")
        _inc(name, {} if fun is None else {"fun_name": fun})


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        _inc("jax.cache_hits", {})


def count_compiles(metrics: MetricsRegistry) -> MetricsRegistry:
    """Count JAX traces, compiles and cache hits into ``metrics`` from now
    on (idempotent per registry).  Returns ``metrics``."""
    global _installed
    import jax
    with _lock:
        if not _installed:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _installed = True
        _sinks.add(metrics)
        for name in COUNTERS:       # present in snapshots from the start
            metrics.counter(name)
    return metrics


def compile_totals(metrics: MetricsRegistry) -> Dict[str, float]:
    """Each counter summed over its labels: ``{"jax.traces": n, ...}``."""
    return {name: sum(metrics.counter(name).series().values())
            for name in COUNTERS}
