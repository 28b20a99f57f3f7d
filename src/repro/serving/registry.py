"""Lazy, cached EngineKey -> SamplingEngine construction.

The registry is the only place the serving layer touches engine
construction: a factory callback builds one
:class:`~repro.sampling.SamplingEngine` (with its
:class:`~repro.sampling.Placement`) per :class:`~repro.serving.EngineKey`
the first time traffic routes to it, and the instance is cached for the
registry's lifetime — so the batcher and loop only ever ROUTE requests; they
never see meshes, shardings, or denoiser parameters.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Optional

from repro.obs import Observability
from repro.sampling.engine import SamplingEngine
from repro.sampling.types import SampleRequest, WarmStart
from repro.serving.cache import TrajectoryCache
from repro.serving.queue import EngineKey

__all__ = ["EngineRegistry", "TrajectoryCache"]


class EngineRegistry:
    """One lazily-constructed :class:`SamplingEngine` per :class:`EngineKey`,
    plus that key's :class:`TrajectoryCache`.

    factory: ``EngineKey -> SamplingEngine``; called at most once per key
             (under a lock — engine construction may shard parameters onto
             a mesh, which must not race).
    """

    def __init__(self, factory: Callable[[EngineKey], SamplingEngine], *,
                 cache_capacity: int = 64,
                 cache_max_bytes: Optional[int] = None,
                 cache_neighborhood: float = 0.0):
        self._factory = factory
        self._lock = threading.Lock()
        self._engines: Dict[EngineKey, SamplingEngine] = {}
        self._caches: Dict[EngineKey, TrajectoryCache] = {}
        self._cache_capacity = cache_capacity
        self._cache_max_bytes = cache_max_bytes
        self._cache_neighborhood = cache_neighborhood
        self._obs: Optional[Observability] = None

    def bind_obs(self, obs: Observability) -> None:
        """Attach one shared observability bundle: every engine and
        trajectory cache constructed so far (and every future one) mirrors
        its stats into ``obs.metrics`` under its key's label and emits
        spans on ``obs.tracer``.  The :class:`~repro.serving.ServingLoop`
        calls this with its own bundle at construction."""
        with self._lock:
            self._obs = obs
            engines = list(self._engines.items())
            caches = list(self._caches.items())
        for key, engine in engines:
            engine.bind_obs(obs, name=key.describe())
        for key, cache in caches:
            cache.bind_metrics(obs.metrics, name=key.describe())

    def get(self, key: EngineKey) -> SamplingEngine:
        with self._lock:
            engine = self._engines.get(key)
            if engine is None:
                engine = self._engines[key] = self._factory(key)
                if self._obs is not None:
                    engine.bind_obs(self._obs, name=key.describe())
            return engine

    def engines(self) -> Dict[EngineKey, SamplingEngine]:
        """Snapshot of the engines constructed so far."""
        with self._lock:
            return dict(self._engines)

    def replace(self, key: EngineKey, engine: SamplingEngine) -> None:
        """Swap in a replacement engine for ``key`` — the elastic-recovery
        path: after device loss, the supervisor builds a fresh engine on
        the surviving sub-mesh and installs it here so every later
        ``get(key)`` routes to it.  The replacement joins the shared
        observability bundle like a factory-built engine would."""
        with self._lock:
            self._engines[key] = engine
            obs = self._obs
        if obs is not None:
            engine.bind_obs(obs, name=key.describe())

    def set_factory(self,
                    factory: Callable[[EngineKey], SamplingEngine]) -> None:
        """Replace the construction callback for keys not yet built — after
        an elastic rebuild, NEW keys must come up on the surviving sub-mesh,
        not on the placement the old factory closed over."""
        with self._lock:
            self._factory = factory

    def cache(self, key: EngineKey) -> TrajectoryCache:
        """``key``'s trajectory cache (lazy, one per key like its engine)."""
        with self._lock:
            cache = self._caches.get(key)
            if cache is None:
                cache = self._caches[key] = TrajectoryCache(
                    self._cache_capacity,
                    max_bytes=self._cache_max_bytes,
                    neighborhood=self._cache_neighborhood)
                if self._obs is not None:
                    cache.bind_metrics(self._obs.metrics,
                                       name=key.describe())
            return cache

    # -- RequestQueue submit-time hooks --------------------------------------

    def validate_submit(self, request: SampleRequest,
                        key: EngineKey) -> None:
        """``RequestQueue(validate=...)`` hook: raise exactly what a
        dispatch carrying ``request`` would raise — including warm-start
        shape/dtype mismatches against ``key``'s engine geometry — so a
        bad request fails its one ticket at submit time instead of
        poisoning a packed dispatch at trace time."""
        self.get(key).validate_request(request)

    def warm_start_for(self, request: SampleRequest,
                       key: EngineKey) -> Optional[WarmStart]:
        """``RequestQueue(warm_start=...)`` hook: the Sec 4.2 cache
        auto-population point.  A request that already carries an ``init``
        keeps it; otherwise the key's cache answers with its best match
        (exact (condition, seed) -> same condition -> neighborhood), or None
        for a cold start."""
        if request.init is not None:
            return None
        return self.cache(key).lookup(request.condition_key,
                                      seed=request.seed)

    def warmup(self, key: EngineKey, *, slots: int,
               request: Optional[SampleRequest] = None,
               chunk_iters: int = 0) -> SamplingEngine:
        """Construct + compile ``key``'s engine ahead of traffic.

        Dispatches one throwaway request at ``slots`` — which must be the
        SERVING slot geometry (``Batcher.slots_for(engine)``), since any
        other slot count compiles a different program and the first real
        batch would still pay the jit compile — then rewinds the engine's
        serving counters (``traces`` is kept: it genuinely compiled).

        With ``chunk_iters > 0`` the stepwise programs are warmed instead
        (open/init/merge/step at the serving slot geometry and chunk size —
        the programs an iteration-level :class:`~repro.serving.ServingLoop`
        drives); the throwaway bank is discarded, the compilations stay.
        """
        engine = self.get(key)
        if chunk_iters:
            bank = engine.stepwise_open(slots, chunk_iters=chunk_iters)
            engine.stepwise_refill(bank, [0], [request or SampleRequest()])
            while bank.occupied:
                engine.stepwise_step(bank)
                engine.stepwise_harvest(bank)
        else:
            pending = engine.dispatch([request or SampleRequest()],
                                      slots=slots)
            engine.collect(pending)
        engine.reset_stats()
        return engine

    def __contains__(self, key: EngineKey) -> bool:
        with self._lock:
            return key in self._engines

    def __len__(self) -> int:
        with self._lock:
            return len(self._engines)

    def describe(self) -> str:
        lines = []
        for key, engine in sorted(self.engines().items()):
            lines.append(f"{key.describe()}: {engine.placement.describe()}, "
                         f"{engine.stats['traces']} compilation(s)")
        return "\n".join(lines) or "(no engines constructed)"
