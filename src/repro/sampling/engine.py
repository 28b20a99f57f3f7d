"""SamplingEngine: compile-once, vmap-batched, mesh-aware execution of
SampleRequests.

The engine owns (denoiser apply fn, params, solver coefficients, sampler
spec, sample shape) AND its device placement: it runs whole batches of
requests through one jitted program whose request axis is vmapped over the
ParaTAA solver, so every solver iteration evaluates the denoiser on a single
(requests x window) batch.  Under a sharded :class:`Placement` the packed
request arrays carry ``NamedSharding(mesh, P("data", ...))``, the vmapped
batch axis is constrained to ``data`` via ``spmd_axis_name``, denoiser
params are placed by their logical-axis rules, and the denoiser traces under
the ambient ``models.shardctx`` mesh so its activations TP-shard over
``model``.  With ``Placement.host()`` (the default) every placement hook is
an identity and the program is bitwise-identical to the unsharded engine.

Per-request conditions (a class label, or a caption pytree), seeds, and
warm starts (Sec 4.2) are all data to that one program: cold and warm
starts share a single compilation because a cold start is just
``init = (xi, T_init=T)``.  Batches are padded to a fixed
``batch_size`` — rounded up to a multiple of the placement's data shards so
every device holds the same number of request slots — so the engine compiles
exactly once per (denoiser, T, sampler-spec, batch-size, diagnostics)
configuration; the ``stats["traces"]`` counter records actual retraces and
``last_dispatches`` reports per-dispatch device utilization (with host
packing, ``pack_s``, timed separately from device wall time).

``run_batch`` is the blocking path.  Its two halves are public —
non-blocking ``dispatch`` (pack + enqueue; JAX async dispatch returns
immediately) and blocking ``collect`` — so a serving loop can pack batch
N+1 on the host while batch N computes on the device (see
:mod:`repro.serving`).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coeffs import SolverCoeffs
from repro.core import parataa as _parataa
from repro.diffusion.samplers import _sequential_sample, draw_noises
from repro.obs import Observability, StatsView
from repro.sampling.placement import Placement
from repro.sampling.specs import SamplerSpec
from repro.sampling.types import DIAG_KEYS, SampleRequest, SampleResult


@dataclasses.dataclass
class PendingBatch:
    """One in-flight engine dispatch.

    ``trajs``/``info`` are the compiled program's outputs: thanks to JAX
    async dispatch they are futures-backed arrays the moment ``dispatch``
    returns, so the host is free to pack the next batch while the device
    computes this one.  Only ``collect`` blocks on them.
    """
    trajs: Any
    info: Dict
    requests: List[SampleRequest]   # the real (unpadded) requests
    slots: int                      # padded request-slot count dispatched
    diagnostics: bool
    pack_s: float                   # host-side packing/PRNG wall time
    t_dispatch: float               # clock reading when the program launched


@dataclasses.dataclass
class LaneBank:
    """A live, resumable batch of solver lanes (the stepwise dispatch unit).

    ``state`` is the batched :class:`repro.core.parataa.SolverState` on
    device; each of the ``slots`` lanes holds one in-flight request (or
    ``None`` = vacant, kept permanently ``finished`` via ``iter_cap=0`` so
    the guarded chunk passes it through).  The bank outlives any single
    request: lanes retire the moment their own lane finishes and are
    refilled in place — iteration-level continuous batching.

    Work accounting (the refactor's visible win on a CPU-shared box):
    ``device_iters`` counts solver iterations the device executed while the
    bank was stepped (every step costs the full batch width, finished or
    not — SPMD), ``useful_iters``/``harvested_nfe`` accumulate per-lane
    progress at harvest, so ``wasted_iter_frac`` measures lane-iterations
    burned after the owning lane already finished (or on vacant lanes).

    Host protocol state (the device-resident hot path): ``summary`` is the
    packed (slots, 5) scheduling array the step program piggybacks
    (finished/it/nfe/done + the per-lane max first-order residual, f32
    bitcast into the int32 payload — convergence telemetry rides the SAME
    fetch) — its host copy starts asynchronously the moment
    the chunk is enqueued, so the blocking ``device_get`` at the NEXT
    round's harvest overlaps host scheduling with device compute.
    ``poll_cache`` shares that ONE fetch between harvest and report within
    a round (invalidated by step/refill).  ``host_fetch_bytes`` /
    ``blocking_polls`` / ``gather_launches`` count what actually crossed
    the host<->device boundary.
    """
    state: Any
    conds: Any                             # per-lane condition pytree,
                                           # each leaf (slots, ...) on device
    requests: List[Optional[SampleRequest]]
    slots: int
    chunk_iters: int
    device_iters: int = 0
    useful_iters: int = 0
    harvested_nfe: int = 0
    completed: int = 0
    refills: int = 0
    pack_s: float = 0.0
    summary: Any = None                    # (slots, 5) device int32
    poll_cache: Optional[Dict] = None      # this round's host-side poll
    host_fetch_bytes: int = 0
    blocking_polls: int = 0
    gather_launches: int = 0
    harvests: int = 0                      # rounds that retired >= 1 lane
    update_launches: int = 0               # modeled Anderson-update kernel
                                           # launches (3/iter staged, 1
                                           # fused, 0 when no update runs)

    def free_lanes(self) -> List[int]:
        return [i for i, r in enumerate(self.requests) if r is None]

    @property
    def occupied(self) -> int:
        return sum(r is not None for r in self.requests)


@dataclasses.dataclass
class BankSnapshot:
    """A host-resident, placement-free copy of a live :class:`LaneBank`.

    The elastic-recovery unit: ``SamplingEngine.fetch_bank`` pulls every
    state leaf off the (possibly dying) mesh as plain numpy, and
    ``adopt_bank`` on a DIFFERENT engine — typically one built on the
    surviving sub-mesh — re-places the exact bytes and resumes the solve
    mid-chunk.  Because ``step_chunk`` is a guarded scan whose per-lane
    math is independent of the data-axis partitioning (PR 7's bitwise
    sharded==unsharded invariant), a snapshot/adopt round-trip changes
    nothing about the trajectory: the resumed lanes are bitwise-identical
    to an uninterrupted run.

    ``counters`` carries the bank-lifetime work accounting (device/useful
    iters, harvests, fetch bytes, ...) across the migration so a rebuilt
    bank's ``stepwise_report`` still describes the whole solve, not just
    the post-recovery tail.
    """
    state: Any                              # numpy SolverState pytree
    conds: Any                              # numpy per-lane conditions
    requests: List[Optional[SampleRequest]]
    slots: int
    chunk_iters: int
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def occupied(self) -> int:
        return sum(r is not None for r in self.requests)

    def nbytes(self) -> int:
        leaves = jax.tree.leaves((self.state, self.conds))
        return int(sum(a.nbytes for a in leaves))


class SamplingEngine:
    """Batched sampling executor for one (denoiser, T, solver) configuration.

    eps_apply:    (params, x (n, *sample_shape), taus (n,), cond) -> eps,
                  each leaf of ``cond`` with a leading (n,) axis
    params:       denoiser parameters (closed over by the jitted program);
                  placed onto the mesh at construction when sharded
    coeffs:       SolverCoeffs (fixes T and the DDIM/DDPM schedule)
    spec:         SamplerSpec strategy ("seq" or any ParaTAA variant)
    sample_shape: per-sample latent shape, e.g. (num_tokens, latent_dim)
    placement:    Placement (mesh + shardings + donation); default host
    param_defs:   optional ParamDef tree matching ``params`` — when given
                  (and sharded), params are placed by their logical-axis
                  rules (TP over `model`, FSDP over `data`) instead of
                  replicated
    clock:        monotonic timestamp source for every duration the engine
                  records (``wall_s``/``pack_s``/span timing) — injectable
                  for deterministic tests, and NEVER wall-clock
                  (``time.time`` steps under NTP, folding durations
                  negative)
    obs:          optional :class:`repro.obs.Observability` bundle; default
                  is a private disabled bundle (``Observability.off()``),
                  so instrumentation never branches.  ``bind_obs`` re-homes
                  the engine onto a shared bundle after construction.
    name:         label for this engine's metric series / trace track
                  (``EngineRegistry`` binds the engine key's description)
    condition:    request -> that request's per-lane condition, a pytree of
                  arrays (``None`` -> a vacant lane's); default the class
                  label as an int32 (``label_condition``)
    """

    #: ``last_dispatches`` cap — ``run_batch`` resets the list per call, but
    #: the continuous-serving path appends via ``collect`` indefinitely, so
    #: long soaks keep only the most recent reports.
    MAX_DISPATCH_REPORTS = 256

    def __init__(self, eps_apply: Callable, params, coeffs: SolverCoeffs,
                 spec: SamplerSpec, *, sample_shape: Sequence[int],
                 dtype=jnp.float32, placement: Optional[Placement] = None,
                 param_defs=None, clock: Callable[[], float] = time.monotonic,
                 obs: Optional[Observability] = None,
                 name: Optional[str] = None,
                 condition: Optional[Callable] = None):
        self.eps_apply = eps_apply
        self.condition = condition or label_condition
        self.coeffs = coeffs
        self.spec = spec
        self.sample_shape = tuple(sample_shape)
        self.dtype = dtype
        self.placement = placement or Placement.host()
        if self.placement.is_sharded and params is not None \
                and not _is_abstract(params):
            params = self.placement.shard_params(params, param_defs)
        self.params = params
        self._clock = clock
        self.obs = obs if obs is not None else Observability.off()
        self.name = name or "engine"
        self._jitted = {}   # diagnostics flag -> jitted batched program
        self._stepwise_jits = {}  # "init"/"merge"/("step", K) -> program
        self.stats = StatsView(
            self.obs.metrics, "engine", labels={"engine": self.name},
            initial={"traces": 0, "stepwise_traces": 0, "batches": 0,
                     "requests": 0, "wall_s": 0.0, "pack_s": 0.0,
                     "host_fetch_bytes": 0, "blocking_polls": 0,
                     "gather_launches": 0, "update_launches": 0})
        self.last_batch_walls = []  # per-dispatch walls of the last run_batch
        self.last_dispatches: List[Dict] = []  # per-dispatch reports

    def bind_obs(self, obs: Observability, name: Optional[str] = None) -> None:
        """Re-home this engine onto a shared observability bundle: its
        ``stats`` view starts mirroring into the shared registry (replaying
        current values) and its spans land on the shared tracer.  Stats keep
        their identity — callers holding ``engine.stats`` see no change."""
        self.obs = obs
        if name is not None:
            self.name = name
        self.stats.rebind(obs.metrics, labels={"engine": self.name})

    @property
    def _tracer(self):
        return self.obs.tracer

    @property
    def window(self) -> int:
        """eps evaluations per solver iteration per lane (1 for seq)."""
        T = self.coeffs.T
        if self.spec.is_sequential:
            return 1
        return min(self.spec.window or T, T)

    def _solver_cfg(self, cfg):
        """Thread the placement's time axis into a solver config: when the
        mesh carries time shards, the solve window's denoiser evals shard
        over them (bitwise-identical — see ``ParaTAAConfig.time_axis``)."""
        plc = self.placement
        if plc.time_shards > 1:
            return dataclasses.replace(cfg, time_axis=plc.time_axis)
        return cfg

    def update_launches_per_iter(self) -> int:
        """Modeled kernel launches per solver iteration for the Anderson
        UPDATE stage — the launch-count proxy the CI box measures instead
        of noisy wall-clock (ROADMAP measurement note).  3 for the staged
        round (Gram pass + cumsum/solve stage + apply pass), 1 when the
        round is fused into one ``ops.taa_round`` dispatch, 0 when no
        Anderson update runs at all (seq and fp/history_m<=1 lanes have
        only the plain fixed-point write)."""
        if self.spec.is_sequential:
            return 0
        cfg = self._stepwise_cfg()
        if cfg.history_m <= 1 or cfg.mode in ("fp", "seq"):
            return 0
        return 1 if cfg.fuse_round else 3

    # -- program construction ------------------------------------------------

    def _batched_fn(self, diagnostics: bool):
        coeffs, spec, plc = self.coeffs, self.spec, self.placement
        T = coeffs.T
        eps_apply = self.eps_apply

        def one(params, xi, cond, x0, t_init, tau_sq, iter_cap):
            def eps_fn(xw, taus):
                return eps_apply(params, xw, taus,
                                 _per_row(cond, xw.shape[0]))

            if spec.is_sequential:
                traj = _sequential_sample(eps_fn, coeffs, xi, return_traj=True)
                return traj, dict(iters=jnp.int32(T), nfe=jnp.int32(T),
                                  converged=jnp.asarray(True))
            solver = self._solver_cfg(spec.solver_config(T))
            fn = _parataa.sample_recording if diagnostics else _parataa.sample
            traj, info = fn(eps_fn, coeffs, solver, xi, x_init=x0,
                            dtype=self.dtype, t_init=t_init,
                            tau_sq=tau_sq, iter_cap=iter_cap)
            keep = ("iters", "nfe", "converged", "residuals") + \
                (DIAG_KEYS if diagnostics else ())
            return traj, {k: info[k] for k in keep if k in info}

        vmap_kw = {}
        if plc.is_sharded:
            # pin the vmapped request axis to the data mesh dimension: every
            # sharding constraint inside the solver gets `data` prepended
            vmap_kw["spmd_axis_name"] = plc.spmd_axes()

        def batched(params, xis, conds, x0s, t_inits, tau_sqs, iter_caps):
            # executes at trace time only: one increment per compilation
            self.stats["traces"] += 1
            xis = plc.constrain_batch(xis)
            conds = jax.tree.map(plc.constrain_batch, conds)
            x0s = plc.constrain_batch(x0s)
            t_inits = plc.constrain_batch(t_inits)
            tau_sqs = plc.constrain_batch(tau_sqs)
            iter_caps = plc.constrain_batch(iter_caps)
            return jax.vmap(
                lambda xi, cond, x0, ti, tq, ic:
                    one(params, xi, cond, x0, ti, tq, ic),
                **vmap_kw)(xis, conds, x0s, t_inits, tau_sqs, iter_caps)

        donate = (1, 3) if plc.donate else ()  # xis, x0s: fresh per dispatch
        return jax.jit(batched, donate_argnums=donate)

    def _program(self, diagnostics: bool):
        fn = self._jitted.get(diagnostics)
        if fn is None:
            fn = self._jitted[diagnostics] = self._batched_fn(diagnostics)
        return fn

    def lower_batch(self, batch_size: int, *, params=None,
                    diagnostics: bool = False):
        """Lower the batched program for allocation-free compile analysis
        (dry-run memory / cost / collective tables).  ``params`` may be an
        abstract (ShapeDtypeStruct) tree carrying its own shardings."""
        B = self.placement.round_batch(batch_size)
        T = self.coeffs.T
        plc = self.placement

        def sds(shape, dt):
            kw = {}
            if plc.is_sharded:
                kw["sharding"] = plc.batch_sharding(len(shape))
            return jax.ShapeDtypeStruct(shape, dt, **kw)

        xis = sds((B, T + 1) + self.sample_shape, jnp.float32)
        conds = jax.tree.map(lambda c: sds((B,) + np.shape(c),
                                           np.asarray(c).dtype),
                             self.condition(None))
        t_inits = sds((B,), jnp.int32)
        tau_sqs = sds((B,), jnp.float32)
        with plc.activations():
            return self._program(diagnostics).lower(
                params if params is not None else self.params,
                xis, conds, xis, t_inits, tau_sqs, t_inits)

    # -- request packing -----------------------------------------------------

    def draw_request_noise(self, request: SampleRequest):
        return draw_noises(jax.random.PRNGKey(request.seed), self.coeffs,
                           self.sample_shape)

    def _iter_cap(self, request: SampleRequest) -> int:
        return self.spec.request_iter_cap(request, self.coeffs.T)

    def _tau_sq(self, request: SampleRequest) -> np.float32:
        return self.spec.request_tau_sq(request)

    def _pack(self, requests: Sequence[SampleRequest]):
        T = self.coeffs.T
        xis, conds, x0s, t_inits = [], [], [], []
        tau_sqs, iter_caps = [], []
        for req in requests:
            xi = self.draw_request_noise(req)
            xis.append(xi)
            conds.append(self.condition(req))
            tau_sqs.append(self._tau_sq(req))
            iter_caps.append(self._iter_cap(req))
            if req.init is None:
                x0s.append(xi)          # cold start: noise-initialized
                t_inits.append(T)
            else:
                # cast to the pack dtype (f32, like the drawn noises): a
                # warm start recorded from a reduced-precision solve must
                # not change the packed program's signature
                x0s.append(jnp.asarray(req.init.trajectory, jnp.float32)
                           .reshape(xi.shape))
                # None => full restart (all T rows active); an explicit 0 is
                # a fully-solved warm start the solver merely verifies
                t_inits.append(T if req.init.t_init is None
                               else req.init.t_init)
        return (jnp.stack(xis), jax.tree.map(_stack, *conds),
                jnp.stack(x0s), jnp.asarray(t_inits, jnp.int32),
                jnp.asarray(tau_sqs, jnp.float32),
                jnp.asarray(iter_caps, jnp.int32))

    def pack(self, requests: Sequence[SampleRequest]):
        """Pack requests into the program's (xis, conds, x0s, t_inits,
        tau_sqs, iter_caps) arrays, placed onto the request-axis sharding
        when meshed — the (slots, T+1, ...) trajectory arrays additionally
        land on the window sharding when the mesh carries time shards (and
        their row count divides them)."""
        xis, conds, x0s, t_inits, tau_sqs, iter_caps = \
            self._pack(requests)
        xis, x0s = self.placement.place_window(xis, x0s)
        conds, t_inits, tau_sqs, iter_caps = self._place_batch(
            conds, t_inits, tau_sqs, iter_caps)
        return xis, conds, x0s, t_inits, tau_sqs, iter_caps

    def _place_batch(self, conds, *arrays):
        """``place_batch`` of a per-lane condition pytree's leaves and of
        further per-lane arrays, in one call; returns (conds, *arrays)."""
        leaves, tree = jax.tree.flatten(conds)
        placed = self.placement.place_batch(*leaves, *arrays)
        return (jax.tree.unflatten(tree, placed[:len(leaves)]),
                *placed[len(leaves):])

    # -- execution -----------------------------------------------------------

    def run(self, request: SampleRequest, **kw) -> SampleResult:
        return self.run_batch([request], **kw)[0]

    def dispatch(self, requests: Sequence[SampleRequest], *,
                 slots: Optional[int] = None,
                 diagnostics: bool = False) -> PendingBatch:
        """Pack ``requests`` and launch ONE non-blocking dispatch.

        Pads to ``slots`` request slots (default: the request count, rounded
        up to a multiple of the placement's data shards) by repeating the
        last request; padding is discarded at ``collect``.  Returns as soon
        as the compiled program is enqueued — JAX async dispatch runs it in
        the background, so callers may pack the NEXT batch on the host while
        this one computes (``repro.serving.ServingLoop`` double-buffers on
        exactly this property).  Packing is timed separately (``pack_s``) so
        the reported device wall time excludes host-side packing/PRNG work.
        """
        requests = list(requests)
        if not requests:
            raise ValueError("dispatch needs at least one request")
        self.spec.check_request_flags(
            diagnostics=diagnostics,
            warm_start=any(r.init is not None for r in requests),
            solver_overrides=any(r.has_solver_overrides for r in requests))
        B = self.placement.round_batch(slots or len(requests))
        if len(requests) > B:
            raise ValueError(
                f"{len(requests)} requests exceed {B} request slots")
        chunk = requests + [requests[-1]] * (B - len(requests))
        fn = self._program(diagnostics)
        t0 = self._clock()
        with self._tracer.span("engine.pack", tid=self.name,
                               requests=len(requests), slots=B):
            packed = self.pack(chunk)
        t1 = self._clock()
        with self._tracer.span("engine.dispatch", tid=self.name, slots=B):
            with self.placement.activations():
                trajs, info = fn(self.params, *packed)
        return PendingBatch(trajs=trajs, info=info, requests=requests,
                            slots=B, diagnostics=diagnostics,
                            pack_s=t1 - t0, t_dispatch=t1)

    def collect(self, pending: PendingBatch) -> List[SampleResult]:
        """Block on one in-flight dispatch, record its stats, unpack results.

        ``wall_s`` spans program launch -> outputs ready: when collect runs
        right after dispatch (the sync ``run_batch`` path) that is pure
        device wall time; when other work was interleaved it is the device
        occupancy window of this batch.  ``pack_s`` is reported separately
        in ``last_dispatches``.
        """
        with self._tracer.span("engine.collect", tid=self.name,
                               requests=len(pending.requests)):
            jax.block_until_ready(pending.trajs)
        wall = self._clock() - pending.t_dispatch
        plc = self.placement
        n_real = len(pending.requests)
        self.stats["batches"] += 1
        self.stats["requests"] += n_real
        self.stats["wall_s"] += wall
        self.stats["pack_s"] += pending.pack_s
        self.last_batch_walls.append(wall)
        del self.last_batch_walls[:-self.MAX_DISPATCH_REPORTS]

        # fetch each output ONCE as a host array and slice per request in
        # numpy: per-request jnp slicing would enqueue fresh device ops that
        # queue behind whatever batch is in flight (the double-buffered loop
        # always has one), serializing unpack against the next dispatch
        trajs = np.asarray(pending.trajs)
        info = {k: np.asarray(v) for k, v in pending.info.items()}
        self.stats["blocking_polls"] += 1
        self.stats["host_fetch_bytes"] += trajs.nbytes + sum(
            v.nbytes for v in info.values())

        # the vmapped program runs every slot until the SLOWEST lane's
        # iteration count: wasted_iter_frac is the fraction of lane-
        # iterations the device executed past the owning lane's own
        # convergence (plus padding lanes) — the work the stepwise chunked
        # path reclaims by retiring/refilling lanes mid-solve
        all_iters = np.asarray(info["iters"], np.int64)
        device_iters = int(all_iters.max()) if all_iters.size else 0
        update_launches = device_iters * self.update_launches_per_iter()
        self.stats["update_launches"] += update_launches
        res_batch = info.get("residuals")
        self.last_dispatches.append(dict(
            update_launches=update_launches,
            residual=[_finite_or_none(np.max(res_batch[i]))
                      for i in range(n_real)]
            if res_batch is not None else [None] * n_real,
            wall_s=wall, pack_s=pending.pack_s,
            host_fetch_bytes=trajs.nbytes + sum(v.nbytes
                                                for v in info.values()),
            blocking_polls=1,
            requests=n_real, slots=pending.slots,
            slot_utilization=plc.slot_utilization(n_real, pending.slots),
            axis_utilization=plc.axis_utilization(n_real, pending.slots,
                                                  self.window),
            devices=plc.num_devices, data_shards=plc.data_shards,
            model_shards=plc.model_shards, time_shards=plc.time_shards,
            iters=[int(i) for i in all_iters[:n_real]],
            nfe=[int(n) for n in info["nfe"][:n_real]],
            warm_start_depth=[self._warm_depth(r)
                              for r in pending.requests],
            **self._work_report(int(all_iters[:n_real].sum()),
                                device_iters, pending.slots)))
        del self.last_dispatches[:-self.MAX_DISPATCH_REPORTS]

        T = self.coeffs.T
        results: List[SampleResult] = []
        for i, req in enumerate(pending.requests):
            diag = None
            if pending.diagnostics:
                diag = {k: info[k][i] for k in DIAG_KEYS}
            res = info.get("residuals")
            iters = int(info["iters"][i])
            converged = bool(info["converged"][i])
            results.append(SampleResult(
                x0=trajs[i, 0], trajectory=trajs[i],
                iters=iters, nfe=int(info["nfe"][i]),
                converged=converged,
                early_stopped=self.spec.request_early_stopped(
                    req, T, iters, converged),
                residuals=None if res is None else res[i],
                diagnostics=diag, request=req, wall_s=wall))
        return results

    def _warm_depth(self, request: Optional[SampleRequest]) -> int:
        """Restart depth T_init of a request's warm start: -1 = cold start
        (or vacant lane), T = full restart from a warm trajectory, 0..T-1 =
        a partial resume with that many rows still active."""
        if request is None or request.init is None:
            return -1
        return self.coeffs.T if request.init.t_init is None \
            else int(request.init.t_init)

    def _work_report(self, useful_iters: int, device_iters: int,
                     slots: int) -> Dict:
        """Shared device-work accounting: the device executes
        ``device_iters`` solver iterations across ``slots`` SPMD lanes no
        matter how many lanes still need them, so ``wasted_iter_frac`` is
        the lane-iteration fraction burned past the owning lane's own
        finish (or on vacant/padding lanes) and ``device_nfe`` the true
        denoiser evaluations issued."""
        capacity = device_iters * slots
        return dict(
            device_iters=device_iters,
            device_nfe=capacity * self.window,
            wasted_iter_frac=1.0 - useful_iters / capacity
            if capacity else 0.0)

    def run_batch(self, requests: Sequence[SampleRequest], *,
                  batch_size: Optional[int] = None,
                  diagnostics: bool = False) -> List[SampleResult]:
        """Run all requests, ``batch_size`` at a time (default: one batch).

        The dispatch size is rounded up to a multiple of the placement's
        data shards, and the final partial batch is padded by repeating its
        last request (padding discarded) so every dispatch reuses one
        compiled program with one request-slot count per device.  This is
        the synchronous path — each dispatch is collected before the next
        one is packed; ``repro.serving`` drives ``dispatch``/``collect``
        directly to overlap the two.
        """
        if not requests:
            return []
        if batch_size is not None and batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        B = self.placement.round_batch(batch_size or len(requests))
        self.last_batch_walls = []
        self.last_dispatches = []
        results: List[SampleResult] = []
        for lo in range(0, len(requests), B):  # step by SLOTS, not batch_size:
            # a rounded-up dispatch takes B real requests when available
            pending = self.dispatch(requests[lo:lo + B], slots=B,
                                    diagnostics=diagnostics)
            results.extend(self.collect(pending))
        return results

    # -- stepwise (iteration-level) execution --------------------------------
    #
    # The chunked serving path: one LaneBank per engine holds a live batched
    # SolverState; `stepwise_step` advances every lane by `chunk_iters`
    # guarded solver iterations, `stepwise_harvest` retires lanes the moment
    # THEIR OWN solve finishes (convergence, max_iters, or a Sec 4.1
    # quality-steps early exit), and `stepwise_refill` packs fresh requests
    # into the vacated lanes of the SAME live state — so the compiled step
    # program never retraces.  Five programs total per engine: open (vacant
    # bank), init (ONE lane — refill packs/draws exactly one request's
    # noise, not a bank-width batch), merge (broadcast the one fresh lane
    # into the masked slot), step (which also emits the packed (slots, 5)
    # scheduling summary so polling fetches ONE tiny array instead of four
    # state fields), and gather (harvest fetches only the RETIRED lanes'
    # trajectory rows instead of the whole bank);
    # ``stats["stepwise_traces"]`` must stay at 5 across refills.

    def _stepwise_cfg(self):
        return self._solver_cfg(self.spec.stepwise_config(self.coeffs.T))

    def _constrain_state(self, tree):
        plc = self.placement
        return jax.tree.map(plc.constrain_batch, tree)

    def _stepwise_program(self, kind, arg: int = 0):
        # "step" keys on its chunk size, "open" on its slot count — each
        # distinct geometry is its own (once-compiled) program
        key = (kind, arg) if kind in ("step", "open") else kind
        chunk_iters = arg
        fn = self._stepwise_jits.get(key)
        if fn is not None:
            return fn
        coeffs, plc = self.coeffs, self.placement
        cfg = self._stepwise_cfg()
        eps_apply = self.eps_apply

        def lane_init(xi, x0, t_init, tau_sq, iter_cap):
            return _parataa.init_state(
                coeffs, cfg, xi, x_init=x0, dtype=self.dtype,
                t_init=t_init, tau_sq=tau_sq, iter_cap=iter_cap)

        if kind == "open":
            B = chunk_iters  # slot count rides the cache-key int

            def program(xi):
                self.stats["stepwise_traces"] += 1  # trace time only
                lane = lane_init(xi, xi, coeffs.T, jnp.float32(0.0),
                                 jnp.int32(0))  # vacant: finished at birth
                return self._constrain_state(jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (B,) + x.shape), lane))

        elif kind == "init":
            vmap_kw = {"spmd_axis_name": plc.spmd_axes()} \
                if plc.is_sharded else {}

            def program(xis, x0s, t_inits, tau_sqs, iter_caps):
                self.stats["stepwise_traces"] += 1
                args = [plc.constrain_batch(a)
                        for a in (xis, x0s, t_inits, tau_sqs, iter_caps)]
                return jax.vmap(lane_init, **vmap_kw)(*args)

        elif kind == "merge":
            def program(state, fresh, conds, fresh_conds, mask):
                self.stats["stepwise_traces"] += 1

                def pick(old, new):
                    m = mask.reshape((-1,) + (1,) * (old.ndim - 1))
                    return plc.constrain_batch(jnp.where(m, new, old))

                conds = jax.tree.map(pick, conds, fresh_conds)
                return jax.tree.map(pick, state, fresh), conds

        elif kind == "step":
            shape = self.sample_shape

            def lane_step(params, state, cond):
                def eps_fn(xw, taus):
                    return eps_apply(params, xw, taus,
                                     _per_row(cond, xw.shape[0]))

                return _parataa.step_chunk(eps_fn, coeffs, cfg, state,
                                           chunk_iters, sample_shape=shape)

            vmap_kw = {"spmd_axis_name": plc.spmd_axes()} \
                if plc.is_sharded else {}

            def program(params, state, conds):
                self.stats["stepwise_traces"] += 1
                state = self._constrain_state(state)
                conds = self._constrain_state(conds)
                out = jax.vmap(lambda s, c: lane_step(params, s, c),
                               **vmap_kw)(state, conds)
                # piggybacked poll: one packed (slots, 5) scheduling array
                # rides out of the chunk, so the host never issues a
                # separate per-field fetch to learn who finished; column 4
                # is the per-lane convergence residual, bitcast f32->int32
                # so telemetry shares the one int32 fetch instead of
                # adding a second host copy
                summary = jnp.stack(
                    [out.finished.astype(jnp.int32), out.it, out.nfe,
                     out.done.astype(jnp.int32),
                     jax.lax.bitcast_convert_type(
                         _parataa.lane_residual(out), jnp.int32)], axis=-1)
                return out, summary

        elif kind == "gather":
            # harvest-time device-side gather: only the RETIRED lanes' rows
            # cross to the host.  idx is a fixed (slots,)-length lane-index
            # vector (padded by repeating the first retired lane), so this
            # compiles exactly once; the host fetches just the first
            # len(ready) rows of the output.  Sequential specs discard
            # residuals, so their gather program never touches r_last.
            seq = self.spec.is_sequential

            def program(x, r_last, idx):
                self.stats["stepwise_traces"] += 1
                xg = jnp.take(x, idx, axis=0)
                if seq:
                    return xg, None
                return xg, jnp.take(r_last, idx, axis=0)

        else:
            raise ValueError(f"unknown stepwise program {kind!r}")

        fn = self._stepwise_jits[key] = jax.jit(program)
        return fn

    def validate_request(self, request: SampleRequest) -> None:
        """Raise exactly what a dispatch carrying ``request`` would raise —
        lets a serving loop (or ``RequestQueue.submit`` via
        ``EngineRegistry.validate_submit``) fail ONE incompatible request's
        ticket instead of a whole admission group.  Warm starts are checked
        structurally (shape/dtype metadata only — no host transfer): a
        mismatched trajectory would otherwise poison a packed dispatch at
        trace time."""
        self.spec.check_request_flags(
            warm_start=request.init is not None,
            solver_overrides=request.has_solver_overrides)
        self.condition(request)
        if request.init is not None:
            self._validate_init(request.init)

    def _validate_init(self, init) -> None:
        """Structural warm-start checks against this engine's geometry —
        shape/dtype METADATA only, so validating a device-resident
        trajectory never forces a host transfer."""
        T = self.coeffs.T
        traj = init.trajectory
        shape = tuple(getattr(traj, "shape", None) or np.shape(traj))
        want_shape = (T + 1,) + self.sample_shape
        if not shape or shape[0] != T + 1 or \
                int(np.prod(shape, dtype=np.int64)) != \
                int(np.prod(want_shape, dtype=np.int64)):
            raise ValueError(
                f"warm-start trajectory shape {shape} does not match this "
                f"engine's (T+1, *sample_shape) = {want_shape} "
                f"(T={T}, sample_shape={self.sample_shape})")
        dtype = getattr(traj, "dtype", None)
        if dtype is None:
            dtype = np.asarray(traj).dtype
        if not jnp.issubdtype(dtype, jnp.floating):
            raise ValueError(
                f"warm-start trajectory dtype {dtype} is not a floating "
                f"type; pack casts warm starts to float32 (reduced-"
                f"precision floats are fine, integer/bool buffers are not)")
        t_init = init.t_init
        if t_init is not None and not 0 <= int(t_init) <= T:
            raise ValueError(
                f"warm-start t_init={t_init} outside [0, T={T}]")

    def stepwise_open(self, slots: int, *, chunk_iters: int) -> LaneBank:
        """Open an all-vacant LaneBank at the engine's fixed slot geometry
        (every lane inits ``finished``, so chunks no-op it until refill).
        Compiles the open program; init/merge compile on the first refill,
        the step program on the first ``stepwise_step``, and the gather on
        the first harvest that retires a lane."""
        if chunk_iters < 1:
            raise ValueError(f"chunk_iters must be >= 1, got {chunk_iters}")
        B = self.placement.round_batch(slots)
        t0 = self._clock()
        with self._tracer.span("stepwise.open", tid=self.name, slots=B):
            xi = self.draw_request_noise(SampleRequest())
            with self.placement.activations():
                state = self._stepwise_program("open", B)(xi)
            (conds,) = self._place_batch(jax.tree.map(
                lambda c: jnp.broadcast_to(jnp.asarray(c), (B,) + np.shape(c)),
                self.condition(None)))
        bank = LaneBank(state=state, conds=conds, requests=[None] * B,
                        slots=B, chunk_iters=chunk_iters)
        bank.pack_s += self._clock() - t0
        return bank

    def stepwise_refill(self, bank: LaneBank, lanes: Sequence[int],
                        requests: Sequence[SampleRequest]) -> None:
        """Pack ``requests`` into the given vacant ``lanes`` of the live
        bank state — no retrace, and ONE init + ONE merge program launch
        per refill round no matter how many lanes it fills (launch
        rendezvous dominates on a multi-device host).  Only the admitted
        requests pay PRNG/pack cost: their packed rows are permuted into
        lane positions and the remaining rows repeat an already-packed row
        under a zeroed iteration budget (vacant = finished at birth)."""
        requests = list(requests)
        if len(requests) != len(lanes):
            raise ValueError(f"{len(requests)} requests for "
                             f"{len(lanes)} lanes")
        if not requests:
            return
        taken = [bank.requests[lane] for lane in lanes]
        if any(r is not None for r in taken):
            raise ValueError(f"lanes {list(lanes)} are not all vacant")
        self.spec.check_request_flags(
            warm_start=any(r.init is not None for r in requests),
            solver_overrides=any(r.has_solver_overrides for r in requests))
        t0 = self._clock()
        with self._tracer.span("stepwise.refill", tid=self.name,
                               lanes=len(lanes)):
            packed = self._pack(requests)       # (k, ...) — k PRNG draws
            pos = {lane: i for i, lane in enumerate(lanes)}
            idx = np.asarray([pos.get(j, 0) for j in range(bank.slots)])
            xis, conds, x0s, t_inits, tau_sqs, iter_caps = jax.tree.map(
                lambda a: jnp.take(a, idx, axis=0), packed)
            # lanes outside the refill keep their OLD state (merge mask), so
            # the repeated filler rows never land anywhere
            untouched = np.asarray([j not in pos
                                    for j in range(bank.slots)])
            xis, x0s = self.placement.place_window(xis, x0s)
            conds, t_inits, tau_sqs, iter_caps, mask = self._place_batch(
                conds, t_inits, tau_sqs, iter_caps, jnp.asarray(~untouched))
            with self.placement.activations():
                fresh = self._stepwise_program("init")(
                    xis, x0s, t_inits, tau_sqs, iter_caps)
                bank.state, bank.conds = self._stepwise_program("merge")(
                    bank.state, fresh, bank.conds, conds, mask)
        for lane, req in zip(lanes, requests):
            bank.requests[lane] = req
        # the pre-merge summary no longer describes the refilled lanes —
        # drop it; the next poll (rare: only a report issued before the
        # next step) falls back to reading the state fields directly
        bank.summary = None
        bank.poll_cache = None
        bank.refills += 1
        bank.pack_s += self._clock() - t0

    def stepwise_step(self, bank: LaneBank) -> None:
        """Advance every lane by ``bank.chunk_iters`` guarded solver
        iterations (non-blocking: JAX async dispatch) and start the
        piggybacked (slots, 5) scheduling summary's device->host copy —
        by the time the NEXT round's harvest polls, the bytes are already
        on the host and the ``device_get`` returns without stalling.

        The ``stepwise.step`` span covers the enqueue only: the chunk runs
        on the device after it closes, and the next ``stepwise.poll``
        span is where the host waits for it."""
        with self._tracer.span("stepwise.step", tid=self.name,
                               chunk_iters=bank.chunk_iters,
                               occupied=bank.occupied):
            with self.placement.activations():
                bank.state, summary = self._stepwise_program(
                    "step", bank.chunk_iters)(self.params, bank.state,
                                              bank.conds)
        bank.summary = summary
        bank.poll_cache = None
        if hasattr(summary, "copy_to_host_async"):
            summary.copy_to_host_async()
        bank.device_iters += bank.chunk_iters
        launches = bank.chunk_iters * self.update_launches_per_iter()
        bank.update_launches += launches
        self.stats["update_launches"] += launches

    def _count_fetch(self, bank: LaneBank, nbytes: int, *,
                     polls: int = 0, gathers: int = 0) -> None:
        bank.host_fetch_bytes += nbytes
        bank.blocking_polls += polls
        bank.gather_launches += gathers
        self.stats["host_fetch_bytes"] += nbytes
        self.stats["blocking_polls"] += polls
        self.stats["gather_launches"] += gathers

    def stepwise_poll(self, bank: LaneBank) -> Dict[str, np.ndarray]:
        """The round's per-lane scheduling view (blocks on the chunk in
        flight; trajectories stay on device until harvest).  ONE blocking
        fetch per round: the first caller materializes the piggybacked
        (slots, 5) summary the step program emitted (whose host copy was
        started asynchronously at step time) and caches it on the bank;
        harvest and report share the cache until step/refill invalidate
        it."""
        if bank.poll_cache is not None:
            return bank.poll_cache
        if bank.summary is not None:
            with self._tracer.span("stepwise.poll", tid=self.name):
                packed = np.asarray(bank.summary)
            # column 4 carries the f32 per-lane residual bitcast into the
            # int32 payload; .copy() first — a column slice is
            # non-contiguous, which .view cannot reinterpret
            polled = dict(finished=packed[:, 0].astype(bool),
                          iters=packed[:, 1], nfe=packed[:, 2],
                          done=packed[:, 3].astype(bool),
                          residual=packed[:, 4].copy().view(np.float32))
            self._count_fetch(bank, packed.nbytes, polls=1)
        else:
            # no chunk has run since open/refill: read the state fields
            state = bank.state
            with self._tracer.span("stepwise.poll", tid=self.name,
                                   fallback=True):
                finished, it, nfe, done, res = jax.device_get(
                    (state.finished, state.it, state.nfe, state.done,
                     _parataa.lane_residual(state)))
            polled = dict(finished=np.asarray(finished),
                          iters=np.asarray(it), nfe=np.asarray(nfe),
                          done=np.asarray(done),
                          residual=np.asarray(res, np.float32))
            self._count_fetch(bank, sum(v.nbytes for v in polled.values()),
                              polls=1)
        bank.poll_cache = polled
        return polled

    def stepwise_harvest(self, bank: LaneBank):
        """Retire every occupied lane whose OWN solve has finished: returns
        ``[(lane, SampleResult), ...]`` and vacates those lanes (their state
        stays ``finished``, so subsequent chunks no-op them until refill).

        Device-resident: only the RETIRED lanes' trajectory rows cross to
        the host — one gather launch + a ``len(ready) x (T+1) x D`` fetch
        instead of the whole ``slots``-wide bank — and the residual fetch
        is skipped entirely for sequential specs (which discard it)."""
        if not any(req is not None for req in bank.requests):
            return []                       # idle bank: nothing to poll
        polled = self.stepwise_poll(bank)
        ready = [i for i, req in enumerate(bank.requests)
                 if req is not None and polled["finished"][i]]
        if not ready:
            return []
        T = self.coeffs.T
        n = len(ready)
        idx = np.asarray(ready + [ready[0]] * (bank.slots - n), np.int32)
        with self._tracer.span("stepwise.harvest", tid=self.name, retired=n):
            with self.placement.activations():
                xg, rg = self._stepwise_program("gather")(
                    bank.state.x, bank.state.r_last, jnp.asarray(idx))
            # fetch ONLY the first n gathered rows (the padding rows repeat
            # ready[0] and never leave the device)
            trajs = np.asarray(xg[:n]).reshape(
                (n, T + 1) + self.sample_shape)
            fetched = trajs.nbytes
            residuals = None
            if rg is not None:
                residuals = np.asarray(rg[:n])
                fetched += residuals.nbytes
        self._count_fetch(bank, fetched, gathers=1)
        bank.harvests += 1
        out = []
        for j, lane in enumerate(ready):
            req = bank.requests[lane]
            iters = int(polled["iters"][lane])
            nfe = int(polled["nfe"][lane])
            converged = bool(polled["done"][lane])
            out.append((lane, SampleResult(
                x0=trajs[j, 0], trajectory=trajs[j],
                iters=iters, nfe=nfe, converged=converged,
                early_stopped=self.spec.request_early_stopped(
                    req, T, iters, converged),
                residuals=None if residuals is None else residuals[j],
                request=req)))
            bank.requests[lane] = None
            bank.useful_iters += iters
            bank.harvested_nfe += nfe
            bank.completed += 1
        return out

    def stepwise_report(self, bank: LaneBank) -> Dict:
        """Work-accounting snapshot of a bank, shaped like a
        ``last_dispatches`` entry (feeds ``Batcher.note`` / benchmarks).
        Reuses the round's cached poll when harvest already paid for it —
        reporting never adds a second blocking fetch to a round."""
        polled = self.stepwise_poll(bank)
        live_iters = int(sum(polled["iters"][i]
                             for i, r in enumerate(bank.requests)
                             if r is not None))
        useful = bank.useful_iters + live_iters
        return dict(
            slots=bank.slots, chunk_iters=bank.chunk_iters,
            completed=bank.completed, refills=bank.refills,
            occupied=bank.occupied, pack_s=bank.pack_s,
            useful_iters=useful,
            residual=[_finite_or_none(polled["residual"][i])
                      if bank.requests[i] is not None else None
                      for i in range(bank.slots)],
            warm_start_depth=[self._warm_depth(r) for r in bank.requests],
            host_fetch_bytes=bank.host_fetch_bytes,
            blocking_polls=bank.blocking_polls,
            gather_launches=bank.gather_launches,
            harvests=bank.harvests,
            update_launches=bank.update_launches,
            devices=self.placement.num_devices,
            slot_utilization=self.placement.slot_utilization(
                bank.occupied, bank.slots),
            axis_utilization=self.placement.axis_utilization(
                bank.occupied, bank.slots, self.window),
            data_shards=self.placement.data_shards,
            model_shards=self.placement.model_shards,
            time_shards=self.placement.time_shards,
            **self._work_report(useful, bank.device_iters, bank.slots))

    # -- elastic migration ---------------------------------------------------

    #: LaneBank counters a snapshot carries across an engine rebuild, so a
    #: migrated bank's report still covers its whole life.
    _CARRIED_COUNTERS = ("device_iters", "useful_iters", "harvested_nfe",
                         "completed", "refills", "pack_s",
                         "host_fetch_bytes", "blocking_polls",
                         "gather_launches", "harvests", "update_launches")

    def fetch_bank(self, bank: LaneBank) -> BankSnapshot:
        """Pull a live bank's entire solver state to the host as a
        placement-free :class:`BankSnapshot` (the elastic-recovery fetch).
        One blocking device->host transfer of the full state pytree —
        deliberately NOT the piggybacked summary path: recovery needs the
        exact trajectory bytes, and it runs once per device-loss event,
        not once per round.  Counted against this bank's fetch accounting
        (``host_fetch_bytes`` + 1 blocking poll) so recovery cost is
        visible in the same ledger as the steady-state protocol."""
        with self._tracer.span("stepwise.fetch_bank", tid=self.name,
                               slots=bank.slots, occupied=bank.occupied):
            state, conds = jax.device_get((bank.state, bank.conds))
        state, conds = jax.tree.map(np.asarray, (state, conds))
        counters = {k: getattr(bank, k) for k in self._CARRIED_COUNTERS}
        snap = BankSnapshot(state=state, conds=conds,
                            requests=list(bank.requests), slots=bank.slots,
                            chunk_iters=bank.chunk_iters, counters=counters)
        self._count_fetch(bank, snap.nbytes(), polls=1)
        snap.counters["host_fetch_bytes"] = bank.host_fetch_bytes
        snap.counters["blocking_polls"] = bank.blocking_polls
        return snap

    def adopt_bank(self, snapshot: BankSnapshot, *,
                   chunk_iters: Optional[int] = None) -> LaneBank:
        """Re-place a :class:`BankSnapshot` onto THIS engine's placement
        and return a live :class:`LaneBank` that resumes the solve exactly
        where ``fetch_bank`` froze it.  No program launch: each state leaf
        is ``device_put`` onto the batch sharding (matching the in-program
        batch-only constraint the step program applies), so the next
        ``stepwise_step`` continues the guarded scan on the new mesh with
        bitwise-identical per-lane math.  ``summary``/``poll_cache`` start
        empty — the first post-adopt poll takes the documented fallback
        path (still exactly one blocking poll for that round)."""
        B = snapshot.slots
        if self.placement.round_batch(B) != B:
            raise ValueError(
                f"snapshot slots={B} do not divide the adopting engine's "
                f"data shards ({self.placement.data_shards}); rebuild with "
                f"a compatible data-parallel degree")
        with self._tracer.span("stepwise.adopt_bank", tid=self.name,
                               slots=B, occupied=snapshot.occupied):
            def place(leaf):
                (out,) = self.placement.place_batch(jnp.asarray(leaf))
                return out
            state, conds = jax.tree.map(place, (snapshot.state,
                                                snapshot.conds))
        bank = LaneBank(state=state, conds=conds,
                        requests=list(snapshot.requests), slots=B,
                        chunk_iters=int(chunk_iters or snapshot.chunk_iters),
                        **snapshot.counters)
        return bank

    def reset_stats(self) -> None:
        """Rewind the serving counters and dispatch reports — e.g. after a
        warmup or compile-only pass — keeping ``traces`` (and its stepwise
        twin): compilations are a property of the program cache, not of
        traffic.  Zeroes EVERY traffic key the dict currently holds (not a
        hand-enumerated list, so counters added later rewind too) and
        zeroes them THROUGH the view, keeping the dict's identity and its
        registry mirror consistent."""
        for key, value in list(self.stats.items()):
            if key in ("traces", "stepwise_traces"):
                continue
            self.stats[key] = 0.0 if isinstance(value, float) else 0
        self.last_batch_walls = []
        self.last_dispatches = []

    def throughput(self) -> float:
        """Requests per second over every batch this engine has run."""
        return self.stats["requests"] / max(self.stats["wall_s"], 1e-9)


def label_condition(request: Optional[SampleRequest]):
    """The per-lane condition of a label-conditioned denoiser (the DiT):
    the request's class label as an int32, 0 for a vacant lane."""
    return np.int32(0 if request is None else request.label)


def _stack(*leaves):
    """One condition leaf of several requests as a (k, ...) device array:
    host values stacked on the host and sent once, device values (a
    caption made on the device) stacked there."""
    if all(isinstance(c, (np.ndarray, np.generic)) for c in leaves):
        return jnp.asarray(np.stack(leaves))
    return jnp.stack(leaves)


def _per_row(cond, rows: int):
    """A lane's condition, each leaf repeated for the lane's ``rows``
    denoiser rows (a window of ParaTAA rows, or the one sequential row)."""
    return jax.tree.map(lambda c: jnp.broadcast_to(c, (rows,) + c.shape),
                        cond)


def _is_abstract(params) -> bool:
    leaves = jax.tree.leaves(params)
    return bool(leaves) and isinstance(leaves[0], jax.ShapeDtypeStruct)


def _finite_or_none(value) -> Optional[float]:
    """Report-friendly residual: +inf (a lane that never produced a
    first-order residual — sequential, or polled before its first parallel
    iterate) becomes None so reports stay strict-JSON-serializable."""
    value = float(value)
    return value if np.isfinite(value) else None
