"""Serving driver: batched ParaTAA diffusion sampling (the paper's workload).

Each request is (class label | conditioning, seed, optional warm start).
The denoiser comes from ``--arch`` through one table (``DENOISERS``): the
class-conditional DiT (``dit-xl``), or PixArt-α (``pixart-alpha``), whose
requests carry a caption drawn from ``--seed`` and which serves guided.
Requests run through one ``repro.sampling.SamplingEngine`` per
(arch, T, solver) configuration, and the engine owns its device placement:
``--mesh`` resolves a named mesh from ``repro.launch.mesh`` (with
``--data-parallel`` / ``--model-parallel`` / ``--time-parallel`` axis
overrides) into a ``Placement`` that shards the request axis over `data`,
TP-shards the denoiser over `model`, and — on the ``*-time`` meshes —
shards each request's solve window over `time` (bitwise-identical to the
unsharded window); without ``--mesh`` the engine runs the bitwise-
identical host placement.  Sequential DDIM/DDPM is the same engine with the
"seq" spec.  Every dispatch reports device utilization (request slots filled
x devices engaged) without retracing — one compilation per engine.
Straggler mitigation duplicates the slowest window shard on spare capacity
(value-deterministic, first-finisher-wins).

    PYTHONPATH=src python -m repro.launch.serve --smoke --requests 8 \
        --solver taa --steps-T 50 --batch-size 4 \
        --mesh debug --data-parallel 4 --model-parallel 2

``--serve-async`` swaps the blocking loop for the ``repro.serving``
continuous-batching layer: a Poisson (``--arrival-rate``) or closed-loop
(rate 0) request stream over mixed (T, solver) ``EngineKey``s is submitted
to a ``RequestQueue``, an ``EngineRegistry`` lazily builds one engine per
key on the shared placement, and a double-buffered ``ServingLoop`` packs
the next dispatch while the previous one computes, reporting p50/p95
latency, throughput, and per-key slot utilization:

    PYTHONPATH=src python -m repro.launch.serve --serve-async --smoke \
        --requests 12 --steps-T 8 --batch-size 4 --arrival-rate 100 \
        --mesh debug --data-parallel 4 --model-parallel 2

``--chunk-iters K`` upgrades the async path to ITERATION-LEVEL continuous
batching (the Sec 4.1 early-stopping serving mode): each key keeps one live
``LaneBank`` of resumable solver state, advanced K solver iterations per
round; a lane retires the moment ITS request converges — or early-exits at
its own per-request ``tau`` / ``quality_steps`` / ``max_iters`` budget —
and the freed lane is refilled from the queue mid-solve, no recompile.
``--loose-tau-frac``/``--loose-tau``/``--quality-steps`` shape a mixed-tau
request population where the per-batch baseline would run every lane to
the slowest member:

    PYTHONPATH=src python -m repro.launch.serve --serve-async --smoke \
        --requests 12 --steps-T 8 --batch-size 4 --arrival-rate 100 \
        --chunk-iters 2 --loose-tau-frac 0.5 --quality-steps 6 \
        --mesh debug --data-parallel 4 --model-parallel 2

``--refine`` (requires ``--chunk-iters``) upgrades the early-exit traffic to
TWO-TIER draft-and-refine serving: an early-exited draft resolves its
ticket's draft stage immediately and a warm-started, preemptible
continuation splices back into the live bank as background work, completing
the same ticket at full tolerance.  ``--cache`` turns on the Sec 4.2
warm-start trajectory cache: converged results are recorded per key and
later submissions auto-populate ``SampleRequest.init`` at submit time
(with submit-time warm-start validation), so repeat/neighbor traffic
solves in a fraction of the cold iteration count:

    PYTHONPATH=src python -m repro.launch.serve --serve-async --smoke \
        --requests 12 --steps-T 8 --batch-size 4 --arrival-rate 100 \
        --chunk-iters 2 --loose-tau-frac 0.5 --quality-steps 3 \
        --refine --cache --mesh debug --data-parallel 4 --model-parallel 2
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Callable


def _force_host_devices(argv):
    """Grow the forced host-platform device count to fit --mesh BEFORE jax
    initializes its backend (the count is locked at first device query).
    Only takes effect for the CLI entry point; no-op when the flag is
    already set or no mesh was requested."""
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--mesh", default="none")
    p.add_argument("--data-parallel", type=int, default=0)
    p.add_argument("--model-parallel", type=int, default=0)
    p.add_argument("--time-parallel", type=int, default=0)
    args, _ = p.parse_known_args(argv)
    if args.mesh == "none":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    from repro.launch.mesh import get_mesh_spec
    try:
        spec = get_mesh_spec(args.mesh).with_sizes(
            data_parallel=args.data_parallel or None,
            model_parallel=args.model_parallel or None,
            time_parallel=args.time_parallel or None)
    except (KeyError, ValueError):
        return  # let main() raise the informative registry error
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count="
        f"{spec.num_devices}").strip()


if __name__ == "__main__":  # must precede the jax import below
    _force_host_devices(sys.argv[1:])
    # --backend-tune: merge the GPU XLA serving flags (latency-hiding
    # scheduler, Triton fusion, async collectives) into XLA_FLAGS before
    # the backend locks them; a guaranteed no-op on CPU/TPU hosts
    from repro.launch.backend import apply_backend_tune
    apply_backend_tune(sys.argv[1:])

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import get_arch
from repro.core import ddim_coeffs, ddpm_coeffs
from repro.diffusion import dit as dit_mod
from repro.diffusion import pixart
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_mesh, mesh_names
from repro.models import pdefs
from repro.obs import (Observability, compile_totals, count_compiles,
                       json_safe)
from repro.runtime import StragglerMitigator
from repro.sampling import (Placement, SampleRequest, SamplingEngine,
                            get_sampler)
from repro.sampling.engine import label_condition
from repro.serving import (Batcher, BatchingPolicy, EngineKey, EngineRegistry,
                           FaultInjector, RefinePlanner, RefinePolicy,
                           RequestQueue, ResilientServingLoop, ServingLoop)


def _dit_eps_apply(cfg):
    """Engine-shaped DiT adapter: (params, x, taus, labels) -> eps."""
    def eps_apply(params, xw, taus_w, labels):
        return dit_mod.dit_apply(params, cfg, xw, taus_w, labels)
    return eps_apply


def _draw_label(rng, cfg) -> dict:
    return {"label": int(rng.integers(0, cfg.num_classes))}


def _draw_caption(rng, cfg) -> dict:
    """A caption (``pixart.draw_caption``) from a seed drawn from ``rng``,
    and a token count uniform over [min(10, L), L]."""
    seed = int(rng.integers(1 << 30))
    tokens = int(rng.integers(min(10, cfg.caption_len), cfg.caption_len + 1))
    caption = pixart.draw_caption(seed, (cfg.caption_len, cfg.caption_dim))
    return {"cond": {"caption": caption, "tokens": np.int32(tokens)}}


@dataclasses.dataclass(frozen=True)
class Denoiser:
    """What serving one denoiser takes, each from the arch's config: its
    weight tree, the engine-shaped eps adapter, a request's per-lane
    condition (``SamplingEngine(condition=...)``), and the conditioning
    keyword arguments of a request drawn from a generator."""
    defs: Callable
    eps_apply: Callable
    condition: Callable
    draw: Callable


DENOISERS = {
    "dit-xl": Denoiser(dit_mod.dit_defs, _dit_eps_apply,
                       lambda cfg: label_condition, _draw_label),
    "pixart-alpha": Denoiser(pixart.pixart_defs, pixart.make_eps_apply,
                             pixart.request_condition, _draw_caption),
}


def denoiser(cfg) -> Denoiser:
    """The table entry of ``cfg``'s architecture (its smoke size too)."""
    name = cfg.name.removesuffix("-smoke")
    if name not in DENOISERS:
        raise SystemExit(f"--arch {name} is not a denoiser this server "
                         f"runs; known: {sorted(DENOISERS)}")
    return DENOISERS[name]


def make_eps_apply(cfg):
    """``cfg``'s engine-shaped denoiser: (params, x, taus, cond) -> eps."""
    return denoiser(cfg).eps_apply(cfg)


def make_placement(mesh_name: str = "none", *, data_parallel: int = 0,
                   model_parallel: int = 0, time_parallel: int = 0,
                   donate: bool = False) -> Placement:
    """Resolve serving CLI placement flags into a Placement."""
    if mesh_name == "none":
        return Placement.host()
    mesh = make_mesh(mesh_name, data_parallel=data_parallel or None,
                     model_parallel=model_parallel or None,
                     time_parallel=time_parallel or None)
    return Placement.for_mesh(mesh, donate=donate)


_UNSTACK = jax.jit(pdefs.unstack)


def _layer_of(s: jax.ShapeDtypeStruct) -> jax.ShapeDtypeStruct:
    """One layer of an abstract stacked leaf, sharded as its slices are
    (the ``layers`` dim is never sharded)."""
    sharding = s.sharding
    if isinstance(sharding, NamedSharding):
        sharding = NamedSharding(sharding.mesh, P(*sharding.spec[1:]))
    return jax.ShapeDtypeStruct(s.shape[1:], s.dtype, sharding=sharding)


def serving_weights(params, cfg, placement: Placement = None):
    """The weights an engine serves ``cfg``'s denoiser from: ``params``
    with its stacked ``blocks`` (the stored layout of init, checkpoints
    and training) as a tuple of per-layer trees, made once by one jitted
    call on ``placement``'s devices.  A layer loop over stacked blocks
    slices layer i out inside the compiled step, which on a TPU writes a
    copy of every block weight at every step (bf16 for the MXU, f32 for
    ``ada``); per-layer arrays feed the dots as they are.  Abstract params
    (ShapeDtypeStructs) come back abstract, with the slices' shardings;
    ``None`` and a tree already per layer come back as they are."""
    if params is None or isinstance(params["blocks"], tuple):
        return params
    blocks = params["blocks"]
    leaf = jax.tree.leaves(blocks)[0]
    if isinstance(leaf, jax.ShapeDtypeStruct):
        layer = jax.tree.map(_layer_of, blocks)
        return dict(params, blocks=(layer,) * leaf.shape[0])
    if placement is not None:
        blocks = placement.shard_params(blocks,
                                        denoiser(cfg).defs(cfg)["blocks"])
    return dict(params, blocks=_UNSTACK(blocks))


def make_engine(params, cfg, coeffs, spec, *, placement: Placement = None):
    """One engine for ``cfg``'s denoiser at its own latent shape:
    ``num_tokens`` tokens of ``latent_dim`` (256 x 16 for DiT-XL/2 at
    256x256), serving per-layer block weights (``serving_weights``; the
    defs the engine places them by are unstacked to match)."""
    den = denoiser(cfg)
    defs = den.defs(cfg)
    return SamplingEngine(make_eps_apply(cfg),
                          serving_weights(params, cfg, placement),
                          coeffs, spec,
                          sample_shape=(cfg.num_tokens, cfg.latent_dim),
                          placement=placement,
                          param_defs=dict(defs,
                                          blocks=pdefs.unstack(defs["blocks"])),
                          condition=den.condition(cfg))


def serve_batch(engine: SamplingEngine, requests, *, batch_size=None):
    """Run requests through the engine ``batch_size`` at a time.

    requests: list of SampleRequest, or legacy (label, seed) tuples.
    Returns (stacked x0 latents, per-request stats, straggler mitigator).
    """
    requests = [r if isinstance(r, SampleRequest) else SampleRequest(*r)
                for r in requests]
    straggler = StragglerMitigator()
    results = engine.run_batch(requests, batch_size=batch_size)
    for wall in engine.last_batch_walls:  # one latency sample per dispatch
        straggler.record(wall)
    stats = [{"cond": res.request.condition_key, "iters": res.iters,
              "nfe": res.nfe, "wall_s": res.wall_s} for res in results]
    return jnp.stack([res.x0 for res in results]), stats, straggler


def make_requests(args, cfg):
    """``--requests`` requests drawn from ``--seed``: a class label or a
    caption each, as the denoiser takes."""
    rng = np.random.default_rng(args.seed)
    draw = denoiser(cfg).draw
    return [SampleRequest(**draw(rng, cfg), seed=int(rng.integers(1 << 30)))
            for _ in range(args.requests)]


def resolve_coeffs(args, T: int):
    """CLI schedule flag -> SolverCoeffs at step count ``T``."""
    return (ddim_coeffs if args.sampler == "ddim" else ddpm_coeffs)(T)


#: --use-pallas CLI value -> SamplerSpec.use_pallas (None = backend auto)
USE_PALLAS = {"auto": None, "on": True, "off": False}


def resolve_spec(args, solver: str):
    """CLI solver flags -> SamplerSpec — ONE resolution shared by the sync
    and async paths, so the same flags always mean the same solver."""
    if solver == "seq":
        return get_sampler("seq")
    return get_sampler(solver, order_k=args.order_k,
                       history_m=args.history_m, window=args.window,
                       use_pallas=USE_PALLAS[args.use_pallas],
                       fuse_round=args.fuse_round)


def make_engine_factory(cfg, params, args, placement: Placement):
    """EngineKey -> SamplingEngine factory: one shared denoiser + placement,
    per-key step count and solver (the registry caches the instances)."""
    def factory(key: EngineKey):
        return make_engine(params, cfg, resolve_coeffs(args, key.T),
                           resolve_spec(args, key.solver),
                           placement=placement)
    return factory


def mixed_engine_keys(args):
    """The (arch, T, solver) key set the async simulator routes over: the
    CLI configuration itself, a half-depth variant, and an alternate
    solver — ``--mixed-keys N`` keeps the first N."""
    base = EngineKey(args.arch, args.steps_T, args.solver)
    alt_solver = "fp" if args.solver != "fp" else "taa"
    variants = [base,
                EngineKey(args.arch, max(args.steps_T // 2, 4), args.solver),
                EngineKey(args.arch, args.steps_T, alt_solver)]
    # tiny --steps-T makes the half-depth variant collide with base
    return list(dict.fromkeys(variants))[:max(args.mixed_keys, 1)]


def simulate_arrivals(rng, n: int, rate_hz: float):
    """Poisson inter-arrival gaps in seconds (all zero when ``rate_hz`` is 0:
    a closed-loop burst)."""
    if rate_hz <= 0:
        return np.zeros(n)
    return rng.exponential(1.0 / rate_hz, size=n)


def simulated_request(rng, cfg, args, *,
                      allow_overrides: bool = True) -> SampleRequest:
    """One simulated request; with ``--loose-tau-frac`` a fraction of the
    traffic carries per-request early-exit budgets (looser tau and/or a
    Sec 4.1 quality-steps cap) — the mixed-tau population that makes
    iteration-level refill measurable as work reduction.  ``allow_overrides``
    is False for seq-routed requests (no solver iterations to budget)."""
    kw = {}
    if args.loose_tau_frac and rng.random() < args.loose_tau_frac \
            and allow_overrides:
        kw["tau"] = args.loose_tau
        if args.quality_steps:
            kw["quality_steps"] = args.quality_steps
    return SampleRequest(**denoiser(cfg).draw(rng, cfg),
                         seed=int(rng.integers(1 << 30)), **kw)


def serve_async(args, cfg, params, placement: Placement):
    """Drive the ``repro.serving`` stack with a simulated request stream."""
    keys = mixed_engine_keys(args)
    # one per-layer weight tree, shared by every engine the factories make
    params = serving_weights(params, cfg, placement)
    registry = EngineRegistry(make_engine_factory(cfg, params, args,
                                                  placement))
    policy = BatchingPolicy(max_batch=args.batch_size or 8,
                            max_wait_s=args.max_wait_ms / 1e3)
    refiner = None
    # ONE observability bundle spans queue + loop + registry (engines,
    # caches): --trace-out turns on span tracing + convergence curves;
    # metrics mirror either way.  Protocol-neutral by construction — see
    # tools/stepwise_guard.py --phase obs.
    obs = Observability.enabled() if getattr(args, "trace_out", None) \
        else Observability()
    compiles = count_compiles(obs.metrics).counter("jax.compiles")
    if args.refine:
        if not args.chunk_iters:
            raise SystemExit("--refine requires --chunk-iters > 0 "
                             "(refinement splices into live stepwise lanes)")
        refiner = RefinePlanner(RefinePolicy(), metrics=obs.metrics)
    # --cache wires the queue's submit-time hooks: warm-start
    # auto-population from the per-key trajectory cache, plus warm-start
    # shape/dtype validation so a bad init fails its one ticket at submit
    queue = RequestQueue(
        validate=registry.validate_submit if args.cache else None,
        warm_start=registry.warm_start_for if args.cache else None,
        obs=obs)
    if args.chaos_drop:
        if not args.chunk_iters:
            raise SystemExit("--chaos-drop requires --chunk-iters > 0 "
                             "(recovery splices fetched LaneBank state "
                             "back into live stepwise banks)")
        # elastic fault-tolerant variant: the supervisor drops
        # --chaos-drop devices at round --chaos-round, rebuilds every
        # engine on the surviving sub-mesh, and resumes mid-solve — the
        # per-placement factory is how it constructs replacement engines
        def elastic_factory(key: EngineKey, plc: Placement):
            return make_engine(params, cfg, resolve_coeffs(args, key.T),
                               resolve_spec(args, key.solver), placement=plc)
        loop = ResilientServingLoop(
            registry, queue, Batcher(policy, metrics=obs.metrics),
            engine_factory=elastic_factory, placement=placement,
            injector=FaultInjector({args.chaos_round: args.chaos_drop}),
            depth=args.async_depth, chunk_iters=args.chunk_iters,
            refiner=refiner, cache=args.cache, obs=obs)
    else:
        loop = ServingLoop(registry, queue,
                           Batcher(policy, metrics=obs.metrics),
                           depth=args.async_depth,
                           chunk_iters=args.chunk_iters,
                           refiner=refiner, cache=args.cache, obs=obs)
    def compiled(key):
        stats = registry.get(key).stats
        return stats["traces"] + stats["stepwise_traces"]

    warm = {}
    warm_request = SampleRequest(**denoiser(cfg).draw(
        np.random.default_rng(args.seed), cfg))
    for key in keys:  # compile ahead of traffic so p95 is not a jit compile
        engine = registry.get(key)
        registry.warmup(key, slots=loop.batcher.slots_for(engine),
                        request=warm_request, chunk_iters=args.chunk_iters)
        warm[key] = compiled(key)
        print(f"warmed {key.describe()}: {engine.placement.describe()}, "
              f"{warm[key]} program(s) compiled")

    warm_compiles = compiles.series()
    rng = np.random.default_rng(args.seed)
    gaps = simulate_arrivals(rng, args.requests, args.arrival_rate)
    tickets = []
    loop.start()
    try:
        for gap in gaps:
            if gap:
                time.sleep(float(gap))
            key = keys[int(rng.integers(len(keys)))]
            tickets.append(loop.queue.submit(
                simulated_request(rng, cfg, args,
                                  allow_overrides=key.solver != "seq"),
                key))
        results = [t.result(timeout=600) for t in tickets]
    finally:
        loop.stop()

    latencies = np.asarray([t.latency_s for t in tickets])
    span = max(t.completed_time for t in tickets) \
        - min(t.request.arrival_time for t in tickets)
    # programs compiled after warm-up (0 unless traffic forced a retrace)
    retraces = {key: compiled(key) - warm[key] for key in keys}
    stats = []
    for ticket, res in zip(tickets, results):
        stats.append({"key": ticket.key.describe(),
                      "cond": res.request.condition_key,
                      "iters": res.iters, "nfe": res.nfe,
                      "early_stopped": res.early_stopped,
                      "latency_s": ticket.latency_s,
                      "draft_latency_s": ticket.draft_latency_s,
                      "refines": ticket.refines,
                      "warmup_programs": warm[ticket.key],
                      "retraces": retraces[ticket.key]})
        early = " early-exit" if res.early_stopped else ""
        two_tier = (f" draft@{ticket.draft_latency_s:.2f}s"
                    if ticket.refines else "")
        print(f"{ticket.key.describe():>24s} "
              f"cond={res.request.condition_key} "
              f"iters={res.iters:3d} latency={ticket.latency_s:.2f}s"
              f"{early}{two_tier}")
    if args.chunk_iters:
        for key, report in sorted(loop.bank_reports().items()):
            rounds = max(report["blocking_polls"], 1)  # one poll per round
            print(f"{key.describe()}: {report['completed']} served over "
                  f"{report['refills']} refill(s), device iters "
                  f"{report['device_iters']} x {report['slots']} lanes, "
                  f"wasted lane-iters {report['wasted_iter_frac']:.0%}, "
                  f"device NFE {report['device_nfe']}; host protocol "
                  f"{report['host_fetch_bytes'] / rounds:.0f} B/round "
                  f"over {rounds} round(s), {report['gather_launches']} "
                  f"retired-lane gather(s), "
                  f"{report['update_launches'] / rounds:.1f} update "
                  f"launch(es)/round")
    else:
        for key, engine in sorted(registry.engines().items()):
            observed = loop.batcher.observed(key) or {}
            print(f"{key.describe()}: {engine.stats['batches']} dispatch(es), "
                  f"{engine.stats['traces']} compilation(s), "
                  f"slot util {observed.get('slot_utilization', 0):.0%}, "
                  f"mean wall {observed.get('wall_s', 0):.2f}s "
                  f"(pack {observed.get('pack_s', 0) * 1e3:.0f}ms overlapped)")
    n_early = sum(1 for r in results if r.early_stopped)
    print(f"async served {len(tickets)} requests over {len(keys)} key(s) in "
          f"{span:.2f}s => {len(tickets) / max(span, 1e-9):.2f} req/s; "
          f"latency p50 {np.percentile(latencies, 50):.2f}s "
          f"p95 {np.percentile(latencies, 95):.2f}s; "
          f"mean NFE/request {np.mean([r.nfe for r in results]):.0f}; "
          f"{n_early} early-exit(s); {sum(retraces.values())} program(s) "
          f"compiled after warm-up; loop stats {loop.stats}")
    late = {fun.partition("=")[2]: n - warm_compiles.get(fun, 0)
            for fun, n in compiles.series().items()
            if n > warm_compiles.get(fun, 0)}
    print(f"jax compiles after warm-up, by function: {late or 'none'}")
    if args.chaos_drop:
        res = loop.resilience
        unresolved = [t for t in tickets if not t.done()]
        assert not unresolved, \
            f"{len(unresolved)} ticket(s) unresolved after chaos drain"
        survivors = len(loop._survivors())
        print(f"chaos: lost {res['device_losses']} device(s) at round "
              f"{args.chaos_round}, {res['rebuilds']} rebuild(s) onto "
              f"{survivors} survivor(s) in {res['rebuild_wall_s']:.2f}s; "
              f"{res['recovered_lanes']} lane(s) recovered mid-solve "
              f"(+{res['recovery_nfe']} recovery NFE), "
              f"{res['resubmitted_lanes']} resubmitted, "
              f"{res['draft_fallbacks']} draft fallback(s), "
              f"{res['retries']} in-place retries — "
              f"{len(tickets)}/{len(tickets)} tickets resolved")
    if args.refine:
        two_tier = [t for t in tickets if t.refines]
        unresolved = [t for t in tickets
                      if not (t.done() and t.draft_done())]
        assert not unresolved, \
            f"{len(unresolved)} ticket(s) missing a resolved stage"
        draft_lat = np.asarray([t.draft_latency_s for t in tickets])
        print(f"refine tier: {len(two_tier)} two-tier ticket(s), every "
              f"stage resolved; draft latency p50 "
              f"{np.percentile(draft_lat, 50):.2f}s p95 "
              f"{np.percentile(draft_lat, 95):.2f}s; "
              f"{loop.stats['preemptions']} preemption(s)")
    if args.cache:
        for key in keys:
            c = registry.cache(key).stats()
            total = max(c["hits"] + c["misses"], 1)
            print(f"{key.describe()} cache: {c['hits']}/{total} hits "
                  f"({c['hits'] / total:.0%}), {c['evictions']} "
                  f"eviction(s), {c['entries']} entries "
                  f"({c['bytes']} B)")
    if getattr(args, "trace_out", None):
        path = obs.tracer.export(args.trace_out)
        snap = path.with_suffix(".metrics.json")
        snap.write_text(json.dumps(json_safe(obs.metrics.snapshot())))
        print(f"metrics: {len(obs.metrics.names())} instrument(s) -> {snap}")
        curves = sum(1 for t in tickets if t.residual_curve)
        wait = obs.metrics.histogram("loop.queue_wait_s").merged() \
            or {"p50": 0.0, "p95": 0.0}
        print(f"trace: {len(obs.tracer.events())} event(s) -> {path} "
              f"({obs.tracer.dropped} dropped); residual curves on "
              f"{curves}/{len(tickets)} ticket(s); queue wait "
              f"p50 {wait['p50'] * 1e3:.1f}ms p95 {wait['p95'] * 1e3:.1f}ms")
    return jnp.stack([res.x0 for res in results]), stats


def report_dispatches(engine: SamplingEngine, *, out=print):
    """Per-dispatch device-utilization report (one line per dispatch)."""
    for i, d in enumerate(engine.last_dispatches):
        out(f"dispatch {i}: {d['requests']}/{d['slots']} request slots "
            f"({d['slot_utilization']:.0%}) on {d['devices']} device(s) "
            f"[data={d['data_shards']} x model={d['model_shards']}"
            f" x time={d['time_shards']}], "
            f"wall {d['wall_s']:.2f}s")


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's argument parser (``main`` and the chip smoke
    script build their ``args`` from it)."""
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="dit-xl")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=0,
                   help="requests per engine dispatch (0 = all in one "
                        "batch; with --serve-async, 0 = the default "
                        "8-slot continuous batches)")
    p.add_argument("--steps-T", type=int, default=50)
    p.add_argument("--solver", default="taa", choices=["fp", "aa", "taa", "seq"])
    p.add_argument("--sampler", default="ddim", choices=["ddim", "ddpm"])
    p.add_argument("--order-k", type=int, default=8)
    p.add_argument("--history-m", type=int, default=3)
    p.add_argument("--window", type=int, default=0)
    p.add_argument("--use-pallas", default="auto",
                   choices=sorted(USE_PALLAS),
                   help="route the solver's TAA Gram/apply passes through "
                        "the repro.kernels.ops Pallas kernels (auto = "
                        "Pallas on TPU, bitwise-identical jnp refs "
                        "elsewhere)")
    p.add_argument("--fuse-round", action="store_true",
                   help="fuse each Anderson round (Gram + gamma solve + "
                        "apply) into ONE kernels.ops.taa_round dispatch: a "
                        "single pallas_call on the Pallas path, the "
                        "bitwise-identical staged jnp composition "
                        "elsewhere — 3x fewer update launches/iteration "
                        "(see update_launches in the bank reports)")
    p.add_argument("--backend-tune", action="store_true",
                   help="merge the XLA:GPU serving flags (latency-hiding "
                        "scheduler, Triton gemm/softmax fusion, async "
                        "collectives) into XLA_FLAGS before jax "
                        "initializes; no-op on CPU/TPU hosts")
    p.add_argument("--mesh", default="none", choices=["none"] + mesh_names(),
                   help="registered mesh to place the engine on "
                        "(none = single-device host placement)")
    p.add_argument("--data-parallel", type=int, default=0,
                   help="override the mesh's `data` axis size "
                        "(request-axis shards; 0 = registry default)")
    p.add_argument("--model-parallel", type=int, default=0,
                   help="override the mesh's `model` axis size "
                        "(denoiser TP shards; 0 = registry default)")
    p.add_argument("--time-parallel", type=int, default=0,
                   help="override a *-time mesh's `time` axis size (solve-"
                        "window shards within one request — bitwise-"
                        "identical to the unsharded window; 0 = registry "
                        "default)")
    p.add_argument("--donate", action="store_true",
                   help="donate packed input buffers to the compiled "
                        "program (pods; CPU ignores donation)")
    p.add_argument("--serve-async", action="store_true",
                   help="serve a simulated request stream through the "
                        "repro.serving continuous-batching layer instead "
                        "of one blocking run_batch call")
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="Poisson arrival rate in requests/s for "
                        "--serve-async (0 = closed-loop burst)")
    p.add_argument("--max-wait-ms", type=float, default=50.0,
                   help="batching deadline: max time a request may wait "
                        "for its dispatch to fill (--serve-async)")
    p.add_argument("--async-depth", type=int, default=2,
                   help="dispatches kept in flight by the serving loop "
                        "(2 = double-buffered pack/compute overlap)")
    p.add_argument("--mixed-keys", type=int, default=2,
                   help="number of distinct (T, solver) EngineKeys the "
                        "--serve-async simulator routes over")
    p.add_argument("--chunk-iters", type=int, default=0,
                   help="solver iterations per serving chunk: > 0 switches "
                        "--serve-async to iteration-level continuous "
                        "batching (lanes retire the moment their own "
                        "request converges or early-exits, freed lanes "
                        "refill mid-solve); 0 = whole-batch dispatches")
    p.add_argument("--loose-tau-frac", type=float, default=0.0,
                   help="fraction of simulated requests carrying a looser "
                        "per-request tau (mixed-tau traffic; the "
                        "early-exit serving mode's target population)")
    p.add_argument("--loose-tau", type=float, default=1e-2,
                   help="the looser per-request stopping tolerance for "
                        "--loose-tau-frac traffic")
    p.add_argument("--quality-steps", type=int, default=0,
                   help="per-request quality-steps budget (Sec 4.1 early "
                        "exit) attached to --loose-tau-frac traffic "
                        "(0 = tolerance-only)")
    p.add_argument("--refine", action="store_true",
                   help="two-tier draft-and-refine serving (requires "
                        "--chunk-iters): early-exited drafts resolve their "
                        "ticket's draft stage immediately and a "
                        "warm-started preemptible continuation completes "
                        "the same ticket at full tolerance")
    p.add_argument("--cache", action="store_true",
                   help="per-key Sec 4.2 warm-start trajectory cache: "
                        "record converged results, auto-populate "
                        "SampleRequest.init at submit time (with "
                        "submit-time warm-start validation)")
    p.add_argument("--chaos-drop", type=int, default=0,
                   help="chaos test (requires --chunk-iters): drop this "
                        "many devices from the serving mesh mid-drain and "
                        "let the elastic supervisor rebuild the engines on "
                        "the survivors — every ticket still resolves, "
                        "resumed solves are bitwise-identical "
                        "(0 = no fault injection)")
    p.add_argument("--chaos-round", type=int, default=3,
                   help="supervision round at which --chaos-drop fires")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write a Chrome-trace JSON (Perfetto/about:tracing "
                        "loadable) of the --serve-async drain: per-ticket "
                        "submit->resolve span chains, engine pack/dispatch/"
                        "stepwise spans, and per-lane residual-vs-round "
                        "convergence curves (see tools/obs_report.py); "
                        "the metrics registry's snapshot goes beside it "
                        "(PATH with suffix .metrics.json)")
    p.add_argument("--ckpt", default=None, help="trained DiT checkpoint dir")
    p.add_argument("--seed", type=int, default=0)
    return p


def make_params(args):
    """(cfg, params) for ``--arch``/``--smoke``: weights drawn from
    ``--seed``, or restored from ``--ckpt`` when given."""
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    params = pdefs.init_params(denoiser(cfg).defs(cfg),
                               jax.random.PRNGKey(args.seed))
    if args.ckpt:
        from pathlib import Path
        from repro.ckpt import CheckpointManager
        mgr = CheckpointManager(Path(args.ckpt))
        _, tree = mgr.restore({"step": 0, "params": params})
        if tree is not None:
            params = tree["params"]
            print(f"restored checkpoint step {tree['step']}")
    return cfg, params


def main(argv=None):
    args = build_parser().parse_args(argv)

    placement = make_placement(args.mesh, data_parallel=args.data_parallel,
                               model_parallel=args.model_parallel,
                               time_parallel=args.time_parallel,
                               donate=args.donate)
    print(f"placement: {placement.describe()}")
    cfg, params = make_params(args)

    if args.serve_async:
        return serve_async(args, cfg, params, placement)

    coeffs = resolve_coeffs(args, args.steps_T)
    engine = make_engine(params, cfg, coeffs,
                         resolve_spec(args, args.solver), placement=placement)

    metrics = count_compiles(engine.obs.metrics)
    outs, stats, straggler = serve_batch(
        engine, make_requests(args, cfg), batch_size=args.batch_size or None)
    for st in stats:
        # wall_s is the wall time of the DISPATCH the request rode in (its
        # latency), not exclusive per-request compute — batch members share it
        print(f"cond={st['cond']} iters={st['iters']:3d} "
              f"nfe={st['nfe']:5d} batch_wall={st['wall_s']:.2f}s")
    report_dispatches(engine)
    seq_steps = coeffs.T
    mean_iters = np.mean([s["iters"] for s in stats])
    print(f"mean parallel steps {mean_iters:.1f} vs sequential {seq_steps} "
          f"=> {seq_steps/mean_iters:.1f}x step reduction; "
          f"p50 deadline {straggler.deadline()}")
    print(f"batched throughput {engine.throughput():.2f} req/s "
          f"({engine.stats['requests']} requests / "
          f"{engine.stats['batches']} batches, "
          f"{engine.stats['traces']} compilation(s))")
    print("jax: " + ", ".join(f"{name} {n:.0f}" for name, n in
                              compile_totals(metrics).items()))
    return outs, stats


if __name__ == "__main__":
    configure_compile_cache()
    main()
