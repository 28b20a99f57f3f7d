#!/usr/bin/env python3
"""Chip smoke: DiT-XL/2 at 256x256 served through ParaTAA on a TPU.

Drives the serving entry points of ``repro.launch.serve`` (the same
``make_placement`` / ``make_engine`` / ``serve_batch`` / ``serve_async``
that ``python -m repro.launch.serve`` runs) at the published DiT-XL/2
shape -- 28 layers, d=1152, 16 heads, 256 latent tokens x 16, 1000
classes -- with weights drawn from ``--seed``, and checks what comes out:

  (a) model set-up: full-width f32 params; the adaLN-zero leaves get seeded
      small normal values so that eps depends on x
  (b) sync path: 4 requests, T=25 DDIM, the default ``taa`` spec; the
      compiled program contains the Pallas kernels (``tpu_custom_call``)
  (c) sequential reference: the same requests with ``seq``
  (d) fused round: (b) with ``--fuse-round``
  (e) async stepwise serving: ``--serve-async --chunk-iters 2``

``--four-chips`` runs only the mesh path of a 4-chip host: the same
requests on one device, on a data=4 mesh and on a time=4 mesh.

    python chip_smoke.py
    python chip_smoke.py --four-chips

Findings go to stdout, one line each; the last line is one JSON object
``{"ok": true, "device": {...}}``.  Without a TPU it exits non-zero before
any phase.  The times printed are smoke timings, not benchmarks.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.obs import (MetricsRegistry, compile_totals,  # noqa: E402
                       count_compiles)
from repro.obs.compiles import COUNTERS  # noqa: E402
from repro.sampling import WarmStart  # noqa: E402

T_ONE_CHIP = 25
# the time axis shards the solve window only when it divides by the shards
T_FOUR_CHIPS = 24
REQUESTS = 4

# Seeded stds for the zero-initialised DiT leaves.  Calibrated on the CPU
# with the full 28-layer model: eps then has RMS ~0.52 and an RMS
# sensitivity |d eps| / |d x| of ~0.7 (t = 39, 499, 999).  Larger adaLN
# values let the random attention blocks dominate, and eps turns chaotic
# in x (sensitivity ~6 at 3e-4, ~60 at 1e-2).
ADA_STD = 1e-4
OUT_GAIN = 0.5                  # out_proj std = OUT_GAIN / sqrt(d_model)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def since(metrics: MetricsRegistry, mark: dict) -> str:
    """JAX's compile counters since ``mark`` (a ``compile_totals``)."""
    now = compile_totals(metrics)
    return (f"{now['jax.compiles'] - mark['jax.compiles']:.0f} compile(s), "
            f"{now['jax.cache_hits'] - mark['jax.cache_hits']:.0f} "
            f"persistent-cache hit(s)")


def wake_adaln_zero(params, seed: int):
    """Give the leaves DiT initialises to zero (``blocks.ada``,
    ``final_ada``, ``out_proj``) seeded normal values, so that a random
    model's eps depends on x and the Anderson rounds do real work."""
    k_ada, k_fin, k_out = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    d = params["out_proj"].shape[0]
    params = dict(params, blocks=dict(params["blocks"]))
    params["blocks"]["ada"] = ADA_STD * jax.random.normal(
        k_ada, params["blocks"]["ada"].shape)
    params["final_ada"] = ADA_STD * jax.random.normal(
        k_fin, params["final_ada"].shape)
    params["out_proj"] = OUT_GAIN / np.sqrt(d) * jax.random.normal(
        k_out, params["out_proj"].shape)
    return params


def stop_rule_scale(engine) -> float:
    """The x0 displacement ParaTAA's stopping rule admits (L2).

    A solve stops once every row j has a first-order residual
    |x_j - a_{j+1} x_{j+1} - b_{j+1} eps_{j+1} - c_j xi_j| of at most
    tau * sqrt(g2[j+1] * D) (``parataa.init_state``); the sequential
    sampler's rows have none.  Carried to x0 by the recursion's own gains
    a_1 ... a_j, residuals at those thresholds move x0 by
    sum_j tau * sqrt(g2[j+1] * D) * prod_{s<=j} a_s.  That is the scale of
    disagreement the rule tolerates; the denoiser's own feedback and the
    chip's bf16 matmul passes are not in it, so ``(c)`` also checks that
    the sequential trajectory itself passes the rule on the chip.
    """
    c = engine.coeffs
    D = int(np.prod(engine.sample_shape))
    scale, gain = 0.0, 1.0              # gain = prod_{s<=j} a_s
    for j in range(c.T):
        scale += gain * engine.spec.tau * float(np.sqrt(c.g2[j + 1] * D))
        gain *= float(c.a[j + 1])
    return scale


def l2(a, b) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64).ravel()
                                - np.asarray(b, np.float64).ravel()))


def parse(argv):
    return serve.build_parser().parse_args(argv)


def sync_run(engine, requests, tag: str, metrics: MetricsRegistry):
    """serve_batch once cold and once warm; returns x0."""
    T = engine.coeffs.T
    mark = compile_totals(metrics)
    t0 = time.perf_counter()
    x0, stats, _ = serve.serve_batch(engine, requests,
                                     batch_size=len(requests))
    cold = time.perf_counter() - t0
    x0 = np.asarray(x0)
    again, _, _ = serve.serve_batch(engine, requests,
                                    batch_size=len(requests))
    warm = engine.last_batch_walls[-1]
    iters = [s["iters"] for s in stats]
    print(f"{tag}: {engine.spec.name} T={T} sample_shape="
          f"{engine.sample_shape} {engine.placement.describe()}; iters "
          f"{iters}, nfe {[s['nfe'] for s in stats]}; cold dispatch "
          f"{cold:.1f}s ({since(metrics, mark)}); warm dispatch {warm:.3f}s "
          f"(smoke timing, not a benchmark)")
    check(np.all(np.isfinite(x0)), f"{tag}: non-finite x0")
    check(np.array_equal(np.asarray(again), x0),
          f"{tag}: a second dispatch of the same requests changed x0")
    if not engine.spec.is_sequential:
        check(all(1 < it <= T for it in iters),
              f"{tag}: iterations {iters} outside (1, T={T}]")
    return x0


def agree(tag: str, x0, ref, tol: float) -> None:
    diffs = [l2(a, b) for a, b in zip(x0, ref)]
    rel = [d / max(float(np.linalg.norm(r)), 1e-30)
           for d, r in zip(diffs, ref)]
    print(f"{tag}: |dx0| {['%.3g' % d for d in diffs]} (relative to |x0| "
          f"{['%.2g' % r for r in rel]}) within {tol:.4g}")
    check(max(diffs) <= tol, f"{tag}: |dx0| {diffs} exceeds {tol}")


def one_chip(base, metrics: MetricsRegistry):
    # (a) model set-up
    args = parse(base + ["--requests", str(REQUESTS), "--batch-size",
                                str(REQUESTS), "--steps-T", str(T_ONE_CHIP)])
    cfg, params = serve.make_params(args)
    params = wake_adaln_zero(params, args.seed)
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    print(f"(a) {cfg.name}: {cfg.num_layers} layers, d={cfg.d_model}, "
          f"{cfg.num_heads} heads, {cfg.num_tokens} tokens x "
          f"{cfg.latent_dim}, {cfg.num_classes} classes; params "
          f"{nbytes / 1e9:.3f} GB f32 from seed {args.seed}")
    placement = serve.make_placement(args.mesh)
    coeffs = serve.resolve_coeffs(args, args.steps_T)
    requests = serve.make_requests(args, cfg)

    # (b) sync path, default taa spec
    engine = serve.make_engine(params, cfg, coeffs,
                               serve.resolve_spec(args, args.solver),
                               placement=placement)
    x0_taa = sync_run(engine, requests, "(b) sync", metrics)
    mark = compile_totals(metrics)
    text = engine.lower_batch(len(requests)).compile().as_text()
    kernels = text.count("tpu_custom_call")
    print(f"(b) lower_batch program: {kernels} tpu_custom_call site(s) "
          f"({since(metrics, mark)})")
    check(kernels > 0, "(b) no Pallas kernel in the compiled program")

    # (c) sequential reference
    seq = serve.make_engine(params, cfg, coeffs,
                            serve.resolve_spec(args, "seq"),
                            placement=placement)
    x0_seq = sync_run(seq, requests, "(c) seq", metrics)
    tol = stop_rule_scale(engine)
    agree("(c) taa vs seq", x0_taa, x0_seq, tol)
    # The sequential trajectories, handed to the taa engine as fully solved
    # warm starts (t_init=0), must pass its stopping rule in the one
    # verifying iteration: the rule as evaluated on the chip (kernels,
    # batch geometry, bf16 matmul passes) accepts the exact solution.
    verify = [dataclasses.replace(req, init=WarmStart(res.trajectory,
                                                      t_init=0))
              for req, res in zip(requests, seq.run_batch(requests))]
    x0_verify, vstats, _ = serve.serve_batch(engine, verify,
                                             batch_size=len(verify))
    viters = [s["iters"] for s in vstats]
    print(f"(c) seq trajectories as taa warm starts: iters {viters}")
    check(viters == [1] * len(verify),
          "(c) a sequential trajectory fails the taa stopping rule")
    check(np.array_equal(np.asarray(x0_verify), x0_seq),
          "(c) verifying a solved trajectory moved it")

    # (d) fused Anderson round
    fargs = parse(base + ["--fuse-round"])
    fused = serve.make_engine(params, cfg, coeffs,
                              serve.resolve_spec(fargs, fargs.solver),
                              placement=placement)
    x0_fused = sync_run(fused, requests, "(d) fused", metrics)
    agree("(d) fused vs seq", x0_fused, x0_seq, tol)
    # each is within tol of x0_seq, so within 2 * tol of the other
    agree("(d) fused vs staged", x0_fused, x0_taa, 2 * tol)

    # (e) async stepwise serving over a closed-loop burst
    aargs = parse(base + [
        "--serve-async", "--chunk-iters", "2", "--requests", str(REQUESTS),
        "--batch-size", str(REQUESTS), "--mixed-keys", "1", "--steps-T",
        str(T_ONE_CHIP), "--arrival-rate", "0"])
    mark = compile_totals(metrics)
    x0_async, stats = serve.serve_async(aargs, cfg, params, placement)
    x0_async = np.asarray(x0_async)
    iters = [s["iters"] for s in stats]
    print(f"(e) async: {len(stats)}/{REQUESTS} ticket(s) resolved, iters "
          f"{iters}; warm-up compiled {stats[0]['warmup_programs']} "
          f"program(s), {stats[0]['retraces']} after it "
          f"({since(metrics, mark)})")
    check(len(stats) == REQUESTS, "(e) a ticket did not resolve")
    check(np.all(np.isfinite(x0_async)), "(e) non-finite x0")
    check(all(1 < it <= T_ONE_CHIP for it in iters),
          f"(e) iterations {iters} outside (1, T]")
    check(all(s["retraces"] == 0 for s in stats),
          "(e) serving retraced after warm-up")


def four_chips(base, metrics: MetricsRegistry):
    common = ["--requests", str(REQUESTS), "--batch-size", str(REQUESTS),
              "--steps-T", str(T_FOUR_CHIPS)]
    args = parse(base + common)
    cfg, params = serve.make_params(args)
    params = wake_adaln_zero(params, args.seed)
    coeffs = serve.resolve_coeffs(args, args.steps_T)
    spec = serve.resolve_spec(args, args.solver)
    requests = serve.make_requests(args, cfg)
    meshes = [("host", [], 1),
              ("data=4", ["--mesh", "single-host", "--data-parallel", "4",
                          "--model-parallel", "1"], 4),
              ("time=4", ["--mesh", "single-host-time", "--data-parallel",
                          "1", "--time-parallel", "4", "--model-parallel",
                          "1"], 4)]
    ref = None
    for name, mesh_argv, ndev in meshes:
        a = parse(base + common + mesh_argv)
        placement = serve.make_placement(
            a.mesh, data_parallel=a.data_parallel,
            model_parallel=a.model_parallel, time_parallel=a.time_parallel)
        engine = serve.make_engine(params, cfg, coeffs, spec,
                                   placement=placement)
        x0 = sync_run(engine, requests, f"[{name}]", metrics)
        pending = engine.dispatch(requests, slots=len(requests))
        on = {"params": {len(leaf.sharding.device_set)
                         for leaf in jax.tree.leaves(engine.params)},
              "inputs": {len(arr.sharding.device_set)
                         for arr in engine.pack(requests)},
              "outputs": {len(pending.trajs.sharding.device_set)}}
        engine.collect(pending)
        util = engine.last_dispatches[-1]["axis_utilization"]
        print(f"[{name}] device_set sizes {on}; axis_utilization {util}")
        check(all(sizes == {ndev} for sizes in on.values()),
              f"[{name}] arrays not on {ndev} device(s): {on}")
        if ref is None:
            ref = x0
            continue
        if name.startswith("time"):
            check(util["time"] == 1.0, "[time=4] window did not shard")
        # each solve is within stop_rule_scale of the sequential solution
        agree(f"[{name}] vs host", x0, ref, 2 * stop_rule_scale(engine))


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 4-chip mesh path")
    p.add_argument("--seed", type=int, default=0)
    opts = p.parse_args(argv)

    cache = configure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform} ({dev.device_kind})")
    want = 4 if opts.four_chips else 1
    check(len(devices) >= want, f"needs {want} chip(s), found {len(devices)}")
    print(f"device: {dev.device_kind} x {len(devices)}; compile cache "
          f"{cache}")
    metrics = count_compiles(MetricsRegistry())
    base = ["--arch", "dit-xl", "--seed", str(opts.seed)]
    (four_chips if opts.four_chips else one_chip)(base, metrics)
    print(f"total {since(metrics, dict.fromkeys(COUNTERS, 0))}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
