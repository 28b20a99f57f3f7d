"""The serving weight layout: ``serve.make_engine`` hands its engine the
denoiser's block weights as a tuple of per-layer trees, made once at load,
and the models' layer loops read either that or the stacked tree (training,
the dry-run, the benchmark's reference) with bitwise the same result."""
import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core import ddim_coeffs
from repro.diffusion import dit, pixart
from repro.launch import serve
from repro.models import pdefs
from repro.sampling import SampleRequest, SamplingEngine, get_sampler


def _woken(cfg, seed=0):
    """Seeded weights with every leaf non-zero (both models zero-initialise
    their output projections, which would make eps vanish)."""
    params = pdefs.init_params(serve.denoiser(cfg).defs(cfg),
                               jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        w + 0.05 * jax.random.normal(k, w.shape) for w, k in
        zip(leaves, keys)])


def _dit(params, cfg, x, t, rng):
    y = jnp.asarray(rng.integers(0, cfg.num_classes, x.shape[0]), jnp.int32)
    return dit.dit_apply(params, cfg, x, t, y)


def _pixart(params, cfg, x, t, rng):
    cond = {"caption": jnp.asarray(rng.normal(
                size=(x.shape[0], cfg.caption_len, cfg.caption_dim)),
                jnp.float32),
            "tokens": jnp.asarray(rng.integers(1, cfg.caption_len + 1,
                                               x.shape[0]), jnp.int32)}
    return pixart.pixart_apply(params, cfg, x, t, cond)


@pytest.mark.parametrize("arch,apply", [("dit-xl", _dit),
                                        ("pixart-alpha", _pixart)],
                         ids=["dit", "pixart"])
def test_per_layer_blocks_give_bitwise_the_same_eps(arch, apply):
    """At full depth and smoke widths, the model on per-layer blocks (the
    serving layout) gives bitwise the eps it gives on the stacked tree."""
    cfg = dataclasses.replace(ARCHS[arch].reduced(),
                              num_layers=ARCHS[arch].num_layers)
    params = _woken(cfg)
    served = serve.serving_weights(params, cfg)
    assert isinstance(served["blocks"], tuple)
    assert len(served["blocks"]) == cfg.num_layers
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(3, cfg.num_tokens, cfg.latent_dim)),
                    jnp.float32)
    t = jnp.asarray([10.0, 500.0, 990.0])
    fn = jax.jit(lambda p: apply(p, cfg, x, t, np.random.default_rng(3)))
    stacked, per_layer = np.asarray(fn(params)), np.asarray(fn(served))
    assert np.abs(stacked).max() > 1e-3          # the weights are awake
    np.testing.assert_array_equal(per_layer, stacked)


@pytest.mark.parametrize("arch", ["dit-xl", "pixart-alpha"])
def test_make_engine_serves_per_layer_blocks(arch):
    """The engine's blocks are one tree per layer, each with the per-layer
    defs' shapes; the caller's stacked tree is left as it was."""
    cfg = ARCHS[arch].reduced()
    params = _woken(cfg)
    engine = serve.make_engine(params, cfg, ddim_coeffs(4),
                               get_sampler("seq"))
    defs = serve.denoiser(cfg).defs(cfg)["blocks"]
    layer_defs = pdefs.unstack(defs)
    blocks = engine.params["blocks"]
    assert isinstance(blocks, tuple) and len(blocks) == cfg.num_layers
    for got, want in zip(blocks, layer_defs):
        assert jax.tree.map(lambda a: a.shape, got) == jax.tree.map(
            lambda d: d.shape, want, is_leaf=pdefs.is_def)
    assert jax.tree.map(lambda d: d.axes, layer_defs[0],
                        is_leaf=pdefs.is_def) == jax.tree.map(
        lambda d: d.axes[1:], defs, is_leaf=pdefs.is_def)
    for i in (0, cfg.num_layers - 1):
        jax.tree.map(np.testing.assert_array_equal, blocks[i],
                     jax.tree.map(lambda w: w[i], params["blocks"]))
    assert not isinstance(params["blocks"], tuple)


def test_engine_given_a_stacked_tree_runs_unchanged():
    """A ``SamplingEngine`` built directly on the stacked tree (the
    non-serving callers) slices in its step and serves bitwise what
    ``make_engine``'s per-layer engine serves."""
    cfg = ARCHS["dit-xl"].reduced()
    params = _woken(cfg)
    coeffs = ddim_coeffs(4)
    direct = SamplingEngine(serve.make_eps_apply(cfg), params, coeffs,
                            get_sampler("taa"),
                            sample_shape=(cfg.num_tokens, cfg.latent_dim))
    served = serve.make_engine(params, cfg, coeffs, get_sampler("taa"))
    assert isinstance(direct.params["blocks"], dict)
    reqs = [SampleRequest(label=i, seed=20 + i) for i in range(2)]
    for a, b in zip(direct.run_batch(reqs), served.run_batch(reqs)):
        np.testing.assert_array_equal(np.asarray(a.trajectory),
                                      np.asarray(b.trajectory))
        assert a.iters == b.iters


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.registry import ARCHS
from repro.core import ddim_coeffs
from repro.launch import serve
from repro.launch.mesh import make_mesh
from repro.models import pdefs
from repro.sampling import Placement, get_sampler

out = {}
for arch in ("dit-xl", "pixart-alpha"):
    cfg = ARCHS[arch].reduced()
    defs = serve.denoiser(cfg).defs(cfg)
    params = pdefs.init_params(defs, jax.random.PRNGKey(0))
    for name, mesh in [("model2", make_mesh("debug", data_parallel=4,
                                            model_parallel=2)),
                       ("time2", make_mesh("debug-time"))]:
        engine = serve.make_engine(params, cfg, ddim_coeffs(4),
                                   get_sampler("seq"),
                                   placement=Placement.for_mesh(mesh))
        specs = pdefs.resolve_specs(defs["blocks"], mesh)
        same, sharded = True, 0
        for layer in engine.params["blocks"]:
            for w, spec in zip(jax.tree.leaves(layer), jax.tree.leaves(
                    specs, is_leaf=lambda s: isinstance(s, P))):
                want = NamedSharding(mesh, P(*spec[1:]))
                same &= w.sharding.is_equivalent_to(want, w.ndim)
                sharded += any(a is not None for a in spec[1:])
        # the abstract tree a described-chip compile lowers with
        abstract = serve.serving_weights(
            pdefs.abstract_params(defs, mesh, jax.numpy.float32), cfg)
        for layer, alayer in zip(engine.params["blocks"],
                                 abstract["blocks"], strict=True):
            for w, a in zip(jax.tree.leaves(layer), jax.tree.leaves(alayer)):
                same &= a.shape == w.shape
                same &= a.sharding.is_equivalent_to(w.sharding, w.ndim)
        out[f"{arch}/{name}"] = {"same": bool(same), "sharded": sharded,
                                 "layers": len(engine.params["blocks"])}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.mesh
def test_per_layer_blocks_keep_the_slices_shardings():
    """On a host-device mesh (``model=2``; ``data=2, time=2, model=2``)
    every per-layer leaf the engine holds has the PartitionSpec its
    stacked leaf resolves to, less the ``layers`` dim, and the heads and
    MLP widths are split over ``model``; ``serving_weights`` gives
    abstract leaves the same shapes and shardings."""
    proc = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parent.parent, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[7:])
    assert len(out) == 4
    for name, rec in out.items():
        assert rec["same"], name
        assert rec["sharded"] > 0, name
        assert rec["layers"] == ARCHS[name.split("/")[0]].reduced().num_layers
