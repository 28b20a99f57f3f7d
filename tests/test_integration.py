"""Integration tests: trained-DiT sampler equivalence (the paper's central
claim end-to-end), the train/serve drivers, and checkpoint-restart."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core import ddim_coeffs, ddpm_coeffs
from repro.diffusion import dit as dit_mod
from repro.launch import steps as S
from repro.data.pipeline import LatentPipeline
from repro.optim import adamw_init
from repro.sampling import draw_noises, get_sampler, run, sequential_sample


@pytest.fixture(scope="module")
def trained_dit():
    """A briefly-trained tiny DiT (real denoiser dynamics for the solver)."""
    cfg = ARCHS["dit-xl"].reduced()
    key = jax.random.PRNGKey(0)
    params = dit_mod.dit_init(cfg, key)
    opt = adamw_init(params)
    step_fn = jax.jit(S.make_train_step(cfg), donate_argnums=(0, 1))
    pipe = LatentPipeline(num_tokens=16, latent_dim=cfg.latent_dim,
                          num_classes=cfg.num_classes)
    losses = []
    for i in range(60):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(i, 16).items()}
        params, opt, m = step_fn(params, opt, batch, jnp.asarray(i, jnp.int32))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    return cfg, params


@pytest.mark.parametrize("mk", [ddim_coeffs, ddpm_coeffs])
def test_parataa_reproduces_sequential_trained_dit(trained_dit, mk):
    """Remark 5.3: parallel sampling produces (almost) identical samples."""
    cfg, params = trained_dit
    coeffs = mk(25)
    xi = draw_noises(jax.random.PRNGKey(5), coeffs, (16, cfg.latent_dim))

    def eps_fn(xw, taus):
        y = jnp.full((xw.shape[0],), 3, jnp.int32)
        return dit_mod.dit_apply(params, cfg, xw, taus, y)

    x_seq = sequential_sample(eps_fn, coeffs, xi)
    res = run(get_sampler("taa", s_max=100), eps_fn, coeffs, xi)
    assert bool(res.converged)
    assert int(res.iters) < coeffs.T  # fewer parallel steps than sequential
    err = float(jnp.max(jnp.abs(res.x0 - x_seq)))
    scale = float(jnp.max(jnp.abs(x_seq))) + 1e-9
    assert err / scale < 2e-2, (err, scale)


def test_train_driver_smoke(tmp_path):
    from repro.launch.train import main
    losses = main(["--arch", "dit-xl", "--smoke", "--steps", "12",
                   "--batch", "8", "--ckpt-dir", str(tmp_path / "ck"),
                   "--ckpt-every", "5", "--log-every", "100"])
    assert len(losses) == 12
    assert not np.isnan(losses[-1])


def test_train_driver_restart_continues(tmp_path):
    from repro.launch.train import main
    ck = str(tmp_path / "ck")
    main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "6", "--batch", "2",
          "--seq", "16", "--ckpt-dir", ck, "--ckpt-every", "3",
          "--log-every", "100"])
    # restart with more steps: must resume from the checkpoint, not step 0
    losses = main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "8",
                   "--batch", "2", "--seq", "16", "--ckpt-dir", ck,
                   "--ckpt-every", "3", "--log-every", "100"])
    assert len(losses) == 2  # only steps 6, 7 executed


def test_serve_driver_smoke():
    from repro.launch.serve import main
    outs, stats = main(["--smoke", "--requests", "4", "--steps-T", "20",
                        "--solver", "taa", "--batch-size", "2"])
    assert outs.shape[0] == 4
    assert all(s["iters"] < 20 for s in stats)


@pytest.mark.parametrize("smoke", [False, True])
def test_serve_engines_take_the_configured_latent_shape(smoke):
    """Sync (`make_engine`) and async (the registry's factory) serving build
    the config's own latent shape: 256 tokens x 16 for DiT-XL/2 at 256x256,
    the reduced config's under --smoke."""
    from repro.launch import serve
    from repro.sampling import Placement
    from repro.serving import EngineKey
    argv = ["--steps-T", "4"] + (["--smoke"] if smoke else [])
    args = serve.build_parser().parse_args(argv)
    cfg = ARCHS["dit-xl"].reduced() if smoke else ARCHS["dit-xl"]
    want = (16, 16) if smoke else (256, 16)
    sync = serve.make_engine(None, cfg, ddim_coeffs(4), get_sampler("taa"))
    factory = serve.make_engine_factory(cfg, None, args, Placement.host())
    assert sync.sample_shape == want
    assert factory(EngineKey(args.arch, 4, "taa")).sample_shape == want


def test_seeded_weights_do_not_depend_on_the_process():
    """`--seed` alone fixes the weights: each leaf's key is folded from a
    stable digest of its path, not from Python's per-process salted hash."""
    script = ("import jax, numpy as np\n"
              "from repro.configs.registry import get_arch\n"
              "from repro.diffusion import dit\n"
              "p = dit.dit_init(get_arch('dit-xl').reduced(), "
              "jax.random.PRNGKey(0))\n"
              "print(repr(float(sum(np.abs(np.asarray(l, np.float64)).sum()"
              " for l in jax.tree.leaves(p)))))\n")
    outs = set()
    for hash_seed in ("1", "2"):
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": hash_seed},
            cwd=Path(__file__).resolve().parent.parent, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.add(proc.stdout.strip().splitlines()[-1])
    assert len(outs) == 1, outs


def test_serve_matches_sequential_solver():
    from repro.launch.serve import main
    outs_p, _ = main(["--smoke", "--requests", "1", "--steps-T", "15",
                      "--solver", "taa", "--seed", "3"])
    outs_s, _ = main(["--smoke", "--requests", "1", "--steps-T", "15",
                      "--solver", "seq", "--seed", "3"])
    err = float(jnp.max(jnp.abs(outs_p - outs_s)))
    scale = float(jnp.max(jnp.abs(outs_s))) + 1e-9
    assert err / scale < 2e-2
