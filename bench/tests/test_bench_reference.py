"""The plain reference against the program at a reduced size on the CPU,
and the shape arithmetic the per-layer metrics divide by."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import counts  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402

# the full configurations' shape keys, at a size a CPU test holds
SMALL = dict(num_layers=2, d_model=64, num_heads=4, head_dim=16, d_ff=128,
             latent_dim=16, num_tokens=16, num_classes=10)
BENCH = os.path.join(os.path.dirname(__file__), os.pardir)


def _config(**sizes):
    import json
    with open(os.path.join(BENCH, "configs", "dit-xl2-256.json")) as f:
        cfg = json.load(f)
    cfg.update(sizes)
    return cfg


@pytest.fixture(scope="module")
def small():
    import harness
    cfg = _config(**SMALL)
    params = weights.make_weights(cfg, weights.seed_key(2**31 + 11))
    return cfg, params, harness.program_arch(cfg)


def test_forward_matches_dit_apply(small):
    """float32 on the CPU: the reference and the program's DiT agree to
    float32 rounding over the whole forward (1e-5 of the eps norm)."""
    import jax
    import jax.numpy as jnp
    from repro.diffusion import dit
    cfg, params, arch = small
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 16))
    t = jnp.asarray([39.0, 499.0, 999.0])
    y = jnp.asarray([0, 4, 9])
    ref = np.asarray(reference.forward(params, x, t, y))
    got = np.asarray(dit.dit_apply(params, arch, x, t, y))
    assert np.linalg.norm(got - ref) <= 1e-5 * np.linalg.norm(ref)
    # the calibrated init makes eps depend on x
    assert np.linalg.norm(ref) > 0.1 * np.sqrt(ref.size)


def test_schedule_matches_program():
    from repro.core import ddim_coeffs
    for T in (8, 25, 100):
        mine, theirs = reference.ddim_schedule(T), ddim_coeffs(T)
        np.testing.assert_allclose(mine["a"], theirs.a, rtol=1e-12)
        np.testing.assert_allclose(mine["b"], theirs.b, rtol=1e-12)
        np.testing.assert_allclose(mine["tau"], theirs.taus, rtol=1e-12)
        np.testing.assert_allclose(mine["g2"], theirs.g2, rtol=1e-12)


@pytest.mark.parametrize("solver", ["seq", "taa"])
def test_served_rows_match_reference(small, solver):
    """The engine's trajectories sit within the solver's stated tolerance
    of the reference's DDIM step (seq: on the step, to float32 rounding);
    the bfloat16 control reads far above the tolerance."""
    import jax.numpy as jnp
    from repro.core import ddim_coeffs
    from repro.launch import serve
    from repro.sampling import SampleRequest, get_sampler
    cfg, params, arch = small
    T = 8
    spec = get_sampler(solver)
    engine = serve.make_engine(params, arch, ddim_coeffs(T), spec)
    results = engine.run_batch([SampleRequest(label=3, seed=5),
                                SampleRequest(label=7, seed=6)])
    sched = reference.ddim_schedule(T)
    D = int(np.prod(results[0].x0.shape))
    thresh2 = None if spec.is_sequential else \
        reference.stopping_thresholds(sched, spec.tau, D)
    prog, ctl = 0.0, 0.0
    for res in results:
        g2, b2 = reference.step_readings(params, res.trajectory,
                                         res.request.label, sched, block=3)
        if thresh2 is not None:
            # the program's own residuals obey the same stated tolerance
            assert res.converged
            assert np.all(res.residuals <= thresh2 * (1 + 1e-6))
        prog = max(prog, reference.step_gap(g2, b2, thresh2))
        g2c, b2c = reference.step_readings(params, res.trajectory,
                                           res.request.label, sched,
                                           block=3, dtype=jnp.bfloat16)
        ctl = max(ctl, reference.step_gap(g2c, b2c, thresh2))
    assert prog < 1e-4
    assert ctl > 100 * max(prog, 1e-4)


def test_dit_flops_match_the_paper():
    """The paper's Gflops column counts multiply-adds of DiT-XL/2: 118.6 G
    at 256x256 and 524.6 G at 512x512 (rounded, from its FLOP counter,
    hence the 0.1% tolerance)."""
    for name, gmac in (("dit-xl2-256", 118.6), ("dit-xl2-512", 524.6)):
        import json
        with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
            cfg = json.load(f)
        macs = counts.dit_forward_flops(cfg, paper=True) / 2
        assert macs / 1e9 == pytest.approx(gmac, rel=1e-3)
        # the program's gated MLP adds a third d x d_ff matrix per layer
        extra = 2 * cfg["num_layers"] * cfg["num_tokens"] \
            * cfg["d_model"] * cfg["d_ff"]
        assert counts.dit_forward_flops(cfg) > \
            counts.dit_forward_flops(cfg, paper=True) + 0.99 * extra


@pytest.mark.parametrize("T,D,m", [(25, 4096, 3), (8, 200, 2)])
def test_taa_kernel_bytes_match_array_sizes(T, D, m):
    """Every input read once and every output written once, at the
    128-lane-padded width the kernels work on."""
    dpad = -(-D // 128) * 128
    f32 = np.float32
    hist = np.zeros((m, T, dpad), f32)
    row = np.zeros((T, dpad), f32)
    mask = np.zeros((T, 1), f32)
    tile = np.zeros((T, 128), f32)
    gram = hist.nbytes + row.nbytes + mask.nbytes + tile.nbytes
    apply = 2 * row.nbytes + 2 * hist.nbytes + tile.nbytes + mask.nbytes \
        + row.nbytes
    for lanes in (1, 8):
        got = counts.taa_kernel_bytes(T, D, m, lanes)
        assert got == {"gram": lanes * gram, "apply": lanes * apply}
