"""The plain reference against the program at a reduced size on the CPU,
the seeded weights, and the shape arithmetic the per-layer metrics divide
by."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import counts  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402

DIT = harness.load_model("dit-xl")

# the full configurations' shape keys, at a size a CPU test holds
SMALL = dict(num_layers=2, d_model=64, num_heads=4, head_dim=16, d_ff=128,
             latent_dim=16, num_tokens=16, num_classes=10)
BENCH = os.path.join(os.path.dirname(__file__), os.pardir)


def _config_file(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _config(**sizes):
    return dict(_config_file("dit-xl2-256"), **sizes)


def _weights(cfg):
    return weights.make_weights(DIT.leaf_shapes(cfg), cfg["init"],
                                weights.seed_key(2**31 + 11))


@pytest.fixture(scope="module")
def small():
    cfg = _config(**SMALL)
    return cfg, _weights(cfg), DIT.program_arch(cfg)


#: sha256 (first 16 hex digits) of each leaf's float32 bytes at SMALL
#: sizes and seed 2**31 + 11, as the benchmark made them before its
#: model-specific code moved into ``models/dit-xl.py``
LEAF_DIGESTS = {
    "blocks.ada": "22a3dfd27947b53b",
    "blocks.mlp.wi_gate": "3fea26a1895c6828",
    "blocks.mlp.wi_up": "98f02c20a41b15f6",
    "blocks.mlp.wo": "607c7f4d0aadb83c",
    "blocks.wk": "d86f1316014586e0",
    "blocks.wo": "ac6bde05c5c0edf7",
    "blocks.wq": "11e5e658fb706b29",
    "blocks.wv": "f2e7feeaa8312632",
    "final_ada": "ea5b887838b8e20f",
    "in_proj": "91412c69c0f8dbb4",
    "out_proj": "3afda63c9194d54c",
    "t_mlp1": "89a8196d9d89a9d5",
    "t_mlp2": "63cd88e9312bcaa1",
    "y_embed": "f364ffaa6e5d2b6f",
}


def test_weights_are_pinned(small):
    """The same weights, bit for bit, as before the move."""
    import jax
    _, params, _ = small
    got = {".".join(k.key for k in path):
           hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:16]
           for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == LEAF_DIGESTS


def test_layout_matches_program(small):
    """The module's weight tree is the program's layout (``dit_defs``)."""
    cfg, params, arch = small
    harness.check_layout(params, DIT, arch)
    assert DIT.program_layout(arch) == DIT.leaf_shapes(cfg)


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
    ref = np.asarray(DIT.forward(params, x, t, y))
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


@pytest.mark.parametrize("dtype,gap,rtol", [
    ("float32", 6.780295207556427, 1e-6),
    ("bfloat16", 0.01727736194757697, 1e-2)])
def test_step_readings_are_pinned(small, dtype, gap, rtol):
    """The teacher-forced readings of one fixed (not served) trajectory
    under label 3 give the step_gap they gave before the move: to float32
    rounding, and the bfloat16 control to a bfloat16 rounding of a few
    elements (the order of a sum may differ between CPU builds)."""
    import jax
    import jax.numpy as jnp
    _, params, _ = small
    traj = jax.random.normal(jax.random.PRNGKey(7), (9, 16, 16))
    g2, b2 = reference.step_readings(DIT.forward, params, traj, 3,
                                     reference.ddim_schedule(8), block=3,
                                     dtype=getattr(jnp, dtype))
    assert reference.step_gap(g2, b2) == pytest.approx(gap, rel=rtol)


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
        g2, b2 = reference.step_readings(DIT.forward, params,
                                         res.trajectory, res.request.label,
                                         sched, block=3)
        if thresh2 is not None:
            # the program's own residuals obey the same stated tolerance
            assert res.converged
            assert np.all(res.residuals <= thresh2 * (1 + 1e-6))
        prog = max(prog, reference.step_gap(g2, b2, thresh2))
        g2c, b2c = reference.step_readings(DIT.forward, params,
                                           res.trajectory, res.request.label,
                                           sched, block=3,
                                           dtype=jnp.bfloat16)
        ctl = max(ctl, reference.step_gap(g2c, b2c, thresh2))
    assert prog < 1e-4
    assert ctl > 100 * max(prog, 1e-4)


def test_dit_flops_match_the_paper():
    """The paper's Gflops column counts multiply-adds of DiT-XL/2: 118.6 G
    at 256x256 and 524.6 G at 512x512 (rounded, from its FLOP counter,
    hence the 0.1% tolerance)."""
    for name, gmac in (("dit-xl2-256", 118.6), ("dit-xl2-512", 524.6)):
        cfg = _config_file(name)
        macs = DIT.forward_flops(cfg, paper=True) / 2
        assert macs / 1e9 == pytest.approx(gmac, rel=1e-3)
        # the program's gated MLP adds a third d x d_ff matrix per layer
        extra = 2 * cfg["num_layers"] * cfg["num_tokens"] \
            * cfg["d_model"] * cfg["d_ff"]
        assert DIT.forward_flops(cfg) > \
            DIT.forward_flops(cfg, paper=True) + 0.99 * extra


@pytest.mark.parametrize("name,flops", [("dit-xl2-256", 313334857728),
                                        ("dit-xl2-512", 1353444655104)])
def test_dit_flops_are_pinned(name, flops):
    """``flops_per_row`` of each cell, exactly as before the move."""
    assert DIT.forward_flops(_config_file(name)) == flops


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
