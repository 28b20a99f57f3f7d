"""PixArt-α against its plain reference at a reduced size on the CPU, its
cell's runs through the harness, and the arithmetic its new per-layer
metrics read.

Every width is cut, but the caption keeps its published (120, 4096) shape:
the module draws captions of that shape."""
import dataclasses
import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import harness  # noqa: E402
import reference  # noqa: E402
import scopes  # noqa: E402
import trace_reduce  # noqa: E402
import weights  # noqa: E402

PIXART = harness.load_model("pixart-alpha")
CELL = "pixart512-seq-offline"
SMALL = dict(num_layers=2, d_model=64, num_heads=4, head_dim=16, d_ff=128,
             num_tokens=16)
SEED = 2**31 + 13


def _config(**sizes):
    with open(harness.BENCH_DIR / "configs" / "pixart-alpha-xl2-512.json") \
            as f:
        return dict(json.load(f), **sizes)


@pytest.fixture(scope="module")
def small():
    cfg = _config(**SMALL)
    params = weights.make_weights(PIXART.leaf_shapes(cfg), cfg["init"],
                                  weights.seed_key(SEED))
    arch = PIXART.program_arch(cfg)
    harness.check_layout(params, PIXART, arch)
    return cfg, params, arch


def _rows(r=3, n=16, seed=1):
    import jax
    import jax.numpy as jnp
    x = jax.random.normal(jax.random.PRNGKey(seed), (r, n, 16))
    return x, jnp.asarray([999.0, 507.0, 3.0][:r])


def _program_eps(params, arch, x, t, cond, guided=True):
    """The program's eps for one request's rows: the engine's guided
    adapter, or the model's conditional eps alone."""
    import jax
    import jax.numpy as jnp
    from repro.diffusion import pixart
    kw = PIXART.request_kwargs(cond)["cond"]
    c = {"caption": jnp.broadcast_to(kw["caption"],
                                     (x.shape[0],) + kw["caption"].shape),
         "tokens": jnp.full((x.shape[0],), kw["tokens"], jnp.int32)}
    with jax.default_matmul_precision("highest"):
        if guided:
            return pixart.make_eps_apply(arch)(params, x, t, c)
        return pixart.eps_of(pixart.pixart_apply(params, arch, x, t, c),
                             arch)


@pytest.mark.parametrize("guided", [True, False])
def test_pixart_matches_reference(small, guided):
    """The program's PixArt against the reference forward at float32:
    guided (the engine's adapter against ``forward``) and unguided (the
    model's conditional eps against ``forward`` at scale 1).  Tolerance
    1e-5 relative: both compute in float32, and only the order of their
    sums differs (read: 6e-7)."""
    cfg, params, arch = small
    x, t = _rows()
    cond = {"caption_seed": 77, "tokens": 37}
    got = _program_eps(params, arch, x, t, cond, guided)
    want = PIXART.forward(params, x, t, cond,
                          guidance=None if guided else 1.0)
    err = float(np.linalg.norm(np.asarray(got) - np.asarray(want))
                / np.linalg.norm(np.asarray(want)))
    assert err < 1e-5, err


def test_caption_and_guidance_move_eps(small):
    """The caption is part of the answer: another caption, another token
    count, and guidance each move eps well past float32 rounding, so a
    program that dropped one would read in ``correct``."""
    cfg, params, arch = small
    x, t = _rows()
    base = np.asarray(PIXART.forward(params, x, t,
                                     {"caption_seed": 77, "tokens": 37}))
    for other in ({"caption_seed": 78, "tokens": 37},
                  {"caption_seed": 77, "tokens": 36}):
        moved = np.asarray(PIXART.forward(params, x, t, other))
        assert np.linalg.norm(moved - base) > 1e-4 * np.linalg.norm(base)
    unguided = np.asarray(PIXART.forward(
        params, x, t, {"caption_seed": 77, "tokens": 37}, guidance=1.0))
    assert np.linalg.norm(unguided - base) > 1e-4 * np.linalg.norm(base)


def test_padded_caption_rows_change_nothing(small):
    """Caption rows past the token count are padding: whatever they hold,
    the program's and the reference's eps stay the same, to the bit."""
    import jax.numpy as jnp
    from repro.diffusion import pixart
    cfg, params, arch = small
    x, t = _rows()
    tokens = 23
    caption = PIXART.caption_embedding(np.int32(5))
    c = {"caption": jnp.broadcast_to(caption, (3,) + caption.shape),
         "tokens": jnp.full((3,), tokens, jnp.int32)}
    noisy = dict(c, caption=c["caption"].at[:, tokens:].add(7.0))
    eps = pixart.make_eps_apply(arch)
    np.testing.assert_array_equal(np.asarray(eps(params, x, t, c)),
                                  np.asarray(eps(params, x, t, noisy)))


def test_reference_ignores_padded_caption_rows(small, monkeypatch):
    """The same of the reference: its mask, not the padding, decides."""
    import jax
    cfg, params, arch = small
    x, t = _rows()
    cond = {"caption_seed": 5, "tokens": 23}
    want = np.asarray(PIXART.forward(params, x, t, cond))
    real = PIXART.caption_embedding
    monkeypatch.setattr(PIXART, "caption_embedding", jax.jit(
        lambda seed: real(seed).at[23:].set(-9.0)))
    np.testing.assert_array_equal(
        np.asarray(PIXART.forward(params, x, t, cond)), want)


def test_pixart_flops_are_pinned():
    """One served (guided) row at the full configuration: two evaluations
    of 1.236 TFLOP.  Per layer 4 N d HK + 2 N^2 HK (self-attention), 2 N d
    HK + 2 M d HK + 2 N M HK (cross-attention, M = 120), 2 N d ff (MLP)
    multiply-adds, 22.04 G; once an evaluation 120 (4096 d + d^2) + 256 d
    + d^2 + 6 d^2 + 16 N d + 32 N d; two FLOPs a multiply-add."""
    assert PIXART.forward_flops(_config()) == 2471971848192


def test_xflash_counts_are_the_call_shapes():
    """The kernel's counts at 16 rows (8 slots of 2 guided rows): scores
    and context against 120 keys, and the HBM bytes of the f32 context it
    writes (its operands come from VMEM)."""
    cfg = _config()
    rows, h, k, n = 16, 16, 72, 1024
    assert PIXART.xflash_flops(cfg, rows) == 4 * rows * h * n * 120 * k
    assert PIXART.xflash_bytes(cfg, rows) == rows * h * k * n * 4


# --- the cell through the harness --------------------------------------------


def small_cell(**traffic):
    cell = harness.load_cell(CELL)
    cell.config = dict(cell.config, **SMALL)
    cell.traffic = dict(cell.traffic, T=6, client_stagger_s=0.02,
                        drain_s=3.0, check={"requests": 3, "block": 4},
                        **traffic)
    return cell


@pytest.mark.parametrize("solver", ["seq", "taa"])
def test_served_x0_matches_reference_solve(solver):
    """PixArt served through the engine and the stepwise serving loop (the
    harness's window) against the reference's own sequential DDIM solve
    from the request's noise under its caption: seq to float32 rounding
    (1e-4 relative over 6 steps), ParaTAA within the x0 scale its
    stopping rule admits (sum_j tau sqrt(g2 D) a_1 ... a_j)."""
    import jax
    import jax.numpy as jnp
    spec = {"name": "taa", "order_k": 8, "history_m": 3, "window": 0,
            "fuse_round": False, "tau": 0.001} if solver == "taa" \
        else {"name": "seq"}
    cell = small_cell(solver=spec, clients=3)
    system = harness.build_system(cell, SEED)
    harness.warm_up(system)
    window = harness.run_window(system, SEED, 1.0)
    served = [(s, r) for s, r in harness.outcome(window.sent)
              if r is not None][:2]
    assert served
    T = cell.traffic["T"]
    sched = reference.ddim_schedule(T)
    D = int(np.prod(system.sample_shape))
    scale, gain = 0.0, 1.0
    for j in range(T):
        scale += gain * 1e-3 * float(np.sqrt(sched["g2"][j + 1] * D))
        gain *= float(sched["a"][j + 1])
    for sent, res in served:
        x = jax.random.normal(jax.random.PRNGKey(sent.noise_seed),
                              (T + 1,) + system.sample_shape)[T][None]
        for t in range(T, 0, -1):
            eps = PIXART.forward(system.params, x,
                                 jnp.asarray([sched["tau"][t]], jnp.float32),
                                 sent.cond)
            x = sched["a"][t] * x + sched["b"][t] * eps
        gap = float(np.linalg.norm(np.asarray(res.x0) - np.asarray(x[0])))
        if solver == "seq":
            assert gap <= 1e-4 * float(np.linalg.norm(x)), gap
        else:
            assert res.converged and gap <= scale, (gap, scale)


def test_cell_run_is_correct_and_control_is_not():
    """A whole run of the cell is correct; the bfloat16 control reads
    above its step_gap limit on the rows the program served below it."""
    import gc
    import jax
    import jax.numpy as jnp
    cell = small_cell()
    result = harness.run(cell, SEED, 1.5, False, time.monotonic(),
                         devices=jax.devices(), out=lambda *_: None)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "images_per_s"}
    system = harness.build_system(cell, SEED)
    harness.warm_up(system)
    window = harness.run_window(system, SEED, 1.0)
    system.engine = None
    gc.collect()
    prog = harness.correctness(system, window, SEED)
    ctl = harness.correctness(system, window, SEED, dtype=jnp.bfloat16)
    limit = cell.limits["step_gap"]
    assert prog["checked"] > 0 and prog["step_gap"] < limit
    assert ctl["step_gap"] > limit


# --- the new per-layer readers ----------------------------------------------


def _scoped(**seconds):
    return scopes.ScopeReduction(window_s=10.0, busy_s=8.0, devices=2,
                                 scope_seconds=seconds, idle_under={})


@pytest.mark.parametrize("name,scope", [
    ("xattn_share.t2i", "parataa/denoise/dit/xattn"),
    ("xattn_share.t2i", "dit/xattn"),
])
def test_scope_share_readers_by_hand(monkeypatch, name, scope):
    """Seconds under the scope, summed over chips, over busy seconds
    summed over chips; nothing without a trace."""
    red = _scoped(**{scope: 4.0, "parataa/denoise/dit/attn": 3.0})
    monkeypatch.setattr(scopes, "for_window", lambda trace: red)
    read = harness.metric_reader(name)
    assert read({"trace": object(), "taa": {}}) == pytest.approx(25.0)
    monkeypatch.setattr(scopes, "for_window", lambda trace: None)
    assert harness.metric_reader(name)({"trace": None, "taa": {}}) is None


def test_xattn_roofline_reader_by_hand():
    """calls x max(FLOPs / peak, bytes / bandwidth) over the calls' device
    time, from the module's counts at the cell's rows per call; an op that
    reads the kernel's output is not a call."""
    cfg = _config()
    peaks = harness.peaks_for("TPU v5 lite")
    least = max(PIXART.xflash_flops(cfg, 16) / peaks["flops_per_s"],
                PIXART.xflash_bytes(cfg, 16) / peaks["hbm_bytes_per_s"])
    kernel = "%_dit_xflash.3 = f32[16] custom-call()"
    reader = "%fusion.9 = f32[16] fusion(%_dit_xflash.3, %p)"
    red = trace_reduce.Reduction(
        window_s=10.0, busy_s=9.0,
        op_seconds={kernel: 28 * 2 * least, reader: 5.0,
                    "%_dit_flash.3 = f32[16] custom-call()": 1.0},
        op_calls={kernel: 28, reader: 28,
                  "%_dit_flash.3 = f32[16] custom-call()": 28}, gaps=[])
    ctx = {"trace": red, "peaks": peaks, "slots": 8, "rows_per_lane_iter": 1}
    read = harness.metric_reader("xattn_roofline.t2i")
    assert read(ctx) == pytest.approx(50.0)
    assert read(dict(ctx, trace=dataclasses.replace(
        red, op_seconds={}, op_calls={}))) is None
    assert read(dict(ctx, peaks=None)) is None
