"""PixArt-α on the serving path at its smoke size: the engine carries a
caption per lane through refill, step, harvest and a bank snapshot, the
warm-start cache keys on the caption, and ``serve.py --arch pixart-alpha``
serves it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core import ddim_coeffs
from repro.diffusion import pixart
from repro.launch import serve
from repro.models import pdefs
from repro.sampling import SampleRequest, SampleResult, get_sampler
from repro.serving import TrajectoryCache

CFG = ARCHS["pixart-alpha"].reduced()


def _params(seed=0):
    """Smoke-size weights with every leaf non-zero (PixArt zero-initialises
    the output projections and biases, which would make eps vanish)."""
    params = pdefs.init_params(pixart.pixart_defs(CFG),
                               jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        w + 0.05 * jax.random.normal(k, w.shape) for w, k in
        zip(leaves, keys)])


def _request(i, seed=None, tokens=None):
    kw = serve._draw_caption(np.random.default_rng(100 + i), CFG)
    if tokens is not None:
        kw["cond"]["tokens"] = np.int32(tokens)
    return SampleRequest(seed=i if seed is None else seed, **kw)


def _drain(engine, bank, upto=None):
    out = {}
    for _ in range(upto or 10 * engine.coeffs.T):
        engine.stepwise_step(bank)
        out.update(engine.stepwise_harvest(bank))
        if not bank.occupied:
            break
    return out


@pytest.mark.parametrize("solver", ["seq", "taa"])
def test_stepwise_lanes_carry_their_captions(solver):
    """Each lane solves under its own caption: requests served together in
    one bank come back as each does alone."""
    params = _params()
    engine = serve.make_engine(params, CFG, ddim_coeffs(6),
                               get_sampler(solver))
    reqs = [_request(i) for i in range(3)]
    bank = engine.stepwise_open(4, chunk_iters=1)
    engine.stepwise_refill(bank, [2, 0, 3], reqs)
    together = _drain(engine, bank)
    for lane, req in zip([2, 0, 3], reqs):
        alone = engine.stepwise_open(4, chunk_iters=1)
        engine.stepwise_refill(alone, [1], [req])
        (res,) = _drain(engine, alone).values()
        np.testing.assert_allclose(together[lane].x0, res.x0,
                                   rtol=1e-5, atol=1e-5)
        assert together[lane].request is req


def test_bank_snapshot_round_trip_with_captions():
    """A bank fetched to the host mid-solve and adopted by a fresh engine
    carries its captions and token counts, and finishes as an
    uninterrupted bank does, bit for bit."""
    params = _params()
    spec = get_sampler("taa")

    def engine():
        return serve.make_engine(params, CFG, ddim_coeffs(6), spec)

    reqs = [_request(i, tokens=5 + i) for i in range(2)]
    first = engine()
    bank = first.stepwise_open(2, chunk_iters=1)
    first.stepwise_refill(bank, [0, 1], reqs)
    first.stepwise_step(bank)
    first.stepwise_step(bank)
    snap = first.fetch_bank(bank)
    assert set(snap.conds) == {"caption", "tokens"}
    np.testing.assert_array_equal(snap.conds["tokens"], [5, 6])
    np.testing.assert_array_equal(snap.conds["caption"][1],
                                  reqs[1].cond["caption"])
    assert snap.nbytes() > snap.conds["caption"].nbytes
    second = engine()
    resumed = _drain(second, second.adopt_bank(snap))
    straight = _drain(first, bank)
    for lane in (0, 1):
        np.testing.assert_array_equal(resumed[lane].x0, straight[lane].x0)


def test_cache_keys_on_the_caption():
    """Two requests with different captions and the same label never share
    a warm start; the same caption under another noise draw does.  The
    key is the condition's digest: the same caption drawn again keys alike,
    and another token count does not."""
    cache = TrajectoryCache()
    a, b = _request(1, seed=7), _request(2, seed=8)
    assert a.label == b.label and a.condition_key != b.condition_key
    traj = np.ones((7, 16, 16), np.float32)
    assert cache.record(SampleResult(x0=traj[0], trajectory=traj, iters=3,
                                     nfe=3, converged=True, request=a))
    assert cache.lookup(b.condition_key, seed=b.seed) is None
    assert cache.lookup(a.condition_key, seed=99) is not None
    again = _request(1, seed=99)
    assert again.cond is not a.cond
    assert again.condition_key == a.condition_key
    assert cache.lookup(again.condition_key, seed=again.seed) is not None
    shorter = _request(1, seed=7, tokens=int(a.cond["tokens"]) - 1)
    assert shorter.condition_key != a.condition_key
    # a label-conditioned request keys on its label, as before
    assert SampleRequest(label=3).condition_key == 3


def test_request_without_a_caption_is_refused():
    """A PixArt engine refuses, request by request, one that carries no
    caption or a caption of the wrong shape or length."""
    engine = serve.make_engine(None, CFG, ddim_coeffs(4), get_sampler("seq"))
    engine.validate_request(_request(0))
    bad = [SampleRequest(label=3),
           dataclasses.replace(_request(0), cond={
               "caption": np.zeros((3, 3), np.float32), "tokens": 1}),
           _request(0, tokens=CFG.caption_len + 1)]
    for req in bad:
        with pytest.raises(ValueError):
            engine.validate_request(req)


@pytest.mark.parametrize("argv", [
    ["--solver", "seq"],
    ["--solver", "taa", "--batch-size", "2"],
    ["--serve-async", "--chunk-iters", "1", "--batch-size", "2",
     "--arrival-rate", "50", "--cache"],
], ids=["seq", "taa", "async-stepwise"])
def test_serve_cli_serves_pixart(argv):
    """``serve.py --arch pixart-alpha --smoke`` serves captions drawn from
    ``--seed`` through the engine (sync) and the serving loop (async)."""
    outs, stats = serve.main(["--arch", "pixart-alpha", "--smoke",
                              "--requests", "3", "--steps-T", "6"] + argv)
    assert outs.shape == (3, CFG.num_tokens, CFG.latent_dim)
    assert np.all(np.isfinite(np.asarray(outs)))
    keys = [s["cond"] for s in stats]
    assert all(isinstance(k, str) for k in keys) and len(set(keys)) == 3


def test_two_d_positions():
    """The 2-D sin-cos table: the first half of a row embeds the token's
    column, the rest its row, [sin, cos] of index x 10000^(-i/(d/4))."""
    from repro.models.layers import sincos_positions_2d
    table = sincos_positions_2d(16, 8)
    assert table.shape == (16, 8)
    col, row = 6 % 4, 6 // 4
    f = 1.0 / 10000 ** (np.arange(2) / 2)
    np.testing.assert_allclose(table[6], np.concatenate(
        [np.sin(col * f), np.cos(col * f), np.sin(row * f),
         np.cos(row * f)]), rtol=1e-6)
    np.testing.assert_allclose(jnp.asarray(table[:4, 4:]),
                               np.tile([0, 0, 1, 1], (4, 1)), atol=1e-7)
