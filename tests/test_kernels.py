"""Per-kernel correctness: shape/dtype sweeps, interpret=True vs pure-jnp
oracle (ref.py)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels import ops
from repro.kernels.flash_attention import (dit_blocks,
                                           dit_cross_flash_attention,
                                           dit_flash_attention,
                                           flash_attention)
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.rglru_scan import rglru_scan_kernel
from repro.kernels.taa_update import taa_gram, taa_apply

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 3e-5


@pytest.mark.parametrize("shape", [(2, 4, 256, 256, 64), (1, 2, 128, 384, 128),
                                   (1, 1, 256, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 100), (False, 0)])
def test_flash_attention(shape, dtype, causal, window):
    b, h, s, t, d = shape
    q = jax.random.normal(KEY, (b, h, s, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, h, t, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, h, t, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32))))
    assert err < _tol(dtype), err


def test_flash_attention_window_changes_output():
    b, h, s, d = 1, 2, 256, 64
    q = jax.random.normal(KEY, (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, h, s, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, h, s, d))
    full = flash_attention(q, k, v, causal=True, window=0, interpret=True)
    win = flash_attention(q, k, v, causal=True, window=64, interpret=True)
    assert float(jnp.max(jnp.abs(full - win))) > 1e-3


# (slots, H, B, N): G > 1 pairs a block at N = 256, 1024 and 16 tokens
DIT_SHAPES = [(2, 4, 2, 256), (2, 2, 1, 1024), (1, 2, 3, 16)]


@pytest.mark.parametrize("slots,h,b,n", DIT_SHAPES)
def test_dit_flash_attention_matches_einsum(slots, h, b, n):
    """The DiT kernel, vmapped over slots as the engine calls it, against
    the f32 einsum attention: no farther off than twice what rounding q, k,
    v to bf16 (XLA's default TPU precision) moves the einsums themselves."""
    assert dit_blocks(h * b, n, 72)[1] > 1
    qkv = [jax.random.normal(jax.random.fold_in(KEY, i), (slots, h, b, 72, n))
           for i in range(3)]
    out = jax.vmap(functools.partial(dit_flash_attention, interpret=True))(*qkv)
    einsum = jax.vmap(ops._dit_attention_ref_t)
    want = einsum(*qkv)
    bf16 = [a.astype(jnp.bfloat16).astype(jnp.float32) for a in qkv]
    rounding = float(jnp.max(jnp.abs(einsum(*bf16) - want)))
    err = float(jnp.max(jnp.abs(out - want)))
    assert 0 < err <= 2 * rounding, (err, rounding)
    assert out.shape == want.shape and out.dtype == want.dtype


@pytest.mark.parametrize("m", [1, 37, 120])
def test_dit_cross_attention_matches_masked_einsum(m):
    """The cross-attention kernel, vmapped over slots as the engine calls
    it, keys padded to 128 and each row masked past its own key count,
    against the f32 einsums under the same mask (``ops`` off the TPU): no
    farther off than twice what rounding q, k, v to bf16 moves the
    einsums themselves."""
    slots, h, b, k, n = 2, 4, 3, 72, 256
    x = jax.random.normal(jax.random.fold_in(KEY, m), (slots, b, n, 48))
    c = jax.random.normal(jax.random.fold_in(KEY, m + 1), (slots, b, m, 48))
    w = [jax.random.normal(jax.random.fold_in(KEY, i), (48, h, k)) / 7
         for i in range(3)]
    wo = jnp.eye(h * k).reshape(h, k, h * k)        # the context itself
    biases = [jax.random.normal(jax.random.fold_in(KEY, 5 + i), (h, k))
              for i in range(3)]
    lengths = jnp.asarray([[m, max(m // 2, 1), 1], [1, m, m]], jnp.int32)

    def attend(x, c, w, **kw):
        return jax.vmap(lambda x, c, lens: ops.dit_cross_attention(
            x, c, lens, *w, wo, biases=biases, **kw))(x, c, lengths)

    want = attend(x, c, w, use_pallas=False)
    out = attend(x, c, w, use_pallas=True, interpret=True)
    r16 = [a.astype(jnp.bfloat16).astype(jnp.float32) for a in (x, c, *w)]
    rounding = float(jnp.max(jnp.abs(
        attend(*r16[:2], r16[2:], use_pallas=False) - want)))
    err = float(jnp.max(jnp.abs(out - want)))
    assert 0 < err <= 2 * rounding, (err, rounding)
    assert out.shape == want.shape == (slots, b, n, h * k)


def test_dit_cross_attention_ignores_padded_keys():
    """Keys past a row's count change nothing, in the kernel and in the
    einsums: the mask, not the padding's values, decides."""
    b, n, m, d = 2, 128, 40, 32
    x = jax.random.normal(KEY, (b, n, d))
    c = jax.random.normal(jax.random.fold_in(KEY, 1), (b, m, d))
    w = [jax.random.normal(jax.random.fold_in(KEY, i), (d, 2, 16)) / 6
         for i in range(2, 5)]
    wo = jax.random.normal(jax.random.fold_in(KEY, 6), (2, 16, d))
    lengths = jnp.asarray([7, 23], jnp.int32)
    noisy = c.at[:, 23:].add(50.0).at[0, 7:].set(-3.0)
    for kw in ({"use_pallas": False}, {"use_pallas": True, "interpret": True}):
        a = ops.dit_cross_attention(x, c, lengths, *w, wo, **kw)
        z = ops.dit_cross_attention(x, noisy, lengths, *w, wo, **kw)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(z))


@pytest.mark.parametrize("slots,h,b,n", DIT_SHAPES[:2])
def test_dit_attention_gradient_is_the_einsum_gradient(slots, h, b, n):
    """The kernel path's custom VJP, vmapped over slots, is the einsum
    attention's VJP."""
    qkv = [jax.random.normal(jax.random.fold_in(KEY, i), (slots, h, b, 72, n))
           for i in range(3)]
    w = jax.random.normal(jax.random.fold_in(KEY, 9), qkv[0].shape)

    def grad(fn):
        return jax.grad(lambda *a: jnp.sum(jax.vmap(fn)(*a) * w),
                        argnums=(0, 1, 2))(*qkv)

    kernel = grad(lambda q, k, v: ops._dit_flash(q, k, v, True))
    for g, r in zip(kernel, grad(ops._dit_attention_ref_t)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape", [(2, 8, 4, 512, 64), (3, 16, 2, 1024, 128),
                                   (2, 8, 8, 512, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(shape, dtype):
    b, h, kv, t, d = shape
    q = jax.random.normal(KEY, (b, h, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, kv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, kv, d), dtype)
    lengths = jnp.asarray(np.random.default_rng(0).integers(1, t, size=b))
    out = flash_decode(q, k, v, lengths, interpret=True)
    want = ref.decode_ref(q, k, v, lengths)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32))))
    assert err < _tol(dtype), err


@pytest.mark.parametrize("shape,chunk", [((2, 256, 4, 32, 64), 64),
                                         ((1, 128, 2, 64, 128), 128),
                                         ((1, 512, 8, 16, 32), 64)])
def test_ssd_scan(shape, chunk):
    b, s, h, p, n = shape
    x = jax.random.normal(KEY, (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (h,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(KEY, 3), (b, s, n)) * 0.5
    C = jax.random.normal(jax.random.fold_in(KEY, 4), (b, s, n)) * 0.5
    y, fs = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, fsr = ref.ssd_ref(x, dt, A, B, C)
    assert float(jnp.max(jnp.abs(y - yr))) / (float(jnp.max(jnp.abs(yr))) + 1e-9) < 1e-4
    assert float(jnp.max(jnp.abs(fs - fsr))) / (float(jnp.max(jnp.abs(fsr))) + 1e-9) < 1e-4


@pytest.mark.parametrize("shape,bt,bc", [((2, 512, 256), 128, 128),
                                         ((1, 256, 512), 256, 256),
                                         ((3, 128, 128), 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan(shape, bt, bc, dtype):
    b, s, c = shape
    a = jax.nn.sigmoid(jax.random.normal(KEY, (b, s, c))).astype(dtype)
    bb = (jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, c)) * 0.3).astype(dtype)
    h = rglru_scan_kernel(a, bb, bt=bt, bc=bc, interpret=True)
    hr = ref.rglru_ref(a, bb)
    err = float(jnp.max(jnp.abs(h.astype(jnp.float32) - hr)))
    assert err < (5e-2 if dtype == jnp.bfloat16 else 1e-4), err


@pytest.mark.parametrize("m,t,d", [(3, 16, 512), (5, 25, 700), (2, 8, 128)])
def test_taa_gram_and_apply(m, t, d):
    dF = jax.random.normal(KEY, (m, t, d))
    dX = jax.random.normal(jax.random.fold_in(KEY, 1), (m, t, d))
    R = jax.random.normal(jax.random.fold_in(KEY, 2), (t, d))
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (t, d))
    mask = (jnp.arange(t) >= t // 3).astype(jnp.float32)
    G, u = taa_gram(dF, R, mask, bd=256, interpret=True)
    Gr, ur = ref.taa_gram_ref(dF, R, mask)
    assert float(jnp.max(jnp.abs(G - Gr))) < 1e-2
    assert float(jnp.max(jnp.abs(u - ur))) < 1e-2
    gamma = jax.random.normal(jax.random.fold_in(KEY, 4), (t, m)) * 0.1
    out = taa_apply(x, R, dX, dF, gamma, mask, bd=256, interpret=True)
    outr = ref.taa_apply_ref(x, R, dX, dF, gamma, mask)
    assert float(jnp.max(jnp.abs(out - outr))) < 1e-4


def test_ops_kernel_taa_gamma_matches_core_anderson():
    """ops.taa_rowwise_gamma (kernel path) == the solver's own suffix Grams."""
    from repro.core.anderson import _suffix_sum
    m, t, d = 3, 12, 300
    dF = jax.random.normal(KEY, (m, t, d))
    R = jax.random.normal(jax.random.fold_in(KEY, 1), (t, d))
    mask = (jnp.arange(t) >= 2).astype(jnp.float32)
    gamma_k = ops.taa_rowwise_gamma(dF, R, mask, lam=1e-6, use_pallas=True,
                                    interpret=True)
    dFw = dF * mask[None, :, None]
    G = jnp.einsum("mtd,ntd->tmn", dFw, dFw)
    u = jnp.einsum("mtd,td->tm", dFw, R * mask[:, None])
    Gs = _suffix_sum(G) + 1e-6 * jnp.eye(m)
    us = _suffix_sum(u)
    gamma_ref = jnp.linalg.solve(Gs, us[..., None])[..., 0]
    np.testing.assert_allclose(np.asarray(gamma_k), np.asarray(gamma_ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("mode", ["fp", "aa", "aa+", "taa"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_routed_anderson_update_interpret_matches_jnp(mode, dtype):
    """The kernels.ops-routed anderson_update with the Pallas path forced
    (interpret mode on CPU) matches the pure-jnp ref routing across every
    Anderson mode and dtype — the acceptance gate for dispatching the
    solver inner loop through the kernel layer."""
    from repro.core.anderson import anderson_update
    T, D, m = 14, 96, 3
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (T, D)).astype(dtype)
    R = (jax.random.normal(ks[1], (T, D)) * 0.3).astype(dtype)
    dX = (jax.random.normal(ks[2], (m, T, D)) * 0.1).astype(dtype)
    dF = (jax.random.normal(ks[3], (m, T, D)) * 0.1).astype(dtype)
    wmask = jnp.arange(T) >= 3
    guard = jnp.arange(T) >= T - 2
    kw = dict(mode=mode, lam=1e-6, safeguard_mask=guard)
    ref_out = anderson_update(x, R, dX, dF, wmask, use_pallas=False, **kw)
    pal_out = anderson_update(x, R, dX, dF, wmask, use_pallas=True,
                              interpret=True, **kw)
    err = float(jnp.max(jnp.abs(pal_out.astype(jnp.float32)
                                - ref_out.astype(jnp.float32))))
    assert err < _tol(dtype), (mode, err)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_routed_anderson_update_matches_literal_theorem_3_2(use_pallas):
    """Both routings of the taa mode reproduce the literal per-row-block
    Theorem 3.2 oracle over the full window."""
    from repro.core.anderson import anderson_update, taa_update_literal
    T, D, m = 10, 64, 3
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (T, D))
    R = jax.random.normal(ks[1], (T, D)) * 0.3
    dX = jax.random.normal(ks[2], (m, T, D)) * 0.1
    dF = jax.random.normal(ks[3], (m, T, D)) * 0.1
    wmask = jnp.ones((T,), bool)
    got = anderson_update(x, R, dX, dF, wmask, mode="taa", lam=1e-6,
                          use_pallas=use_pallas, interpret=use_pallas)
    want = taa_update_literal(x, R, dX, dF, 0, T - 1, 1e-6)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-3)


def test_ops_taa_gram_wrapper_dispatches_to_ref_on_cpu():
    """The new ops.taa_gram wrapper (shared by the aa/aa+ routings) auto-
    selects the jnp ref off-TPU and matches the kernel in interpret mode."""
    m, t, d = 3, 12, 256
    dF = jax.random.normal(KEY, (m, t, d))
    R = jax.random.normal(jax.random.fold_in(KEY, 1), (t, d))
    mask = (jnp.arange(t) >= 2).astype(jnp.float32)
    G_auto, u_auto = ops.taa_gram(dF, R, mask)           # CPU -> ref
    G_ref, u_ref = ref.taa_gram_ref(dF, R, mask)
    assert np.array_equal(np.asarray(G_auto), np.asarray(G_ref))
    assert np.array_equal(np.asarray(u_auto), np.asarray(u_ref))
    G_k, u_k = ops.taa_gram(dF, R, mask, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(G_k), np.asarray(G_ref),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(u_ref),
                               rtol=1e-3, atol=1e-3)


def _round_inputs(dtype=jnp.float32, T=14, D=96, m=3):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (T, D)).astype(dtype)
    R = (jax.random.normal(ks[1], (T, D)) * 0.3).astype(dtype)
    dX = (jax.random.normal(ks[2], (m, T, D)) * 0.1).astype(dtype)
    dF = (jax.random.normal(ks[3], (m, T, D)) * 0.1).astype(dtype)
    wmask = jnp.arange(T) >= 3
    guard = jnp.arange(T) >= T - 2
    return x, R, dX, dF, wmask, guard


@pytest.mark.parametrize("mode", ["aa", "aa+", "taa"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_taa_round_interpret_matches_staged(mode, dtype):
    """The single-pallas_call fused round (interpret mode on CPU) matches
    the staged gram->solve->apply composition for every Anderson mode and
    dtype — the acceptance gate for the one-launch update."""
    x, R, dX, dF, wmask, guard = _round_inputs(dtype)
    mask = wmask.astype(jnp.float32)
    kw = dict(mode=mode, lam=1e-6, safeguard_mask=guard)
    staged = ops.taa_round(x, R, dX, dF, mask, use_pallas=False, **kw)
    fused = ops.taa_round(x, R, dX, dF, mask, use_pallas=True,
                          interpret=True, **kw)
    err = float(jnp.max(jnp.abs(fused.astype(jnp.float32)
                                - staged.astype(jnp.float32))))
    assert err < _tol(dtype), (mode, err)


def test_fused_taa_round_matches_literal_theorem_3_2():
    """The fused kernel reproduces the literal per-row-block Theorem 3.2
    oracle over the full window (no safeguard, full mask)."""
    from repro.core.anderson import taa_update_literal
    T, D, m = 10, 64, 3
    x, R, dX, dF, _, _ = _round_inputs(T=T, D=D, m=m)
    mask = jnp.ones((T,), jnp.float32)
    got = ops.taa_round(x, R, dX, dF, mask, mode="taa", lam=1e-6,
                        use_pallas=True, interpret=True)
    want = taa_update_literal(x, R, dX, dF, 0, T - 1, 1e-6)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("mode", ["fp", "aa", "aa+", "taa"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fuse_round_cpu_default_is_bitwise_identical(mode, dtype):
    """On the CPU default routing, anderson_update(fuse_round=True) stages
    the EXACT same primitives in the same order as the unfused path, so the
    outputs must be bit-for-bit equal — the regression gate that lets
    fuse_round default on without perturbing any golden output."""
    from repro.core.anderson import anderson_update
    x, R, dX, dF, wmask, guard = _round_inputs(dtype)
    kw = dict(mode=mode, lam=1e-6, safeguard_mask=guard)
    unfused = anderson_update(x, R, dX, dF, wmask, fuse_round=False, **kw)
    fused = anderson_update(x, R, dX, dF, wmask, fuse_round=True, **kw)
    assert np.array_equal(np.asarray(unfused), np.asarray(fused)), mode


def test_ops_dispatch_cpu_uses_ref():
    q = jax.random.normal(KEY, (1, 2, 128, 64))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 2, 128, 64))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 2, 128, 64))
    out = ops.attention(q, k, v)  # auto: CPU -> ref
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
