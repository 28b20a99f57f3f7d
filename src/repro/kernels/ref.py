"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors its kernel's contract exactly; tests sweep shapes and
dtypes asserting allclose between kernel (interpret=True on CPU) and oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# --- flash attention (fwd) --------------------------------------------------


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B, H, S, D); k, v: (B, H, T, D) -> (B, H, S, D).  f32 softmax."""
    d = q.shape[-1]
    s, t = q.shape[2], k.shape[2]
    scores = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(d)
    qp = jnp.arange(s)[:, None] + (t - s)  # right-aligned positions
    kp = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= kp > qp - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def dit_attention_ref(q, k, v):
    """The DiT's non-causal attention, f32 scores and softmax.  q, k, v:
    (B, N, H, K) -> f32 (B, N, H, K)."""
    scores = jnp.einsum("bnhk,bmhk->bhnm", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = scores / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhnm,bmhk->bnhk", probs, v.astype(jnp.float32))


def dit_cross_attention_ref(q, k, v, lengths):
    """Attention of DiT tokens to a key sequence of which each row counts
    its first ``lengths`` keys, f32 scores and softmax.  q: (B, N, H, K);
    k, v: (B, M, H, K); lengths: (B,) -> f32 (B, N, H, K).  Masked scores
    take the kernels' finite NEG_INF, so a row with no key stays finite."""
    from repro.kernels.flash_attention import NEG_INF
    scores = jnp.einsum("bnhk,bmhk->bhnm", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = scores / np.sqrt(q.shape[-1])
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]    # (B, M)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhnm,bmhk->bnhk", probs, v.astype(jnp.float32))


# --- GQA flash decode --------------------------------------------------------


def decode_ref(q, k_cache, v_cache, lengths):
    """q: (B, H, D) one token; k/v_cache: (B, T, KV, D); lengths: (B,) valid
    prefix lengths -> (B, H, D)."""
    b, h, d = q.shape
    kv = k_cache.shape[2]
    g = h // kv
    qr = q.reshape(b, kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bkgd,btkd->bkgt", qr, k_cache.astype(jnp.float32)) / np.sqrt(d)
    t = k_cache.shape[1]
    valid = jnp.arange(t)[None] < lengths[:, None]  # (B, T)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


# --- Mamba2 SSD chunked scan --------------------------------------------------


def ssd_ref(x, dt, A, B, C, init_state=None):
    """Sequential (exact) SSD recurrence.  x: (b, s, h, p); dt: (b, s, h);
    A: (h,); B, C: (b, s, n) (single group) -> (y, final_state (b,h,p,n))."""
    bsz, s, h, p = x.shape
    n = B.shape[-1]
    f32 = jnp.float32
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    state = jnp.zeros((bsz, h, p, n), f32) if init_state is None else init_state.astype(f32)

    def step(state, i):
        a = jnp.exp(dt[:, i] * A[None, :])  # (b, h)
        upd = jnp.einsum("bhp,bn,bh->bhpn", x[:, i], B[:, i], dt[:, i])
        state = state * a[..., None, None] + upd
        y = jnp.einsum("bn,bhpn->bhp", C[:, i], state)
        return state, y

    state, ys = jax.lax.scan(step, state, jnp.arange(s))
    return jnp.moveaxis(ys, 0, 1), state  # (b, s, h, p)


# --- RG-LRU linear scan --------------------------------------------------------


def rglru_ref(a, b, h0=None):
    """h_t = a_t * h_{t-1} + b_t.  a, b: (B, S, C) -> (B, S, C) f32."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    h = jnp.zeros_like(a[:, 0]) if h0 is None else h0.astype(jnp.float32)

    def step(h, i):
        h = a[:, i] * h + b[:, i]
        return h, h

    _, hs = jax.lax.scan(step, h, jnp.arange(a.shape[1]))
    return jnp.moveaxis(hs, 0, 1)


# --- TAA fused update ----------------------------------------------------------


def taa_gram_ref(dF, R, mask):
    """dF: (m, T, D); R: (T, D); mask: (T,) -> (G (T,m,m), u (T,m)) f32
    per-row Gram blocks (suffix-cumsum applied by the caller)."""
    f32 = jnp.float32
    dFw = dF.astype(f32) * mask[None, :, None]
    Rw = R.astype(f32) * mask[:, None]
    G = jnp.einsum("mtd,ntd->tmn", dFw, dFw)
    u = jnp.einsum("mtd,td->tm", dFw, Rw)
    return G, u


def taa_apply_ref(x, R, dX, dF, gamma, mask):
    """x, R: (T, D); dX, dF: (m, T, D); gamma: (T, m); mask: (T,) ->
    x + R - (dX + dF)^T gamma on masked rows."""
    f32 = jnp.float32
    corr = jnp.einsum("mtd,tm->td", dX.astype(f32) + dF.astype(f32), gamma.astype(f32))
    x_new = x.astype(f32) + R.astype(f32) * mask[:, None] - corr * mask[:, None]
    return jnp.where(mask[:, None] > 0, x_new, x.astype(f32)).astype(x.dtype)
