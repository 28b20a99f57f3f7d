"""Plain reference: the DiT denoiser (Peebles & Xie 2023) and its DDIM step.

Written from the DiT paper in straightforward ``jax.numpy`` and imports
nothing of the program.  It follows the architecture the program serves,
which departs from the paper in four places (each a key the
configuration lists in ``reduced``): a 1-D sin-cos position table over the N
tokens instead of the 2-D one, a gated GELU MLP (GeGLU, two input
matrices) instead of the plain one, no biases, and an eps-only output (no
learned-sigma channels).

The forward scans over the stacked layers, so that it compiles in seconds
at full depth, and computes in float32 with ``precision="highest"`` unless
a lower ``dtype`` is asked for (the control: everything in that dtype).

DDIM (eta = 0, Song et al. 2020) on DDPM's linear beta schedule, with the
evenly spaced grid tau_t = t * (1000 // T) - 1:

    x_{t-1} = a_t x_t + b_t eps(x_t, tau_t)
    a_t = sqrt(abar_{t-1} / abar_t)
    b_t = sqrt(1 - abar_{t-1}) - sqrt(abar_{t-1} (1 - abar_t) / abar_t)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TEMB_DIM = 256
LN_EPS = 1e-6


def ddim_schedule(T: int, n_train: int = 1000, beta_min: float = 1e-4,
                  beta_max: float = 0.02) -> dict:
    """(T+1,) float64 arrays a, b, tau (index t = 1..T; entry 0 unused) and
    g2 = n_train * beta(tau_t), the noise scale ParaTAA's stopping rule
    reads (``g2[0] = g2[1]``)."""
    betas = np.linspace(beta_min, beta_max, n_train, dtype=np.float64)
    abar_full = np.cumprod(1.0 - betas)
    grid = np.arange(1, T + 1) * (n_train // T) - 1
    abar = np.concatenate([[1.0], abar_full[grid]])
    a = np.zeros(T + 1)
    b = np.zeros(T + 1)
    a[1:] = np.sqrt(abar[:-1] / abar[1:])
    b[1:] = np.sqrt(1.0 - abar[:-1]) \
        - np.sqrt(abar[:-1] * (1.0 - abar[1:]) / abar[1:])
    tau = np.concatenate([[0.0], grid.astype(np.float64)])
    g2 = np.concatenate([[0.0], betas[grid] * n_train])
    g2[0] = g2[1]
    return {"a": a, "b": b, "tau": tau, "g2": g2}


def _layernorm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS)


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def _timestep_embedding(t, dim: int = TEMB_DIM, max_period: float = 1e4):
    """DiT's TimestepEmbedder frequencies: [cos, sin] of t * f_i."""
    half = dim // 2
    freqs = jnp.exp(-np.log(max_period) * jnp.arange(half) / half)
    args = t[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _positions(n: int, d: int):
    """1-D sin-cos table (n, d): [sin, cos] of position * f_i."""
    half = d // 2
    freqs = np.exp(-np.log(10_000.0) * np.arange(half) / half)
    ang = np.arange(n)[:, None] * freqs[None, :]
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(float(np.sqrt(2.0 / np.pi))
                                     * (x + 0.044715 * x ** 3)))


def forward(params, x, t, y, *, dtype=jnp.float32):
    """eps for a block of rows.  x: (R, N, L_in); t: (R,) float timesteps;
    y: (R,) int labels.  Returns float32 (R, N, L_in)."""
    prec = "highest" if dtype == jnp.float32 else "default"
    mm = functools.partial(jnp.einsum, precision=prec)
    p = jax.tree.map(lambda w: w.astype(dtype), params)
    x = x.astype(dtype)
    n, d = x.shape[1], p["in_proj"].shape[1]
    h = mm("rnl,ld->rnd", x, p["in_proj"]) \
        + jnp.asarray(_positions(n, d), dtype)[None]
    temb = _timestep_embedding(t.astype(jnp.float32)).astype(dtype)
    c = mm("re,ed->rd", jax.nn.silu(mm("rf,fe->re", temb, p["t_mlp1"])),
           p["t_mlp2"])
    c = jax.nn.silu(c + p["y_embed"][y])
    scale = np.asarray(1.0 / np.sqrt(p["blocks"]["wq"].shape[-1]), dtype)

    def layer(h, w):
        s1, sc1, g1, s2, sc2, g2 = jnp.split(mm("rd,de->re", c, w["ada"]),
                                             6, axis=-1)
        u = _modulate(_layernorm(h), s1, sc1)
        q = mm("rnd,dhk->rnhk", u, w["wq"])
        k = mm("rnd,dhk->rnhk", u, w["wk"])
        v = mm("rnd,dhk->rnhk", u, w["wv"])
        att = jax.nn.softmax(mm("rnhk,rmhk->rhnm", q, k) * scale, axis=-1)
        o = mm("rhnm,rmhk->rnhk", att, v)
        h = h + g1[:, None, :] * mm("rnhk,hkd->rnd", o, w["wo"])
        u = _modulate(_layernorm(h), s2, sc2)
        m = _gelu_tanh(mm("rnd,df->rnf", u, w["mlp"]["wi_gate"])) \
            * mm("rnd,df->rnf", u, w["mlp"]["wi_up"])
        h = h + g2[:, None, :] * mm("rnf,fd->rnd", m, w["mlp"]["wo"])
        return h, None

    h, _ = jax.lax.scan(layer, h, p["blocks"])
    shift, sc = jnp.split(mm("rd,de->re", c, p["final_ada"]), 2, axis=-1)
    out = mm("rnd,dl->rnl", _modulate(_layernorm(h), shift, sc),
             p["out_proj"])
    return out.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _step_block(params, x_t, x_prev, t, y, a, b, *, dtype):
    """Per-row teacher-forced DDIM step over a block of rows: the squared
    gap |x_prev - (a x_t + b eps(x_t))|^2 against the float32 reference,
    and |b eps|^2.  With ``dtype`` below float32, ``x_prev`` is ignored and
    replaced by the whole step, denoiser and recursion, computed in that
    precision (the control)."""
    eps = forward(params, x_t, t, y)
    be = b[:, None, None] * eps
    if dtype != jnp.float32:
        low = [v.astype(dtype) for v in (a[:, None, None], x_t,
                                         b[:, None, None])]
        x_prev = (low[0] * low[1] + low[2] * forward(
            params, x_t, t, y, dtype=dtype).astype(dtype)
                  ).astype(jnp.float32)
    gap = x_prev - (a[:, None, None] * x_t + be)
    return jnp.sum(gap ** 2, axis=(1, 2)), jnp.sum(be ** 2, axis=(1, 2))


def step_readings(params, trajectory, label: int, sched: dict, *,
                  block: int, dtype=jnp.float32):
    """Teacher-forced readings of one served trajectory (T+1, N, L_in),
    rows in index order (row T is the initial noise, row 0 is x0).

    For each t = 1..T the reference takes the served x_t, computes its own
    DDIM step, and returns ``(gap2, beps2)``: (T,) squared L2 norms of the
    served x_{t-1} minus the reference's x_{t-1}, and of b_t * eps_ref.
    A ``dtype`` below float32 reads the control instead: the gap of the
    x_{t-1} that the reference computed in that precision, denoiser and
    recursion alike, puts in the same served x_t's place."""
    X = jnp.asarray(trajectory, jnp.float32)
    T = X.shape[0] - 1
    gap2, beps2 = [], []
    for lo in range(1, T + 1, block):
        ts = np.arange(lo, min(lo + block, T + 1))
        ts_p = np.concatenate([ts, np.full(block - len(ts), ts[-1])])
        a = jnp.asarray(sched["a"][ts_p], jnp.float32)
        b = jnp.asarray(sched["b"][ts_p], jnp.float32)
        tau = jnp.asarray(sched["tau"][ts_p], jnp.float32)
        y = jnp.full((block,), label, jnp.int32)
        g, e = _step_block(params, X[ts_p], X[ts_p - 1], tau, y, a, b,
                           dtype=dtype)
        gap2.append(np.asarray(g)[:len(ts)])
        beps2.append(np.asarray(e)[:len(ts)])
    return np.concatenate(gap2), np.concatenate(beps2)


def stopping_thresholds(sched: dict, tau: float, D: int) -> np.ndarray:
    """ParaTAA's stopping rule (Tang et al. 2024, eq. 11 and Sec. 5): row
    x_{t-1} has converged once its squared first-order residual
    |x_{t-1} - a_t x_t - b_t eps(x_t)|^2 is at most tau^2 g^2(t) D.
    Returns those (T,) squared tolerances for t = 1..T."""
    return (tau ** 2) * sched["g2"][1:] * D


def step_gap(gap2, beps2, thresh2=None) -> float:
    """The widest excess of a served row over its allowed distance from
    the reference's DDIM step, relative to |b eps_ref|: per row
    sqrt(max(gap^2 - thresh^2, 0)) / |b eps_ref|.  ``thresh2`` is the
    solver's stated squared tolerance for the row (``stopping_thresholds``;
    none for the sequential sampler, whose rows are the recursion itself).
    A row the solver left within its tolerance of the recursion on the
    program's eps reads at most the program's eps error; a row left
    further out, by a looser tolerance or an early stop, reads what it
    exceeds by."""
    t2 = 0.0 if thresh2 is None else np.asarray(thresh2, np.float64)
    excess = np.maximum(np.asarray(gap2, np.float64) - t2, 0.0)
    return float(np.max(np.sqrt(excess / np.asarray(beps2, np.float64))))
