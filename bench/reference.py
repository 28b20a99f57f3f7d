"""Plain reference: the DDIM step around a model's reference denoiser.

Written in straightforward ``jax.numpy`` and imports nothing of the
program.  The denoiser is the model module's ``forward`` (``models/
<arch>.py``), which computes in float32 with ``precision="highest"`` unless
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


@functools.partial(jax.jit, static_argnames=("forward", "dtype"))
def _step_block(forward, params, x_t, x_prev, t, cond, a, b, *, dtype):
    """Per-row teacher-forced DDIM step over a block of rows: the squared
    gap |x_prev - (a x_t + b eps(x_t))|^2 against the float32 reference,
    and |b eps|^2.  With ``dtype`` below float32, ``x_prev`` is ignored and
    replaced by the whole step, denoiser and recursion, computed in that
    precision (the control)."""
    eps = forward(params, x_t, t, cond)
    be = b[:, None, None] * eps
    if dtype != jnp.float32:
        low = [v.astype(dtype) for v in (a[:, None, None], x_t,
                                         b[:, None, None])]
        x_prev = (low[0] * low[1] + low[2] * forward(
            params, x_t, t, cond, dtype=dtype).astype(dtype)
                  ).astype(jnp.float32)
    gap = x_prev - (a[:, None, None] * x_t + be)
    return jnp.sum(gap ** 2, axis=(1, 2)), jnp.sum(be ** 2, axis=(1, 2))


def step_readings(forward, params, trajectory, cond, sched: dict, *,
                  block: int, dtype=jnp.float32):
    """Teacher-forced readings of one served trajectory (T+1, N, L_in),
    rows in index order (row T is the initial noise, row 0 is x0), under
    the reference denoiser ``forward`` (a model module's) and the
    request's condition ``cond``.

    For each t = 1..T the reference takes the served x_t, computes its own
    DDIM step, and returns ``(gap2, beps2)``: (T,) squared L2 norms of the
    served x_{t-1} minus the reference's x_{t-1}, and of b_t * eps_ref.
    A ``dtype`` below float32 reads the control instead: the gap of the
    x_{t-1} that the reference computed in that precision, denoiser and
    recursion alike, puts in the same served x_t's place."""
    X = jnp.asarray(trajectory, jnp.float32)
    T = X.shape[0] - 1
    cond = jax.tree.map(jnp.asarray, cond)
    gap2, beps2 = [], []
    for lo in range(1, T + 1, block):
        ts = np.arange(lo, min(lo + block, T + 1))
        ts_p = np.concatenate([ts, np.full(block - len(ts), ts[-1])])
        a = jnp.asarray(sched["a"][ts_p], jnp.float32)
        b = jnp.asarray(sched["b"][ts_p], jnp.float32)
        tau = jnp.asarray(sched["tau"][ts_p], jnp.float32)
        g, e = _step_block(forward, params, X[ts_p], X[ts_p - 1], tau, cond,
                           a, b, dtype=dtype)
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
