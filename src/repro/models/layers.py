"""Common layers: norms, MLPs, RoPE / M-RoPE, embeddings."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.pdefs import ParamDef


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_def(d: int):
    return {"scale": ParamDef((d,), ("norm",), init="ones")}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def layernorm_noaffine(x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


# ---------------------------------------------------------------------------
# Dense / MLP
# ---------------------------------------------------------------------------


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def mlp_def(d: int, ff: int):
    """Gated MLP (SwiGLU / GeGLU)."""
    return {
        "wi_gate": ParamDef((d, ff), ("embed", "mlp"), init="lecun"),
        "wi_up": ParamDef((d, ff), ("embed", "mlp"), init="lecun"),
        "wo": ParamDef((ff, d), ("mlp", "embed"), init="lecun"),
    }


def mlp(params, x, act: str = "silu"):
    g = act_fn(act)(x @ params["wi_gate"])
    y = (g * (x @ params["wi_up"])) @ params["wo"]
    return y


# ---------------------------------------------------------------------------
# RoPE (+ multimodal M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta), jnp.float32)  # (d/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, d/2)
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_m_rope(x, positions3, theta: float, sections):
    """M-RoPE (qwen2-vl): positions3 (3, B, S) for (t, h, w); `sections` sums
    to head_dim // 2, each section rotates with its own position stream."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta), jnp.float32)  # (d/2,)
    # section id per frequency index
    sec_id = np.repeat(np.arange(len(sections)), sections)  # (d/2,)
    pos = positions3.astype(jnp.float32)[sec_id, :, :]  # (d/2, B, S)
    ang = jnp.moveaxis(pos, 0, -1) * freqs  # (B, S, d/2)
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Timestep embedding (diffusion)
# ---------------------------------------------------------------------------


def sinusoidal_embed(t, dim: int, max_period: float = 10_000.0):
    """t: (B,) float; -> (B, dim)."""
    half = dim // 2
    freqs = jnp.exp(-np.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def sincos_positions(n: int, dim: int) -> np.ndarray:
    """Fixed 1-D sincos position table (n, dim)."""
    half = dim // 2
    freqs = np.exp(-np.log(10_000.0) * np.arange(half) / half)
    ang = np.arange(n)[:, None] * freqs[None, :]
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1).astype(np.float32)


def sincos_positions_2d(n: int, dim: int) -> np.ndarray:
    """Fixed 2-D sincos table (n, dim) over a square grid of n tokens in
    row-major order (MAE / DiT / PixArt at pe scale 1): the first dim/2
    columns embed a token's column index, the rest its row index, each as
    [sin, cos] of index * 10000^(-i / (dim/4))."""
    side = int(round(np.sqrt(n)))
    assert side * side == n, f"{n} tokens are not a square grid"
    quarter = dim // 4
    freqs = 1.0 / 10_000.0 ** (np.arange(quarter) / quarter)

    def half(pos):
        ang = pos[:, None] * freqs[None, :]
        return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)

    rows, cols = np.divmod(np.arange(n, dtype=np.float64), side)
    return np.concatenate([half(cols), half(rows)], axis=-1).astype(
        np.float32)
