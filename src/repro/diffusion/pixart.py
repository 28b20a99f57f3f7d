"""PixArt-α (Chen et al. 2023, arXiv:2310.00426): a text-to-image latent
transformer, and the guided denoiser the sampling engine serves.

Inputs are (B, N, latent_dim) latent tokens, each a p x p patch of
``latent_channels`` channels in (row, column, channel) order; the caption
is the T5 encoder's output, (B, L, caption_dim), of which each row counts
its first ``tokens`` (the rest is padding).  One block:

    shift/scale/gate (msa, mlp) = table (6, d) + t_block(t_emb)   adaLN-single
    x += gate_msa * SelfAttn(LN(x) (1 + scale_msa) + shift_msa)
    x += CrossAttn(q = x, kv = caption, keys masked past ``tokens``)
    x += gate_mlp * MLP(LN(x) (1 + scale_mlp) + shift_mlp)

LN has no affine and eps 1e-6, every linear has a bias, the MLP is fc1,
GELU (tanh), fc2, and ``t_block`` (SiLU, then d -> 6d) is shared by all
blocks.  The caption enters through a projection (caption_dim -> d, GELU,
d -> d); positions are the 2-D sin-cos table over the patch grid.  The
final layer modulates by its own (2, d) table plus t_emb and projects to
p x p x 2 channels a token: eps and the learned sigma.

Named scopes sit under ``dit``, as the DiT's do, so a device trace splits
a step by part: ``embed``, ``caption``, ``ada``, ``weights`` (slicing
layer i out of stacked blocks; the serving path holds them per layer and
has no such ops, ``serve.make_engine``), ``attn`` (self-attention,
through ``ops.dit_attention``), ``xattn``, ``mlp``, ``final`` and
``guide`` (the guided adapter's doubling and combination).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import pdefs
from repro.models.layers import (layernorm_noaffine, sincos_positions_2d,
                                 sinusoidal_embed)
from repro.models.pdefs import ParamDef, stack_defs
from repro.models.shardctx import constrain

TEMB_DIM = 256


def pixart_defs(cfg: ArchConfig):
    d, ff = cfg.d_model, cfg.d_ff
    h, k = cfg.num_heads, cfg.head_dim

    def proj():
        return ParamDef((d, h, k), ("embed", "heads", None), scale=d ** -0.5)

    def head_bias():
        return ParamDef((h, k), ("heads", None), init="zeros")

    def out():
        return ParamDef((h, k, d), ("heads", None, "embed"),
                        scale=(h * k) ** -0.5)

    def bias(n, axis="embed"):
        return ParamDef((n,), (axis,), init="zeros")

    block = {
        "table": ParamDef((6, d), (None, "embed"), scale=d ** -0.5),
        "wq": proj(), "bq": head_bias(), "wk": proj(), "bk": head_bias(),
        "wv": proj(), "bv": head_bias(), "wo": out(), "bo": bias(d),
        "xq": proj(), "xbq": head_bias(), "xk": proj(), "xbk": head_bias(),
        "xv": proj(), "xbv": head_bias(),
        # PixArt zero-initialises the cross-attention's output projection
        "xo": ParamDef((h, k, d), ("heads", None, "embed"), init="zeros"),
        "xbo": bias(d),
        "fc1": ParamDef((d, ff), ("embed", "mlp"), init="lecun"),
        "b1": bias(ff, "mlp"),
        "fc2": ParamDef((ff, d), ("mlp", "embed"), init="lecun"),
        "b2": bias(d),
    }
    return {
        "x_embed": ParamDef((cfg.latent_dim, d), (None, "embed"),
                            init="lecun"),
        "x_bias": bias(d),
        "t_mlp1": ParamDef((TEMB_DIM, d), (None, "embed")),
        "t_b1": bias(d),
        "t_mlp2": ParamDef((d, d), (None, "embed")),
        "t_b2": bias(d),
        "t_block": ParamDef((d, 6 * d), ("embed", "cond")),
        "t_block_b": bias(6 * d, "cond"),
        "y_fc1": ParamDef((cfg.caption_dim, d), (None, "embed")),
        "y_b1": bias(d),
        "y_fc2": ParamDef((d, d), (None, "embed")),
        "y_b2": bias(d),
        # the learned null caption that guidance contrasts against
        "y_null": ParamDef((cfg.caption_len, cfg.caption_dim), (None, None),
                           scale=cfg.caption_dim ** -0.5),
        "blocks": stack_defs(block, cfg.num_layers),
        "final_table": ParamDef((2, d), (None, "embed"), scale=d ** -0.5),
        "out_proj": ParamDef((d, 2 * cfg.latent_dim), ("embed", None),
                             init="zeros"),
        "out_b": bias(2 * cfg.latent_dim, None),
    }


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def _gelu_mlp(x, w1, b1, w2, b2):
    return jax.nn.gelu(x @ w1 + b1, approximate=True) @ w2 + b2


def _t_embed(temb, params):
    """PixArt's timestep embedder: Linear, SiLU, Linear."""
    return jax.nn.silu(temb @ params["t_mlp1"] + params["t_b1"]) \
        @ params["t_mlp2"] + params["t_b2"]


def pixart_apply(params, cfg: ArchConfig, latents, t, cond):
    """The model's output.  latents: (B, N, latent_dim); t: (B,) float
    timesteps; cond: {"caption": (B, L, caption_dim), "tokens": (B,)
    int32}.  Returns (B, N, 2 * latent_dim): per pixel of each patch, the
    eps channels then the sigma channels (``eps_of`` takes eps)."""
    with jax.named_scope("dit"):
        return _pixart_apply(params, cfg, latents, t, cond)


def _pixart_apply(params, cfg: ArchConfig, latents, t, cond):
    b, n, _ = latents.shape
    d = cfg.d_model
    with jax.named_scope("embed"):
        x = latents @ params["x_embed"] + params["x_bias"]
        x = x + jnp.asarray(sincos_positions_2d(n, d), x.dtype)[None]
        x = constrain(x, "batch", None, None)
        temb = sinusoidal_embed(t, TEMB_DIM).astype(x.dtype)
        temb = _t_embed(temb, params)
    with jax.named_scope("ada"):
        t0 = (jax.nn.silu(temb) @ params["t_block"]
              + params["t_block_b"]).reshape(b, 6, d)
    with jax.named_scope("caption"):
        y = _gelu_mlp(cond["caption"].astype(x.dtype), params["y_fc1"],
                      params["y_b1"], params["y_fc2"], params["y_b2"])
    tokens = cond["tokens"]

    def block(p, x):
        with jax.named_scope("ada"):
            mods = p["table"][None] + t0
            s1, sc1, g1, s2, sc2, g2 = (mods[:, i] for i in range(6))
            h = _modulate(layernorm_noaffine(x), s1, sc1)
        with jax.named_scope("attn"):
            a = ops.dit_attention(h, p["wq"], p["wk"], p["wv"], p["wo"],
                                  biases=(p["bq"], p["bk"], p["bv"]))
            x = x + g1[:, None, :] * (a + p["bo"])
        with jax.named_scope("xattn"):
            x = x + ops.dit_cross_attention(
                x, y, tokens, p["xq"], p["xk"], p["xv"], p["xo"],
                biases=(p["xbq"], p["xbk"], p["xbv"])) + p["xbo"]
        with jax.named_scope("ada"):
            h = _modulate(layernorm_noaffine(x), s2, sc2)
        with jax.named_scope("mlp"):
            return x + g2[:, None, :] * _gelu_mlp(h, p["fc1"], p["b1"],
                                                  p["fc2"], p["b2"])

    for i in range(cfg.num_layers):
        x = block(pdefs.layer_weights(params["blocks"], i), x)
    with jax.named_scope("final"):
        mods = params["final_table"][None] + temb[:, None, :]
        x = _modulate(layernorm_noaffine(x), mods[:, 0], mods[:, 1])
        return x @ params["out_proj"] + params["out_b"]


def eps_of(out, cfg: ArchConfig):
    """The eps channels of the model's output: the first
    ``latent_channels`` of each pixel's 2 x ``latent_channels``."""
    b, n, _ = out.shape
    c = cfg.latent_channels
    return out.reshape(b, n, -1, 2 * c)[..., :c].reshape(b, n, -1)


def make_eps_apply(cfg: ArchConfig):
    """The engine-shaped guided denoiser: (params, x (R, N, L), taus (R,),
    cond with leading R) -> eps (R, N, L).  Evaluates 2R rows, the
    request's caption and the learned null caption under the same token
    count, and returns eps_u + s (eps_c - eps_u) on every channel
    (classifier-free guidance at ``cfg.guidance_scale``), so the sampler
    sees one denoiser."""
    scale = cfg.guidance_scale

    def eps_apply(params, x, taus, cond):
        r = x.shape[0]
        with jax.named_scope("dit"), jax.named_scope("guide"):
            null = jnp.broadcast_to(params["y_null"], cond["caption"].shape)
            both = {"caption": jnp.concatenate([cond["caption"], null]),
                    "tokens": jnp.concatenate([cond["tokens"]] * 2)}
            xx = jnp.concatenate([x, x])
            tt = jnp.concatenate([taus, taus])
        eps = eps_of(pixart_apply(params, cfg, xx, tt, both), cfg)
        with jax.named_scope("dit"), jax.named_scope("guide"):
            eps_c, eps_u = eps[:r], eps[r:]
            return eps_u + scale * (eps_c - eps_u)

    return eps_apply


#: std of a drawn caption's elements, standing in for T5-XXL's output
CAPTION_STD = 0.15


@functools.partial(jax.jit, static_argnums=1)
def draw_caption(seed, shape):
    """The caption of ``seed``: a ``shape`` (caption_len, caption_dim) f32
    array of normal elements with std ``CAPTION_STD``, made on the device;
    every row takes values, padding rows too, as T5's do."""
    return CAPTION_STD * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                           jnp.float32)


def request_condition(cfg: ArchConfig):
    """A request's per-lane condition for the engine: its caption, a
    (caption_len, caption_dim) f32 array, and its token count.  A vacant
    lane (``None``) holds a zero caption of one token."""
    shape = (cfg.caption_len, cfg.caption_dim)

    def condition(request) -> dict:
        if request is None:
            return {"caption": np.zeros(shape, np.float32),
                    "tokens": np.int32(1)}
        if request.cond is None:
            raise ValueError(f"{cfg.name} requests carry a caption "
                             f"(SampleRequest.cond)")
        caption, tokens = request.cond["caption"], request.cond["tokens"]
        if tuple(caption.shape) != shape:
            raise ValueError(f"caption shape {tuple(caption.shape)} is not "
                             f"{cfg.name}'s {shape}")
        if not 1 <= int(tokens) <= cfg.caption_len:
            raise ValueError(f"caption tokens {int(tokens)} outside "
                             f"[1, {cfg.caption_len}]")
        # a host caption stays on the host (the engine stacks a refill's
        # on the host and sends them once); one made on the device stays
        caption = caption.astype(np.float32) \
            if isinstance(caption, np.ndarray) \
            else jnp.asarray(caption, jnp.float32)
        return {"caption": caption, "tokens": np.int32(tokens)}

    return condition
