"""DiT (Peebles & Xie 2023) — the paper's own denoiser — plus a
DiffusionWrapper that turns ANY assigned LM backbone into an eps-model over
continuous latent sequences (how `--arch qwen3-0.6b --mode parataa` runs).

DiT: class-conditional latent transformer with adaLN-zero conditioning.  The
VAE/patchify frontend is a stub: inputs are (B, N, latent_dim) latent tokens,
exactly the space the paper's sampling experiments operate in.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import pdefs
from repro.models.pdefs import ParamDef, stack_defs
from repro.models.layers import (layernorm_noaffine, mlp, mlp_def,
                                 sinusoidal_embed, sincos_positions)
from repro.models.shardctx import constrain

TEMB_DIM = 256


# ---------------------------------------------------------------------------
# DiT
# ---------------------------------------------------------------------------


def dit_defs(cfg: ArchConfig):
    d, ff = cfg.d_model, cfg.d_ff
    block = {
        "ada": ParamDef((d, 6 * d), ("embed", "cond"), init="zeros"),
        "wq": ParamDef((d, cfg.num_heads, cfg.head_dim), ("embed", "heads", None), init="lecun"),
        "wk": ParamDef((d, cfg.num_heads, cfg.head_dim), ("embed", "heads", None), init="lecun"),
        "wv": ParamDef((d, cfg.num_heads, cfg.head_dim), ("embed", "heads", None), init="lecun"),
        "wo": ParamDef((cfg.num_heads, cfg.head_dim, d), ("heads", None, "embed"), init="lecun"),
        "mlp": mlp_def(d, ff),
    }
    return {
        "in_proj": ParamDef((cfg.latent_dim, d), (None, "embed"), init="lecun"),
        "t_mlp1": ParamDef((TEMB_DIM, d), (None, "embed"), init="lecun"),
        "t_mlp2": ParamDef((d, d), (None, "embed"), init="lecun"),
        "y_embed": ParamDef((cfg.num_classes + 1, d), (None, "embed"), init="normal"),
        "blocks": stack_defs(block, cfg.num_layers),
        "final_ada": ParamDef((d, 2 * d), ("embed", "cond"), init="zeros"),
        "out_proj": ParamDef((d, cfg.latent_dim), ("embed", None), init="zeros"),
    }


def dit_init(cfg: ArchConfig, key, dtype=jnp.float32):
    return pdefs.init_params(dit_defs(cfg), key, dtype)


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def dit_apply(params, cfg: ArchConfig, latents, t, y=None, *, remat: bool = False):
    """eps prediction.  latents: (B, N, latent_dim); t: (B,) float timesteps;
    y: (B,) int class labels (None -> unconditional bucket).

    Named scopes put each part's ops under ``dit/<part>`` in the HLO
    metadata (``op_name``), so a device trace splits the step by part:
    ``embed`` (input projection, positions, t/y conditioning), ``weights``
    (slicing layer i out of stacked block weights; the serving path holds
    them per layer, ``serve.make_engine``, and has no such ops), ``ada``
    (adaLN: the conditioning projection, layer norm and modulation), ``attn``
    (q/k/v/o projections, scores, softmax, context and the gated
    residual), ``mlp`` (the MLP and its gated residual), ``final``."""
    with jax.named_scope("dit"):
        return _dit_apply(params, cfg, latents, t, y, remat=remat)


def _dit_apply(params, cfg: ArchConfig, latents, t, y, *, remat: bool):
    b, n, _ = latents.shape
    d = cfg.d_model
    with jax.named_scope("embed"):
        x = latents @ params["in_proj"]
        pos = jnp.asarray(sincos_positions(n, d), x.dtype)
        x = x + pos[None]
        x = constrain(x, "batch", None, None)

        temb = sinusoidal_embed(t, TEMB_DIM).astype(x.dtype)
        cond = jax.nn.silu(temb @ params["t_mlp1"]) @ params["t_mlp2"]
        if y is None:
            y = jnp.full((b,), cfg.num_classes, jnp.int32)  # null class
        cond = cond + jnp.take(params["y_embed"], y, axis=0)
        cond = jax.nn.silu(cond)

    def block(p, x):
        with jax.named_scope("ada"):
            ada = cond @ p["ada"]  # (B, 6d)
            s1, sc1, g1, s2, sc2, g2 = jnp.split(ada, 6, axis=-1)
            h = _modulate(layernorm_noaffine(x), s1, sc1)
        with jax.named_scope("attn"):
            x = x + g1[:, None, :] * ops.dit_attention(
                h, p["wq"], p["wk"], p["wv"], p["wo"])
        with jax.named_scope("ada"):
            h = _modulate(layernorm_noaffine(x), s2, sc2)
        with jax.named_scope("mlp"):
            return x + g2[:, None, :] * mlp(p["mlp"], h, "gelu")

    # python loop (unrolled HLO): DiT is small enough, and unrolled layers
    # are counted exactly by the dry-run's cost analysis
    fn = jax.checkpoint(block) if remat else block
    for i in range(cfg.num_layers):
        x = fn(pdefs.layer_weights(params["blocks"], i), x)
    with jax.named_scope("final"):
        fa = cond @ params["final_ada"]
        sh, sc = jnp.split(fa, 2, axis=-1)
        x = _modulate(layernorm_noaffine(x), sh, sc)
        return x @ params["out_proj"]


def dit_loss(params, cfg: ArchConfig, batch, abar_full):
    """Denoising score-matching MSE.  batch: {"latents": (B,N,L) clean,
    "t": (B,) int train timesteps, "noise": (B,N,L), "labels": (B,)}."""
    ab = abar_full[batch["t"]][:, None, None].astype(jnp.float32)
    x_t = jnp.sqrt(ab) * batch["latents"] + jnp.sqrt(1.0 - ab) * batch["noise"]
    pred = dit_apply(params, cfg, x_t.astype(batch["latents"].dtype),
                     batch["t"].astype(jnp.float32), batch["labels"], remat=True)
    return jnp.mean(jnp.square(pred.astype(jnp.float32) - batch["noise"]))


# ---------------------------------------------------------------------------
# DiffusionWrapper: any LM backbone as a latent-sequence denoiser
# ---------------------------------------------------------------------------


def wrapper_defs(cfg: ArchConfig, latent_dim: int):
    from repro.models.backbone import build_defs

    d = cfg.d_model
    return {
        "backbone": build_defs(cfg),
        "in_proj": ParamDef((latent_dim, d), (None, "embed"), init="lecun"),
        "t_mlp1": ParamDef((TEMB_DIM, d), (None, "embed"), init="lecun"),
        "t_mlp2": ParamDef((d, d), (None, "embed"), init="lecun"),
        "out_proj": ParamDef((d, latent_dim), ("embed", None), init="zeros"),
    }


def wrapper_init(cfg: ArchConfig, latent_dim: int, key, dtype=jnp.float32):
    return pdefs.init_params(wrapper_defs(cfg, latent_dim), key, dtype)


def wrapper_apply(params, cfg: ArchConfig, latents, t, *, remat: bool = False):
    """latents: (B, N, latent_dim); t: (B,) -> eps (B, N, latent_dim).

    The backbone runs in its native (causal for attention archs) mode —
    a causal denoiser over latent token sequences (diffusion-forcing style);
    ParaTAA is agnostic to the denoiser's internal structure.
    """
    from repro.models.backbone import trunk

    b, n, _ = latents.shape
    x = latents @ params["in_proj"]
    temb = sinusoidal_embed(t, TEMB_DIM).astype(x.dtype)
    cond = jax.nn.silu(temb @ params["t_mlp1"]) @ params["t_mlp2"]
    x = x + cond[:, None, :]
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (b, n))
    if cfg.m_rope:
        pos = jnp.broadcast_to(pos[None], (3, b, n))
    h, _, _ = trunk(params["backbone"], cfg, x, pos, mode="train", remat=remat)
    return h @ params["out_proj"]
