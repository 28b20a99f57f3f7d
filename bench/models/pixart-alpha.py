"""PixArt-α XL/2 (Chen et al. 2023, arXiv:2310.00426) as the benchmark runs
it: the configuration's ``arch`` ``pixart-alpha``, text-to-image, served
guided by the program's PixArt.

Everything the harness needs that depends on the model lives here (the
contract is listed at the top of ``harness.py``): the program's
architecture and weight layout, the seeded weight tree, the engine, the
request's condition (a caption), the plain reference forward and the FLOPs
of one served row; beside them the FLOPs and HBM bytes of one call of the
cross-attention kernel ``_dit_xflash``, which ``xattn_roofline.t2i`` reads.

A caption stands in for T5-XXL's output: a (120, 4096) array drawn from
the condition's ``caption_seed`` by the program's one caption drawer
(``pixart.draw_caption``, std 0.15, an assumption of the configuration
file) through ``caption_embedding``, which both the request the program
serves and the reference call, and a token count; rows past it are
padding, as T5 pads to 120, and take values too (the mask must hide them).

The reference forward is written from the paper and the public code
(``diffusion/model/nets/PixArt.py``, ``PixArt_blocks.py``) in
straightforward ``jax.numpy``; of the program it takes only that input
data, the caption.  Per
block, adaLN-single (a shared ``t_block`` plus the block's (6, d) table),
self-attention, cross-attention to the projected caption with keys past
the token count masked and no norm before it, and a GELU (tanh) MLP, each
linear with a bias; 2-D sin-cos positions; a final layer with its own
(2, d) table; learned sigma (the model's 8 channels a pixel, of which eps
is the first 4).  Guidance: eps_u + 4.5 (eps_c - eps_u) on every channel,
the null caption under the request's own token count.  It scans over the
stacked layers and computes in float32 with ``precision="highest"`` unless
a lower ``dtype`` is asked for (the control: everything in that dtype).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

TEMB_DIM = 256
LN_EPS = 1e-6
NEG_INF = -1e30

#: the caption's shape (T5's max_length x T5-XXL's width)
CAPTION_SHAPE = (120, 4096)
#: token counts are drawn uniform over [MIN_TOKENS, 120]
MIN_TOKENS = 10
GUIDANCE_SCALE = 4.5

#: the configuration's sizes that the program's ArchConfig takes
SIZE_KEYS = ("num_layers", "d_model", "num_heads", "head_dim", "d_ff",
             "latent_dim", "num_tokens", "latent_channels", "caption_len",
             "caption_dim", "guidance_scale")


def program_arch(cfg: dict):
    """The program's ArchConfig for this configuration: its registered
    architecture with every size taken from the configuration file."""
    from repro.configs.registry import get_arch
    if (cfg["caption_len"], cfg["caption_dim"]) != CAPTION_SHAPE or \
            cfg["guidance_scale"] != GUIDANCE_SCALE:
        raise SystemExit(f"{cfg['name']}: captions {CAPTION_SHAPE} and "
                         f"guidance {GUIDANCE_SCALE} are this module's")
    sizes = {k: cfg[k] for k in SIZE_KEYS}
    return dataclasses.replace(get_arch(cfg["arch"]), **sizes)


def program_layout(arch) -> dict:
    """The weight shapes the program's PixArt reads (``pixart_defs``), as
    a tree of shape tuples."""
    from repro.diffusion import pixart
    from repro.models.pdefs import is_def
    return jax.tree.map(lambda d: tuple(d.shape), pixart.pixart_defs(arch),
                        is_leaf=is_def)


def leaf_shapes(cfg: dict) -> dict:
    """The weight tree, ``blocks`` stacked over layers (H heads of K):

        x_embed (L_in, d) x_bias (d)      t_mlp1 (256, d) t_mlp2 (d, d)
        t_block (d, 6d)                   y_fc1 (C, d) y_fc2 (d, d)
        y_null (120, C)  the learned null caption
        blocks: table (L, 6, d)  wq/wk/wv, xq/xk/xv (L, d, H, K) with
                (L, H, K) biases; wo, xo (L, H, K, d) with (L, d) biases;
                fc1 (L, d, ff) fc2 (L, ff, d) with biases
        final_table (2, d)  out_proj (d, 2 L_in) out_b (2 L_in)

    PixArt zero-initialises the cross-attention's and the final layer's
    output projections and every bias, which would make a random model's
    caption and eps vanish, so the configuration's ``init`` block gives
    those leaves seeded values (its ``assumed`` list says why)."""
    d, h, k = cfg["d_model"], cfg["num_heads"], cfg["head_dim"]
    ff, n_layers, lat = cfg["d_ff"], cfg["num_layers"], cfg["latent_dim"]
    cl, cd = cfg["caption_len"], cfg["caption_dim"]
    L = n_layers
    return {
        "x_embed": (lat, d), "x_bias": (d,),
        "t_mlp1": (TEMB_DIM, d), "t_b1": (d,),
        "t_mlp2": (d, d), "t_b2": (d,),
        "t_block": (d, 6 * d), "t_block_b": (6 * d,),
        "y_fc1": (cd, d), "y_b1": (d,),
        "y_fc2": (d, d), "y_b2": (d,),
        "y_null": (cl, cd),
        "blocks": {
            "table": (L, 6, d),
            "wq": (L, d, h, k), "bq": (L, h, k),
            "wk": (L, d, h, k), "bk": (L, h, k),
            "wv": (L, d, h, k), "bv": (L, h, k),
            "wo": (L, h, k, d), "bo": (L, d),
            "xq": (L, d, h, k), "xbq": (L, h, k),
            "xk": (L, d, h, k), "xbk": (L, h, k),
            "xv": (L, d, h, k), "xbv": (L, h, k),
            "xo": (L, h, k, d), "xbo": (L, d),
            "fc1": (L, d, ff), "b1": (L, ff),
            "fc2": (L, ff, d), "b2": (L, d),
        },
        "final_table": (2, d),
        "out_proj": (d, 2 * lat), "out_b": (2 * lat,),
    }


def make_engine(params, arch, coeffs, spec, placement):
    """The program's serving engine for PixArt (``serve.make_engine``)."""
    from repro.launch import serve
    return serve.make_engine(params, arch, coeffs, spec, placement=placement)


def draw_condition(rng: np.random.Generator, cfg: dict) -> dict:
    """A caption: its seed and its token count (uniform over 10-120)."""
    return {"caption_seed": int(rng.integers(2 ** 31 - 1)),
            "tokens": int(rng.integers(MIN_TOKENS, cfg["caption_len"] + 1))}


def warm_condition(i: int, cfg: dict) -> dict:
    """The caption of the ``i``-th warm-up request."""
    return {"caption_seed": i, "tokens": cfg["caption_len"] - i}


def caption_embedding(seed):
    """The (120, 4096) caption of ``seed``, by the program's one caption
    drawer: input data, the same bits for the request and the reference."""
    from repro.diffusion import pixart
    return pixart.draw_caption(seed, CAPTION_SHAPE)


def request_kwargs(cond: dict) -> dict:
    """``SampleRequest`` keyword arguments that carry the condition: the
    caption and its token count."""
    return {"cond": {"caption": caption_embedding(
                         np.int32(cond["caption_seed"])),
                     "tokens": np.int32(cond["tokens"])}}


def _layernorm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS)


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(float(np.sqrt(2.0 / np.pi))
                                     * (x + 0.044715 * x ** 3)))


def _timestep_embedding(t, dim: int = TEMB_DIM, max_period: float = 1e4):
    """PixArt's TimestepEmbedder frequencies: [cos, sin] of t * f_i."""
    half = dim // 2
    freqs = jnp.exp(-np.log(max_period) * jnp.arange(half) / half)
    args = t[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _positions(n: int, d: int):
    """PixArt's ``get_2d_sincos_pos_embed`` at pe scale 1 over the square
    grid of n patches: row-major tokens; the first d/2 columns from the
    grid's w coordinate (the column index), the rest from h, each
    [sin, cos] of pos * 1/10000^(i / (d/4))."""
    side = int(round(np.sqrt(n)))
    grid_w, grid_h = np.meshgrid(np.arange(side, dtype=np.float64),
                                 np.arange(side, dtype=np.float64))

    def one_d(pos):
        omega = 1.0 / 10000 ** (np.arange(d // 4, dtype=np.float64)
                                / (d / 4))
        out = np.einsum("m,d->md", pos.reshape(-1), omega)
        return np.concatenate([np.sin(out), np.cos(out)], axis=1)

    return np.concatenate([one_d(grid_w), one_d(grid_h)], axis=1)


def _eps_pair(p, x, t, y, tokens, mm, dtype):
    """The model's eps for rows x (R, N, L_in) at t (R,), each row under
    the projected caption y (R, M, d) of which it counts ``tokens`` (R,)."""
    n, d = x.shape[1], p["x_embed"].shape[1]
    h = mm("rnl,ld->rnd", x, p["x_embed"]) + p["x_bias"] \
        + jnp.asarray(_positions(n, d), dtype)[None]
    temb = _timestep_embedding(t.astype(jnp.float32)).astype(dtype)
    temb = mm("re,ed->rd", jax.nn.silu(mm("rf,fe->re", temb, p["t_mlp1"])
                                       + p["t_b1"]), p["t_mlp2"]) + p["t_b2"]
    t0 = mm("rd,de->re", jax.nn.silu(temb), p["t_block"]) + p["t_block_b"]
    t0 = t0.reshape(t0.shape[0], 6, d)
    valid = jnp.arange(y.shape[1])[None, :] < tokens[:, None]   # (R, M)

    scale = np.asarray(1.0 / np.sqrt(p["blocks"]["wq"].shape[-1]), dtype)

    def attend(q, k, v, mask=None):
        s = mm("rnhk,rmhk->rhnm", q, k) * scale
        if mask is not None:
            s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        return mm("rhnm,rmhk->rnhk", jax.nn.softmax(s, axis=-1), v)

    def layer(h, w):
        mods = w["table"][None] + t0
        s1, sc1, g1, s2, sc2, g2 = (mods[:, i] for i in range(6))
        u = _modulate(_layernorm(h), s1, sc1)
        q, k, v = (mm("rnd,dhk->rnhk", u, w[f"w{c}"]) + w[f"b{c}"]
                   for c in "qkv")
        o = mm("rnhk,hkd->rnd", attend(q, k, v), w["wo"]) + w["bo"]
        h = h + g1[:, None, :] * o
        q = mm("rnd,dhk->rnhk", h, w["xq"]) + w["xbq"]
        k, v = (mm("rmd,dhk->rmhk", y, w[f"x{c}"]) + w[f"xb{c}"]
                for c in "kv")
        h = h + mm("rnhk,hkd->rnd", attend(q, k, v, valid), w["xo"]) \
            + w["xbo"]
        u = _modulate(_layernorm(h), s2, sc2)
        m = _gelu_tanh(mm("rnd,df->rnf", u, w["fc1"]) + w["b1"])
        h = h + g2[:, None, :] * (mm("rnf,fd->rnd", m, w["fc2"]) + w["b2"])
        return h, None

    h, _ = jax.lax.scan(layer, h, p["blocks"])
    mods = p["final_table"][None] + temb[:, None, :]
    out = mm("rnd,dl->rnl", _modulate(_layernorm(h), mods[:, 0], mods[:, 1]),
             p["out_proj"]) + p["out_b"]
    c = out.shape[-1] // 8                      # 2 x 4 channels a pixel
    return out.reshape(out.shape[:2] + (c, 8))[..., :4].reshape(x.shape)


def forward(params, x, t, cond, *, dtype=jnp.float32, guidance=None):
    """The plain reference: guided eps for a block of rows.  x: (R, N,
    L_in); t: (R,) float timesteps; cond: one request's
    ``{"caption_seed", "tokens"}``.  Computes the conditional and the
    unconditional (null caption, same token count) eps and returns
    eps_u + s (eps_c - eps_u), s = ``guidance`` (default 4.5; 1 gives the
    conditional eps alone).  Returns float32 (R, N, L_in)."""
    prec = "highest" if dtype == jnp.float32 else "default"
    mm = functools.partial(jnp.einsum, precision=prec)
    s = GUIDANCE_SCALE if guidance is None else guidance
    p = jax.tree.map(lambda w: w.astype(dtype), params)
    r = x.shape[0]
    x = x.astype(dtype)
    tokens = jnp.broadcast_to(jnp.asarray(cond["tokens"], jnp.int32), (r,))
    caption = caption_embedding(cond["caption_seed"]).astype(dtype)

    def project(c):
        y = _gelu_tanh(mm("mc,cd->md", c, p["y_fc1"]) + p["y_b1"])
        return jnp.broadcast_to(mm("md,de->me", y, p["y_fc2"]) + p["y_b2"],
                                (r,) + y.shape)

    eps = _eps_pair(p, jnp.concatenate([x, x]), jnp.concatenate([t, t]),
                    jnp.concatenate([project(caption), project(p["y_null"])]),
                    jnp.concatenate([tokens, tokens]), mm, dtype)
    eps_c, eps_u = eps[:r], eps[r:]
    return (eps_u + s * (eps_c - eps_u)).astype(jnp.float32)


def forward_flops(cfg: dict) -> float:
    """FLOPs (2 per multiply-add) of one served row: two evaluations, the
    caption's and the null caption's, at the configuration's token count.
    Per layer: q/k/v/o over the N image tokens, scores and context over
    N x N, the cross-attention's q/o over N and k/v over the caption's
    120 rows, its scores and context over N x 120, and the MLP; per
    evaluation: the caption projection, the timestep embedder, t_block,
    the input embedding and the final projection."""
    d, n, L = cfg["d_model"], cfg["num_tokens"], cfg["num_layers"]
    ff, lat = cfg["d_ff"], cfg["latent_dim"]
    m, c = cfg["caption_len"], cfg["caption_dim"]
    hk = cfg["num_heads"] * cfg["head_dim"]
    layer = (n * 4 * d * hk + 2 * n * n * hk           # self-attention
             + n * 2 * d * hk + m * 2 * d * hk + 2 * n * m * hk   # cross
             + n * 2 * d * ff)                           # MLP
    once = (m * (c * d + d * d) + TEMB_DIM * d + d * d + d * 6 * d
            + n * lat * d + n * d * 2 * lat)
    return 2.0 * 2 * (L * layer + once)


def xflash_flops(cfg: dict, rows: int) -> float:
    """FLOPs of one ``_dit_xflash`` call over ``rows`` evaluated rows: the
    scores and the context of N queries against the caption's 120 keys,
    per head."""
    hk = cfg["num_heads"] * cfg["head_dim"]
    return 2.0 * 2 * rows * cfg["num_tokens"] * cfg["caption_len"] * hk


def xflash_bytes(cfg: dict, rows: int) -> float:
    """HBM bytes of one ``_dit_xflash`` call: the f32 context it writes.
    Its operands, q, k and v (bf16, keys padded to 128) and the int32
    lengths, reach it from VMEM: in the step program a TPU v5e compiles
    and runs (the optimized HLO its profile keeps), all four operands of
    each of the 28 calls have ``S(1)`` layouts and the output ``S(0)``, so
    no HBM byte moves in; counted as HBM bytes they would put the kernel
    above its roofline (a call takes ~147 us there: 123 MB would be
    835 GB/s, above the chip's 819)."""
    hk, n = cfg["num_heads"] * cfg["head_dim"], cfg["num_tokens"]
    return float(rows * hk * n * 4)
