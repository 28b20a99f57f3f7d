"""DiT (Peebles & Xie 2023) as the benchmark runs it: the configuration's
``arch`` ``dit-xl``, class-conditional, served by the program's DiT.

Everything the harness needs that depends on the model lives here (the
contract is listed at the top of ``harness.py``): the program's
architecture and weight layout, the seeded weight tree, the engine, the
request's condition (a class label), the plain reference forward and the
FLOPs of one evaluation.

The reference forward is written from the DiT paper in straightforward
``jax.numpy`` and imports nothing of the program.  It follows the
architecture the program serves, which departs from the paper in four
places (each a key the configuration lists in ``reduced``): a 1-D sin-cos
position table over the N tokens instead of the 2-D one, a gated GELU MLP
(GeGLU, two input matrices) instead of the plain one, no biases, and an
eps-only output (no learned-sigma channels).  It scans over the stacked
layers, so that it compiles in seconds at full depth, and computes in
float32 with ``precision="highest"`` unless a lower ``dtype`` is asked for
(the control: everything in that dtype).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

TEMB_DIM = 256
LN_EPS = 1e-6

#: the configuration's sizes that the program's ArchConfig takes
SIZE_KEYS = ("num_layers", "d_model", "num_heads", "head_dim", "d_ff",
             "latent_dim", "num_tokens", "num_classes")


def program_arch(cfg: dict):
    """The program's ArchConfig for this configuration: its registered
    architecture with every size taken from the configuration file."""
    from repro.configs.registry import get_arch
    sizes = {k: cfg[k] for k in SIZE_KEYS}
    return dataclasses.replace(get_arch(cfg["arch"]), **sizes)


def program_layout(arch) -> dict:
    """The weight shapes the program's DiT reads (``dit_defs``), as a tree
    of shape tuples."""
    from repro.diffusion import dit
    from repro.models.pdefs import is_def
    return jax.tree.map(lambda d: tuple(d.shape), dit.dit_defs(arch),
                        is_leaf=is_def)


def leaf_shapes(cfg: dict) -> dict:
    """The weight tree, ``blocks`` stacked over layers:

        in_proj (L_in, d)   t_mlp1 (256, d)   t_mlp2 (d, d)   y_embed (C+1, d)
        blocks: ada (L, d, 6d)  wq/wk/wv (L, d, H, hd)  wo (L, H, hd, d)
                mlp: wi_gate/wi_up (L, d, ff)  wo (L, ff, d)
        final_ada (d, 2d)   out_proj (d, L_in)

    DiT initialises ``ada``, ``final_ada`` and ``out_proj`` to zero, which
    makes a random model's eps identically 0 and every solve trivial, so
    the configuration's ``init`` block gives those leaves small seeded
    values instead (its ``assumed`` list says why)."""
    d, h, hd = cfg["d_model"], cfg["num_heads"], cfg["head_dim"]
    ff, n_layers = cfg["d_ff"], cfg["num_layers"]
    lat, ncls = cfg["latent_dim"], cfg["num_classes"]
    return {
        "in_proj": (lat, d),
        "t_mlp1": (TEMB_DIM, d),
        "t_mlp2": (d, d),
        "y_embed": (ncls + 1, d),
        "blocks": {
            "ada": (n_layers, d, 6 * d),
            "wq": (n_layers, d, h, hd),
            "wk": (n_layers, d, h, hd),
            "wv": (n_layers, d, h, hd),
            "wo": (n_layers, h, hd, d),
            "mlp": {"wi_gate": (n_layers, d, ff), "wi_up": (n_layers, d, ff),
                    "wo": (n_layers, ff, d)},
        },
        "final_ada": (d, 2 * d),
        "out_proj": (d, lat),
    }


def make_engine(params, arch, coeffs, spec, placement):
    """The program's serving engine for this DiT (``serve.make_engine``)."""
    from repro.launch import serve
    return serve.make_engine(params, arch, coeffs, spec, placement=placement)


def draw_condition(rng: np.random.Generator, cfg: dict) -> int:
    """A class label, uniform over the configuration's classes."""
    return int(rng.integers(cfg["num_classes"]))


def warm_condition(i: int, cfg: dict) -> int:
    """The label of the ``i``-th warm-up request."""
    return i % cfg["num_classes"]


def request_kwargs(cond: int) -> dict:
    """``SampleRequest`` keyword arguments that carry the condition."""
    return {"label": cond}


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


def forward(params, x, t, cond, *, dtype=jnp.float32):
    """The plain reference: eps for a block of rows.  x: (R, N, L_in);
    t: (R,) float timesteps; cond: one request's label, or (R,) labels.
    Returns float32 (R, N, L_in)."""
    prec = "highest" if dtype == jnp.float32 else "default"
    mm = functools.partial(jnp.einsum, precision=prec)
    y = jnp.broadcast_to(jnp.asarray(cond, jnp.int32), x.shape[:1])
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


def forward_flops(cfg: dict, *, paper: bool = False) -> float:
    """FLOPs (2 per multiply-add) of one DiT forward row at the
    configuration's token count, attention scores included.

    ``paper=False`` counts the block as the program runs it: a gated MLP
    with ``d_ff``-wide gate and up matrices, and ``latent_dim`` output
    channels.  ``paper=True`` counts the block of the DiT paper (plain
    two-matrix MLP, learned-sigma output of twice the channels, and the
    adaLN modulation products the paper's counter includes), whose
    multiply-adds are the paper's Gflops column: 118.6 G for DiT-XL/2 at
    256 tokens and 524.6 G at 1024."""
    d, n, L = cfg["d_model"], cfg["num_tokens"], cfg["num_layers"]
    ff, lat = cfg["d_ff"], cfg["latent_dim"]
    hk = cfg["num_heads"] * cfg["head_dim"]
    mlp_mats = 2 if paper else 3
    out_ch = 2 * lat if paper else lat
    per_token = 3 * d * hk + hk * d + mlp_mats * d * ff
    attention = 2 * n * n * hk                  # q k^T and p v
    ada = d * 6 * d                             # once per row
    modulate = 2 * n * d if paper else 0
    layer = n * per_token + attention + ada + modulate
    embed = n * lat * d + TEMB_DIM * d + d * d + d * 2 * d + n * d * out_ch
    if paper:
        embed += n * d                          # final modulation
    return 2.0 * (L * layer + embed)
