"""Seeded DiT weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the plain reference
(``reference.py``) and the system under test read the same arrays and
neither takes anything the other made.  The tree has the layout the
program's DiT reads (``blocks`` stacked over layers):

    in_proj (L_in, d)   t_mlp1 (256, d)   t_mlp2 (d, d)   y_embed (C+1, d)
    blocks: ada (L, d, 6d)  wq/wk/wv (L, d, H, hd)  wo (L, H, hd, d)
            mlp: wi_gate/wi_up (L, d, ff)  wo (L, ff, d)
    final_ada (d, 2d)   out_proj (d, L_in)

Each leaf is normal with the std the configuration's ``init`` block gives.
DiT initialises ``ada``, ``final_ada`` and ``out_proj`` to zero, which makes
a random model's eps identically 0 and every solve trivial, so the
configuration gives those leaves small seeded values instead (its
``assumed`` list says why).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TEMB_DIM = 256


def leaf_shapes(cfg: dict) -> dict:
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


def leaf_std(path: str, shape, init: dict) -> float:
    """The std of one leaf: a number in ``init`` under the leaf's path, or
    ``"fan_in_2"`` for 1/sqrt(shape[-2]) (the fan-in the program's own
    initialiser reads), or a ``[gain, "sqrt_fan_in"]`` pair for
    gain/sqrt(shape[0])."""
    rule = init[path]
    if isinstance(rule, (int, float)):
        return float(rule)
    if rule == "fan_in_2":
        return 1.0 / float(np.sqrt(shape[-2]))
    gain, kind = rule
    if kind != "sqrt_fan_in":
        raise ValueError(f"unknown init rule {rule!r} for {path}")
    return float(gain) / float(np.sqrt(shape[0]))


def _paths(tree, prefix=""):
    for name, value in tree.items():
        path = f"{prefix}{name}"
        if isinstance(value, dict):
            yield from _paths(value, path + ".")
        else:
            yield path, value


def make_weights(cfg: dict, seed_key, dtype=jnp.float32):
    """All leaves from ``seed_key`` in one jitted program on the default
    device (the key is an argument, so every seed reuses one compile)."""
    shapes = leaf_shapes(cfg)
    plan = [(path, shape, leaf_std(path, shape, cfg["init"]))
            for path, shape in _paths(shapes)]

    def build(key):
        flat = {}
        for i, (path, shape, std) in enumerate(plan):
            k = jax.random.fold_in(key, i)
            flat[path] = (std * jax.random.normal(k, shape, jnp.float32)
                          ).astype(dtype)
        return _unflatten(flat)

    return jax.jit(build)(seed_key)


def _unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        *parents, leaf = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = value
    return tree


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds may pass 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")
