"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the plain reference and
the system under test read the same arrays and neither takes anything the
other made.  The model module (``models/<arch>.py``) gives the tree's
shapes, ``leaf_shapes(cfg)``, in the layout the program reads; each leaf
is normal with the std the configuration's ``init`` block gives, drawn
from ``fold_in(key, i)`` for the i-th leaf in the tree's order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


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


def make_weights(shapes: dict, init: dict, seed_key, dtype=jnp.float32):
    """All leaves of the tree ``shapes`` (nested dicts of shape tuples)
    from ``seed_key`` in one jitted program on the default device (the key
    is an argument, so every seed reuses one compile), each with its std
    from ``init``."""
    plan = [(path, shape, leaf_std(path, shape, init))
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
