"""Parameter definitions: one source of truth for shape, init, and sharding.

A model is described as a pytree of `ParamDef` leaves.  From that single tree
we derive (a) materialized parameters (`init_params`), (b) ShapeDtypeStructs
for allocation-free lowering (`abstract_params`), and (c) PartitionSpecs
(`resolve_specs`) via MaxText-style logical-axis rules with divisibility
fallback (a logical axis only maps to a mesh axis when the dimension divides
the axis size; otherwise it is replicated).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis names, len == len(shape)
    init: str = "normal"  # normal | zeros | ones | lecun | trunc
    scale: Optional[float] = None  # stddev override for normal init
    dtype: Optional[str] = None  # override model param dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


# Logical axis -> mesh axis (or tuple of mesh axes for FSDP over pod+data).
# "fsdp" resolves to ("pod", "data") on the multi-pod mesh, ("data",) single.
LOGICAL_RULES = {
    "vocab": "model",
    "embed": "fsdp",
    "heads": "model",
    "kv_heads": "model",
    "qdim": "model",   # flattened q feature dim (hidden TP strategy)
    "kvdim": "model",
    "mlp": "model",
    "expert": "model",
    "inner": "model",  # mamba2 d_inner / rg-lru width
    "ssm_heads": "model",
    "layers": None,
    "conv": None,
    "norm": None,
    "cond": "model",   # DiT adaLN output dim (6*d)
}


def _mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def resolve_axis(logical: Optional[str], dim: int, mesh) -> Optional[object]:
    """Map a logical axis to mesh axis/axes if the dim is divisible."""
    if logical is None:
        return None
    target = LOGICAL_RULES.get(logical, None)
    if target is None:
        return None
    sizes = _mesh_axis_sizes(mesh)
    if target == "fsdp":
        fsdp_axes = tuple(a for a in ("pod", "data") if a in sizes)
        total = int(np.prod([sizes[a] for a in fsdp_axes]))
        if fsdp_axes and dim % total == 0:
            return fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
        # fall back to data-only fsdp if pod*data does not divide
        if "data" in sizes and dim % sizes["data"] == 0:
            return "data"
        return None
    if target in sizes and dim % sizes[target] == 0:
        return target
    return None


def resolve_spec(d: ParamDef, mesh) -> P:
    return P(*[resolve_axis(ax, dim, mesh) for ax, dim in zip(d.axes, d.shape)])


def resolve_specs(defs, mesh):
    return jax.tree.map(lambda d: resolve_spec(d, mesh), defs, is_leaf=is_def)


def stack_defs(defs, n: int):
    """Prepend a stacked `layers` dim of size n to every def (for lax.scan)."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.axes, d.init, d.scale, d.dtype),
        defs,
        is_leaf=is_def,
    )


def _leaf_key(key, path) -> jax.Array:
    # crc32, not hash(): str hashes are salted per process, so the same seed
    # would draw different weights in every run
    h = zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31)
    return jax.random.fold_in(key, h)


def _materialize(d: ParamDef, key, dtype) -> jax.Array:
    dt = jnp.dtype(d.dtype) if d.dtype else dtype
    if d.init == "zeros":
        return jnp.zeros(d.shape, dt)
    if d.init == "ones":
        return jnp.ones(d.shape, dt)
    if d.init == "lecun":
        fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
        std = 1.0 / np.sqrt(fan_in)
        return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dt)
    std = d.scale if d.scale is not None else 0.02
    return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dt)


def init_params(defs, key, dtype=jnp.float32):
    """Materialize a ParamDef tree into a parameter pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, d: _materialize(d, _leaf_key(key, path), dtype),
        defs,
        is_leaf=is_def,
    )


def abstract_params(defs, mesh=None, dtype=jnp.bfloat16):
    """ShapeDtypeStructs (with shardings if mesh given) — no allocation."""
    from jax.sharding import NamedSharding

    def mk(d: ParamDef):
        dt = jnp.dtype(d.dtype) if d.dtype else dtype
        if mesh is None:
            return jax.ShapeDtypeStruct(d.shape, dt)
        return jax.ShapeDtypeStruct(
            d.shape, dt, sharding=NamedSharding(mesh, resolve_spec(d, mesh))
        )

    return jax.tree.map(mk, defs, is_leaf=is_def)


def param_count(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_def)
    return int(sum(np.prod(d.shape) for d in leaves))
