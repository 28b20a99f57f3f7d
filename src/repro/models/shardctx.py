"""Activation-sharding context: lets model code express logical activation
shardings (`constrain(x, "batch", "seq", None)`) that resolve against the
launcher's mesh — and become no-ops in single-device tests.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_MESH = contextvars.ContextVar("repro_mesh", default=None)
# override for the "batch" logical axis (e.g. serving: batch over ALL axes)
_BATCH_AXES = contextvars.ContextVar("repro_batch_axes", default=None)


@contextlib.contextmanager
def batch_axes(axes):
    tok = _BATCH_AXES.set(tuple(axes))
    try:
        yield
    finally:
        _BATCH_AXES.reset(tok)

# logical activation axes -> mesh axes (with divisibility fallback)
ACT_RULES = {
    "batch": "fsdp",   # ("pod","data") multi-pod, ("data",) single-pod
    "seq": "model",    # context parallel (hidden-TP archs / long context)
    "heads": "model",
    "embed": None,
    "window": "fsdp",  # ParaTAA window-of-timesteps axis
}


@contextlib.contextmanager
def use_mesh(mesh):
    tok = _MESH.set(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _MESH.reset(tok)


def current_mesh():
    return _MESH.get()


@contextlib.contextmanager
def serving_mesh(mesh):
    """Engine-serving activation context (see repro.sampling.Placement).

    Under a SamplingEngine the REQUEST axis owns the `data` mesh dimension
    (the engine constrains the vmapped batch axis via spmd_axis_name), so
    denoiser-internal "batch" constraints — whose dim is the per-request
    window of timesteps — must not claim `data` a second time.  This context
    sets the ambient mesh for `model`-axis TP constraints while resolving
    the "batch" logical axis to replicated.
    """
    with use_mesh(mesh) as m, batch_axes(()):
        yield m


def _resolve(logical: Optional[str], dim: int, mesh):
    if logical is None:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if logical == "batch" and _BATCH_AXES.get() is not None:
        axes = tuple(a for a in _BATCH_AXES.get() if a in sizes)
        total = 1
        for a in axes:
            total *= sizes[a]
        if axes and dim % total == 0:
            return axes if len(axes) > 1 else axes[0]
        return None
    target = ACT_RULES.get(logical)
    if target is None:
        return None
    if target == "fsdp":
        axes = tuple(a for a in ("pod", "data") if a in sizes)
        total = 1
        for a in axes:
            total *= sizes[a]
        if axes and dim % total == 0:
            return axes if len(axes) > 1 else axes[0]
        if "data" in sizes and dim % sizes["data"] == 0:
            return "data"
        return None
    if target in sizes and dim % sizes[target] == 0:
        return target
    return None


def logical_spec(shape, *logical_axes) -> P:
    """The PartitionSpec ``constrain`` gives an array of ``shape`` on the
    ambient mesh (empty without one)."""
    mesh = _MESH.get()
    if mesh is None:
        return P()
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    return P(*[_resolve(ax, d, mesh) for ax, d in zip(logical_axes, shape)])


def constrain(x, *logical_axes):
    """with_sharding_constraint against the ambient mesh (no-op without one)."""
    mesh = _MESH.get()
    if mesh is None:
        return x
    spec = logical_spec(x.shape, *logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def window_constrain(x, axis: Optional[str], dim: int = 0, *,
                     replicate: bool = False):
    """Pin ``x`` row-sharded over mesh axis ``axis`` along ``dim`` — or pin
    it fully replicated (``replicate=True``).

    The ParaTAA time-axis sharding discipline (bitwise-safety contract):
    only per-row-independent passes — the window eps eval, the per-row Gram
    blocks, the per-row history apply — are sharded over ``time``; every
    cross-row reduction (suffix cumsums, global Grams, the triangular
    ``lift_k @ x``) runs on REPLICATED operands.  The collective between the
    two regimes is therefore an all-gather (exact data movement), never a
    psum of partial f32 sums, so summation order — and the bits — match the
    unsharded program.  The explicit ``replicate=True`` pins are what hold
    XLA to that contract.

    No-op when there is no ambient mesh, ``axis`` is ``None`` or absent from
    the mesh, or (sharding only) ``x.shape[dim]`` is not divisible by the
    axis size — e.g. ``seq`` mode's w=1 window, or T+1-row pytrees.
    """
    mesh = _MESH.get()
    if mesh is None or axis is None:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis not in sizes:
        return x
    spec = [None] * x.ndim
    if not replicate:
        if x.shape[dim] % sizes[axis] != 0:
            return x
        spec[dim] = axis
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
