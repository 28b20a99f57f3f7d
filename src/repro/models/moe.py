"""Mixture-of-Experts layer: top-k routing with sort-based capacity dispatch.

Design (TPU-native, FLOPs-lean): instead of the Switch-style dense one-hot
dispatch einsum (which adds O(T * E * C * d) matmul FLOPs), tokens are sorted
by expert id and scattered into an (E, C, d) buffer; expert MLPs then run as
one batched (E, C, d) x (E, d, ff) matmul, and results are combined back with
a weighted scatter-add.  FLOPs ~= active-expert FLOPs only; the dispatch is
pure data movement.

Expert parallelism: the expert dim of the weight stacks is sharded over the
`model` mesh axis.  Experts are padded to a multiple of the axis size
(e.g. qwen2-moe 60 -> 64); pad experts get -inf router logits so the function
is exactly the unpadded model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.pdefs import ParamDef
from repro.models.layers import act_fn, mlp_def, mlp


def padded_experts(cfg: ArchConfig, axis: int = 16) -> int:
    e = cfg.num_experts
    return int(np.ceil(e / axis) * axis)


def moe_def(cfg: ArchConfig):
    d, ff = cfg.d_model, cfg.moe_d_ff
    ep = padded_experts(cfg)
    defs = {
        "router": ParamDef((d, ep), ("embed", None), init="lecun", dtype="float32"),
        "we_gate": ParamDef((ep, d, ff), ("expert", "embed", None), init="lecun"),
        "we_up": ParamDef((ep, d, ff), ("expert", "embed", None), init="lecun"),
        "we_down": ParamDef((ep, ff, d), ("expert", None, "embed"), init="lecun"),
    }
    if cfg.num_shared_experts:
        # shared experts fused into one wider always-on MLP
        defs["shared"] = mlp_def(d, ff * cfg.num_shared_experts)
    return defs


def router_probs(params, cfg: ArchConfig, x):
    """x: (T, d) -> (weights (T,K) f32, ids (T,K) i32, aux_loss scalar)."""
    ep = params["router"].shape[1]
    logits = x.astype(jnp.float32) @ params["router"]  # (T, EP)
    if ep > cfg.num_experts:  # mask pad experts
        pad_mask = jnp.arange(ep) >= cfg.num_experts
        logits = jnp.where(pad_mask[None, :], -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.moe_top_k)  # (T, K)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # load-balancing aux loss (Switch-style): E * sum_e f_e * p_e
    dispatch_frac = jnp.zeros((ep,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    dispatch_frac = dispatch_frac / (ids.size)
    mean_probs = jnp.mean(probs, axis=0)
    aux = cfg.num_experts * jnp.sum(dispatch_frac * mean_probs)
    return weights, ids, aux


def moe_apply(params, cfg: ArchConfig, x, capacity: int | None = None):
    """x: (B, S, d) -> (y, aux_loss).

    With an ambient mesh, dispatch runs expert-parallel under shard_map:
    each model-rank routes its (model-replicated) local tokens to the
    experts it owns and the partial outputs are psum'd over `model` — ONE
    collective per layer.  (GSPMD cannot partition the data-dependent
    sort/scatter dispatch and falls back to replicating the token buffers,
    which made the MoE train cells collective-bound by 30x; see
    EXPERIMENTS.md §Perf.)  Without a mesh (tests, single-device) the plain
    local path runs.
    """
    from repro.models.shardctx import current_mesh

    mesh = current_mesh()
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
        dp = 1
        for a in dp_axes:
            dp *= sizes[a]
        msize = sizes.get("model", 1)
        ep = params["we_gate"].shape[0]
        if (msize > 1 and x.shape[0] % dp == 0 and ep % msize == 0):
            return _moe_shard_map(params, cfg, x, mesh, dp_axes, msize)
    return _moe_local(params, cfg, x, capacity)


def _moe_local(params, cfg: ArchConfig, x, capacity: int | None = None):
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    weights, ids, aux = router_probs(params, cfg, xt)
    k = cfg.moe_top_k
    ep = params["we_gate"].shape[0]
    if capacity is None:
        capacity = int(np.ceil(t * k / ep * cfg.moe_capacity_factor / 8) * 8)
        capacity = max(capacity, 8)

    flat_ids = ids.reshape(-1)  # (T*K,)
    flat_w = weights.reshape(-1)
    token_of_slot = jnp.arange(t * k) // k

    # sort slots by expert; within-expert rank via exclusive-cumsum of counts
    order = jnp.argsort(flat_ids, stable=True)  # (T*K,)
    sorted_ids = flat_ids[order]
    counts = jnp.zeros((ep,), jnp.int32).at[flat_ids].add(1)
    starts = jnp.cumsum(counts) - counts  # exclusive cumsum
    rank = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_ids]
    keep = rank < capacity
    dest = jnp.where(keep, sorted_ids * capacity + rank, ep * capacity)  # drop -> OOB

    # scatter tokens into (E*C, d) buffer (extra row swallows drops)
    buf = jnp.zeros((ep * capacity + 1, d), x.dtype)
    buf = buf.at[dest].set(xt[token_of_slot[order]], mode="drop")
    buf = buf[: ep * capacity].reshape(ep, capacity, d)

    # expert MLPs as batched matmuls (the only FLOPs-heavy part)
    act = act_fn(cfg.act)
    g = act(jnp.einsum("ecd,edf->ecf", buf, params["we_gate"]))
    u = jnp.einsum("ecd,edf->ecf", buf, params["we_up"])
    yb = jnp.einsum("ecf,efd->ecd", g * u, params["we_down"])  # (E, C, d)

    # combine: gather back + weighted scatter-add over tokens
    yb = yb.reshape(ep * capacity, d)
    y_slot = jnp.where(keep[:, None], yb[jnp.clip(dest, 0, ep * capacity - 1)], 0.0)
    w_sorted = flat_w[order]
    out = jnp.zeros((t, d), jnp.float32)
    out = out.at[token_of_slot[order]].add(y_slot.astype(jnp.float32) * w_sorted[:, None])

    if cfg.num_shared_experts:
        out = out + mlp(params["shared"], xt, cfg.act).astype(jnp.float32)
    return out.astype(x.dtype).reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map)
# ---------------------------------------------------------------------------


def _moe_shard_map(params, cfg: ArchConfig, x, mesh, dp_axes, msize: int):
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    ep = params["we_gate"].shape[0]
    e_loc = ep // msize
    dp = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in dp_axes:
        dp *= sizes[a]
    t_loc = (b // dp) * s
    k = cfg.moe_top_k
    c_loc = int(np.ceil(t_loc * k / ep * cfg.moe_capacity_factor / 8) * 8)
    c_loc = max(c_loc, 8)

    def local_fn(xl, router, wg, wu, wd):
        bl = xl.shape[0]
        t = bl * s
        xt = xl.reshape(t, d)
        weights, ids, aux = router_probs({"router": router}, cfg, xt)
        aux = jax.lax.pmean(aux, dp_axes)

        m_idx = jax.lax.axis_index("model")
        lo = m_idx * e_loc
        flat_ids = ids.reshape(-1)
        flat_w = weights.reshape(-1)
        tok = jnp.arange(t * k) // k
        mine = (flat_ids >= lo) & (flat_ids < lo + e_loc)
        loc_ids = jnp.where(mine, flat_ids - lo, e_loc)  # e_loc = drop bucket

        order = jnp.argsort(loc_ids, stable=True)
        sorted_ids = loc_ids[order]
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[loc_ids].add(1)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_ids]
        keep = (sorted_ids < e_loc) & (rank < c_loc)
        dest = jnp.where(keep, sorted_ids * c_loc + rank, e_loc * c_loc)

        buf = jnp.zeros((e_loc * c_loc + 1, d), xl.dtype)
        buf = buf.at[dest].set(xt[tok[order]], mode="drop")
        buf = buf[: e_loc * c_loc].reshape(e_loc, c_loc, d)

        act = act_fn(cfg.act)
        g = act(jnp.einsum("ecd,edf->ecf", buf, wg))
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        yb = jnp.einsum("ecf,efd->ecd", g * u, wd).reshape(e_loc * c_loc, d)

        y_slot = jnp.where(keep[:, None],
                           yb[jnp.clip(dest, 0, e_loc * c_loc - 1)], 0.0)
        w_sorted = flat_w[order]
        out = jnp.zeros((t, d), jnp.float32)
        out = out.at[tok[order]].add(y_slot.astype(jnp.float32) * w_sorted[:, None])
        # the ONE collective: combine expert partials across the model axis
        out = jax.lax.psum(out, "model")
        return out.astype(xl.dtype).reshape(bl, s, d), aux

    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp_spec, None, None), P()),
    )(x, params["router"], params["we_gate"], params["we_up"], params["we_down"])

    if cfg.num_shared_experts:
        out = out + mlp(params["shared"], x.reshape(b * s, d), cfg.act).reshape(b, s, d)
    return out, aux
