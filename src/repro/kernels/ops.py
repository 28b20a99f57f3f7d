"""jit'd public wrappers for the Pallas kernels.

Dispatch: `use_pallas=None` (default) auto-selects — the compiled kernels on
TPU backends, the pure-jnp references on CPU (XLA:CPU cannot lower TPU
pallas_call; interpret mode is for correctness tests, not speed).  Tests
pass use_pallas=True + interpret=True explicitly.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import (
    dit_cross_flash_attention as _dit_cross_flash_attention,
    dit_flash_attention as _dit_flash_attention,
    flash_attention as _flash_attention)
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan
from repro.kernels.rglru_scan import rglru_scan_kernel as _rglru_scan
from repro.kernels.taa_update import (taa_gram as _taa_gram,
                                      taa_apply as _taa_apply,
                                      taa_round as _taa_round_kernel)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pick(use_pallas: Optional[bool]) -> bool:
    return _on_tpu() if use_pallas is None else use_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "use_pallas", "interpret"))
def attention(q, k, v, *, causal: bool = True, window: int = 0,
              use_pallas: Optional[bool] = None, interpret: bool = False):
    """q: (B,H,S,D); k, v: (B,H,T,D) -> (B,H,S,D)."""
    if _pick(use_pallas):
        return _flash_attention(q, k, v, causal=causal, window=window,
                                interpret=interpret)
    return _ref.attention_ref(q, k, v, causal=causal, window=window)


def _dit_attention_ref_t(qt, kt, vt):
    """``ref.dit_attention_ref`` in the kernel's (H, B, K, N) layout."""
    t = functools.partial(jnp.transpose, axes=(1, 3, 0, 2))
    ctx = _ref.dit_attention_ref(t(qt), t(kt), t(vt))
    return jnp.transpose(ctx, (2, 0, 3, 1)).astype(qt.dtype)


def _dit_spec(shape):
    """The kernel's block of (H, B, K, N) on each device of the ambient
    mesh: heads over `model`, rows over the batch axes.  A serving mesh
    keeps the batch axes for the request axis; there the rows go over
    `time` where the mesh has it and it divides them, the rule by which
    ParaTAA pins its window rows (``window_constrain``), so each time shard
    attends only its own rows."""
    from repro.models.shardctx import current_mesh, logical_spec
    mesh = current_mesh()
    if mesh is None:
        return P()
    heads, rows, _, _ = logical_spec(shape, "heads", "batch", None, None)
    time = dict(zip(mesh.axis_names, mesh.devices.shape)).get("time")
    if rows is None and time and shape[1] % time == 0:
        rows = "time"
    return P(heads, rows, None, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dit_flash(qt, kt, vt, interpret):
    return _per_device(functools.partial(_dit_flash_attention,
                                         interpret=interpret),
                       qt, kt, vt, spec=_dit_spec(qt.shape))


def _dit_flash_fwd(qt, kt, vt, interpret):
    return _dit_flash(qt, kt, vt, interpret), (qt, kt, vt)


def _dit_flash_bwd(interpret, qkv, g):
    return jax.vjp(_dit_attention_ref_t, *qkv)[1](g)


_dit_flash.defvjp(_dit_flash_fwd, _dit_flash_bwd)


def _project(x, ws, biases, layout):
    """x (B, N, d) through each (d, H, K) projection into ``layout``
    ("hbkn" or "bnhk"), each plus its (H, K) bias when ``biases`` are
    given."""
    outs = [jnp.einsum(f"bnd,dhk->{layout}", x, w) for w in ws]
    if biases is None:
        return outs
    if layout == "hbkn":
        biases = [b[:, None, :, None] for b in biases]
    return [o + b for o, b in zip(outs, biases)]


def dit_attention(x, wq, wk, wv, wo, *, biases=None,
                  use_pallas: Optional[bool] = None, interpret: bool = False):
    """The DiT's non-causal self-attention.  x: (B, N, d); wq, wk, wv:
    (d, H, K); wo: (H, K, d); ``biases`` (q, k, v), each (H, K), when the
    projections have them -> (B, N, d).

    On the TPU q, k, v leave their projections as (H, B, K, N), the layout
    of one Pallas flash kernel (``_dit_flash``) that keeps each score tile
    in VMEM, at the precision XLA's default matmul gives the einsums (bf16
    MXU operands, f32 accumulation and softmax); its gradient is the
    einsums', recomputed from q, k, v.  Elsewhere the f32 einsums
    (``ref.dit_attention_ref``)."""
    if _pick(use_pallas):
        qt, kt, vt = _project(x, (wq, wk, wv), biases, "hbkn")
        ctx = _dit_flash(qt, kt, vt, interpret).astype(x.dtype)
        return jnp.einsum("hbkn,hkd->bnd", ctx, wo)
    q, k, v = _project(x, (wq, wk, wv), biases, "bnhk")
    ctx = _ref.dit_attention_ref(q, k, v).astype(x.dtype)
    return jnp.einsum("bnhk,hkd->bnd", ctx, wo)


def _dit_xflash(qt, kt, vt, lengths, interpret):
    spec = _dit_spec(qt.shape)
    rows = P(spec[1]) if len(spec) else P()
    return _per_device(functools.partial(_dit_cross_flash_attention,
                                         interpret=interpret),
                       qt, kt, vt, lengths, spec=spec,
                       in_specs=(spec, spec, spec, rows))


def dit_cross_attention(x, c, lengths, wq, wk, wv, wo, *, biases=None,
                        use_pallas: Optional[bool] = None,
                        interpret: bool = False):
    """Cross-attention of DiT tokens to a conditioning sequence (PixArt's
    caption).  x: (B, N, d) queries; c: (B, M, d) keys and values, of
    which row b counts its first ``lengths[b]`` (the rest are padding);
    weights and ``biases`` as ``dit_attention`` -> (B, N, d).

    On the TPU the (H, B, K, M) keys and values are padded to 128 keys
    and the Pallas kernel ``_dit_xflash`` masks each row's padding; its
    precision is ``_dit_flash``'s.  Elsewhere f32 einsums under the same
    mask (``ref.dit_cross_attention_ref``)."""
    if _pick(use_pallas):
        (qt,) = _project(x, (wq,), biases and biases[:1], "hbkn")
        kt, vt = _project(c, (wk, wv), biases and biases[1:], "hbkn")
        pad = -kt.shape[-1] % 128
        kt, vt = (jnp.pad(a, ((0, 0),) * 3 + ((0, pad),)) for a in (kt, vt))
        ctx = _dit_xflash(qt, kt, vt, lengths, interpret).astype(x.dtype)
        return jnp.einsum("hbkn,hkd->bnd", ctx, wo)
    (q,) = _project(x, (wq,), biases and biases[:1], "bnhk")
    k, v = _project(c, (wk, wv), biases and biases[1:], "bnhk")
    ctx = _ref.dit_cross_attention_ref(q, k, v, lengths).astype(x.dtype)
    return jnp.einsum("bnhk,hkd->bnd", ctx, wo)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *,
                     use_pallas: Optional[bool] = None, interpret: bool = False):
    """q: (B,H,D); caches (B,T,KV,D); lengths (B,) -> (B,H,D)."""
    if _pick(use_pallas):
        return _flash_decode(q, k_cache, v_cache, lengths, interpret=interpret)
    return _ref.decode_ref(q, k_cache, v_cache, lengths)


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas", "interpret"))
def ssd(x, dt, A, B, C, *, chunk: int = 128,
        use_pallas: Optional[bool] = None, interpret: bool = False):
    """Mamba2 SSD scan.  Returns (y, final_state)."""
    if _pick(use_pallas):
        return _ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    return _ref.ssd_ref(x, dt, A, B, C)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def rglru(a, b, *, use_pallas: Optional[bool] = None, interpret: bool = False):
    """Linear recurrence h_t = a_t h_{t-1} + b_t over axis 1."""
    if _pick(use_pallas):
        return _rglru_scan(a, b, interpret=interpret)
    return _ref.rglru_ref(a, b)


def _row_pin(x, time_axis, dim=0, *, replicate=False):
    """Time-axis constraint pin (lazy import keeps kernels<->models acyclic)."""
    if time_axis is None:
        return x
    from repro.models.shardctx import window_constrain
    return window_constrain(x, time_axis, dim, replicate=replicate)


def _per_device(kernel, *args, spec=P(), in_specs=None):
    """Run a Pallas kernel call on every device of the ambient serving mesh.

    GSPMD cannot partition a Mosaic kernel, so under a mesh the call goes
    through ``shard_map`` with ``spec`` on the output and on every operand
    (``in_specs``, when given, per operand; replicated by default): each
    device runs it on its own block of the operands.  Under the engine's
    ``vmap(spmd_axis_name=data)`` the request axis becomes a ``data``-sharded
    dim of that shard_map, so each device runs its own requests; on every
    other mesh axis the per-request operands are whole (the ops below keep
    them replicated anyway).  No mesh: a plain call."""
    from repro.models.shardctx import current_mesh
    mesh = current_mesh()
    if mesh is None:
        return kernel(*args)
    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=in_specs or (spec,) * len(args),
                         out_specs=spec, check_vma=False)(*args)


# Time-sharded dispatch notes (both caught by the bitwise suite):
#
#  * When ``time_axis`` is set the public wrappers run the implementation
#    INLINE in the caller's trace instead of through their jit wrapper —
#    sharding-constraint pins inside a nested pjit miscompile under
#    ``lax.while_loop`` on the CPU partitioner (values, not just layouts,
#    go wrong).
#  * The pins are REPLICATE pins only.  Row-sharding the full-T operands
#    (dF/dX/R/x/mask) back-propagates a time sharding onto the solver's
#    loop carry, and ``dynamic_slice`` at a traced offset on a row-sharded
#    carry miscompiles the same way.  The window slice values the solver
#    feeds the denoiser ARE safely sharded (pins in
#    ``repro.core.parataa._iterate``) — that is the dominant cost; the
#    replicate pins here hold every cross-row reduction (suffix cumsum,
#    global Gram, gamma solve) to the unsharded f32 summation order, so the
#    only collective over ``time`` is the exact all-gather at the window
#    boundary.


def _taa_gram_impl(dF, R, mask, use_pallas, interpret, time_axis):
    if _pick(use_pallas):
        G, u = _per_device(functools.partial(_taa_gram, interpret=interpret),
                           dF, R, mask)
    else:
        G, u = _ref.taa_gram_ref(dF, R, mask)
    return (_row_pin(G, time_axis, replicate=True),
            _row_pin(u, time_axis, replicate=True))


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _taa_gram_jit(dF, R, mask, *, use_pallas, interpret):
    return _taa_gram_impl(dF, R, mask, use_pallas, interpret, None)


def taa_gram(dF, R, mask, *, use_pallas: Optional[bool] = None,
             interpret: bool = False, time_axis: Optional[str] = None):
    """Raw per-row Gram blocks G_t = F_t^T F_t, u_t = F_t^T R_t (masked) —
    the memory-bound first pass every Anderson variant shares; the AA/AA+
    variants reduce these blocks globally instead of via the TAA suffix
    cumsum (see ``repro.core.anderson``).

    ``time_axis`` pins the G/u outputs replicated over that mesh axis, so
    the AA/TAA cross-row reductions downstream keep the unsharded f32
    summation order — bitwise-identical to the unsharded pass.
    """
    if time_axis is not None:
        return _taa_gram_impl(dF, R, mask, use_pallas, interpret, time_axis)
    return _taa_gram_jit(dF, R, mask, use_pallas=use_pallas,
                         interpret=interpret)


def _taa_rowwise_gamma_impl(dF, R, mask, lam, use_pallas, interpret,
                            time_axis):
    # The suffix cumsum is a cross-row reduction: taa_gram hands back
    # REPLICATED G/u, so the f32 summation order here is the unsharded one
    # regardless of time_axis — the bitwise contract.
    G, u = _taa_gram_impl(dF, R, mask, use_pallas, interpret, time_axis)
    m = dF.shape[0]
    Gs = jnp.flip(jnp.cumsum(jnp.flip(G, 0), 0), 0) + lam * jnp.eye(m)
    us = jnp.flip(jnp.cumsum(jnp.flip(u, 0), 0), 0)
    Gs = _row_pin(Gs, time_axis, replicate=True)
    us = _row_pin(us, time_axis, replicate=True)
    gamma = jnp.linalg.solve(Gs, us[..., None])[..., 0]
    return _row_pin(gamma, time_axis, replicate=True)


@functools.partial(jax.jit, static_argnames=("lam", "use_pallas", "interpret"))
def _taa_rowwise_gamma_jit(dF, R, mask, *, lam, use_pallas, interpret):
    return _taa_rowwise_gamma_impl(dF, R, mask, lam, use_pallas, interpret,
                                   None)


def taa_rowwise_gamma(dF, R, mask, *, lam: float = 1e-8,
                      use_pallas: Optional[bool] = None,
                      interpret: bool = False,
                      time_axis: Optional[str] = None):
    """Per-row TAA gammas via suffix-cumsum Grams (Theorem 3.2)."""
    if time_axis is not None:
        return _taa_rowwise_gamma_impl(dF, R, mask, lam, use_pallas,
                                       interpret, time_axis)
    return _taa_rowwise_gamma_jit(dF, R, mask, lam=lam,
                                  use_pallas=use_pallas, interpret=interpret)


def _taa_apply_impl(x, R, dX, dF, gamma, mask, use_pallas, interpret,
                    time_axis):
    if _pick(use_pallas):
        out = _per_device(functools.partial(_taa_apply, interpret=interpret),
                          x, R, dX, dF, gamma, mask)
    else:
        out = _ref.taa_apply_ref(x, R, dX, dF, gamma, mask)
    return _row_pin(out, time_axis, replicate=True)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _taa_apply_jit(x, R, dX, dF, gamma, mask, *, use_pallas, interpret):
    return _taa_apply_impl(x, R, dX, dF, gamma, mask, use_pallas, interpret,
                           None)


def taa_apply(x, R, dX, dF, gamma, mask, *,
              use_pallas: Optional[bool] = None, interpret: bool = False,
              time_axis: Optional[str] = None):
    """Per-row history apply x_t + R_t - (dX_t + dF_t) @ gamma_t;
    ``time_axis`` pins the output replicated (see dispatch notes above)."""
    if time_axis is not None:
        return _taa_apply_impl(x, R, dX, dF, gamma, mask, use_pallas,
                               interpret, time_axis)
    return _taa_apply_jit(x, R, dX, dF, gamma, mask, use_pallas=use_pallas,
                          interpret=interpret)


def _taa_round_impl(x, R, dX, dF, mask, guard, mode, lam, use_pallas,
                    interpret, time_axis):
    if _pick(use_pallas):
        g = jnp.zeros_like(mask) if guard is None \
            else guard.astype(jnp.float32)
        out = _per_device(
            functools.partial(_taa_round_kernel, mode=mode, lam=lam,
                              interpret=interpret),
            x, R, dX, dF, mask, g)
        return _row_pin(out, time_axis, replicate=True)
    # Staged reference: the EXACT primitives anderson_update's unfused path
    # composes, in the same order — gram, (suffix) reduce + solve, apply —
    # so the CPU default is bitwise-identical with fuse_round on or off.
    T = x.shape[0]
    m = dF.shape[0]
    if mode == "taa":
        gamma = _taa_rowwise_gamma_impl(dF, R, mask, lam, use_pallas,
                                        interpret, time_axis)
    else:
        G, u = _taa_gram_impl(dF, R, mask, use_pallas, interpret, time_axis)
        eye = jnp.eye(m, dtype=jnp.float32)
        if mode == "aa":
            M = jnp.sum(G, axis=0) + lam * eye
            rhs = jnp.sum(u, axis=0)
            g = jnp.linalg.solve(M, rhs)
            gamma = jnp.broadcast_to(g[None], (T, m))
        elif mode == "aa+":
            M = jnp.sum(G, axis=0) + lam * eye
            rhs = jnp.flip(jnp.cumsum(jnp.flip(u, 0), 0), 0)
            gamma = jnp.linalg.solve(M[None], rhs[..., None])[..., 0]
        else:
            raise ValueError(mode)
        gamma = _row_pin(gamma, time_axis, replicate=True)
    if guard is not None:
        gamma = jnp.where(guard[:, None], 0.0, gamma)
    return _taa_apply_impl(x, R, dX, dF, gamma, mask, use_pallas, interpret,
                           time_axis)


@functools.partial(jax.jit,
                   static_argnames=("mode", "lam", "use_pallas", "interpret"))
def _taa_round_jit(x, R, dX, dF, mask, guard, *, mode, lam, use_pallas,
                   interpret):
    return _taa_round_impl(x, R, dX, dF, mask, guard, mode, lam, use_pallas,
                           interpret, None)


def taa_round(x, R, dX, dF, mask, *, mode: str = "taa", lam: float = 1e-8,
              safeguard_mask=None, use_pallas: Optional[bool] = None,
              interpret: bool = False, time_axis: Optional[str] = None):
    """The whole Theorem-3.2 round — Gram blocks, suffix cumsum, the T tiny
    regularized solves (taa; aa/aa+ use their global/suffix reductions), the
    Theorem-3.6 safeguard, and the history apply — as ONE dispatch.

    On the Pallas path this is a single ``pallas_call`` (one launch instead
    of gram + host solve + apply); elsewhere it falls back to the staged jnp
    composition, bitwise-identical to running the three ops separately.
    ``safeguard_mask``: (T,) bool rows forced to the plain FP update;
    ``time_axis`` pins every cross-row reduction replicated, same rules as
    the staged ops (see dispatch notes above).
    """
    if time_axis is not None:
        return _taa_round_impl(x, R, dX, dF, mask, safeguard_mask, mode, lam,
                               use_pallas, interpret, time_axis)
    return _taa_round_jit(x, R, dX, dF, mask, safeguard_mask, mode=mode,
                          lam=lam, use_pallas=use_pallas, interpret=interpret)
