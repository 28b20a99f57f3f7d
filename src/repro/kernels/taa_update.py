"""Fused TAA (Theorem 3.2) building blocks as Pallas TPU kernels.

The suffix-cumsum reformulation (see repro.core.anderson) reduces TAA to:
  1. per-row Gram blocks  G_t = F_t^T F_t (m x m), u_t = F_t^T R_t (m)
  2. a reverse cumsum over t + T tiny (m x m) solves         [host jnp]
  3. the update x_t + R_t - (dX_t + dF_t)^T gamma_t

Steps 1 and 3 are memory-bound passes over the (m, T, D) histories;
``taa_gram`` / ``taa_apply`` fuse each into a single HBM sweep.  Every block
spans ALL T rows and a 128-multiple tile of D, so the last two block dims
are (T, bd): T equals the array's row count (legal for any T) and bd is
lane-aligned.  The grid runs over D tiles only, sequentially, so the
per-row partials accumulate in place.

Per-row (m, m) / (m,) quantities never take their natural shape inside a
kernel.  They live COLUMN-PACKED in one lane-dense (T, 128) f32 tile: lane
``i*m + j`` holds G[:, i, j] and lane ``m*m + i`` holds u[:, i]
(so m <= 10).  Each column is a lane reduction of an elementwise product
over the D tile, so the kernels use only 2-D elementwise ops, lane
reductions and lane-iota selects: no 1-D values, no in-kernel reshapes or
concatenates, no SMEM scalars, and no matvecs for the MXU.

``taa_round`` goes further: ONE ``pallas_call`` for the whole round.  The
grid grows a leading phase axis (2, d_blocks).  Phase 0 is the Gram sweep
into a (T, 128) VMEM accumulator.  At the first step of phase 1 the suffix
cumsum (a row loop over the accumulator, in place), the ridge and the T
tiny (m, m) solves (unrolled pivot-free Gauss-Jordan on (T, 1) columns;
the Grams are SPD + ridge) run on the resident tile.  The rest of phase 1
is the apply sweep, which reads the gammas from VMEM.  Launches per round
go from 3 (gram + host solve + apply) to 1, and the G/u/gamma intermediates
never touch HBM or the host.

Each ``pallas_call`` is named after its entry point (``taa_gram``,
``taa_apply``, ``taa_round``): the kernel's name in the lowered program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# VMEM for the double-buffered (T, bd) blocks of one grid step: the D tile
# shrinks for long schedules so x, R, out and the two histories fit.
_VMEM_BLOCK_BUDGET = 8 << 20


def _block_d(t: int, d: int, m: int, bd: int):
    """(D tile, padded D): the tile is a multiple of 128 lanes, at most
    ``bd``, within the VMEM budget, and divides the 128-padded D, so D is
    padded only when it is not itself a multiple of 128."""
    if m * m + m > _LANES:
        raise ValueError(f"history m={m} does not fit the column-packed "
                         f"(T, {_LANES}) Gram tile (m <= 10)")
    dpad = -(-d // _LANES) * _LANES
    fit = _VMEM_BLOCK_BUDGET // (2 * (3 + 2 * m) * t * 4)
    tile = max(_LANES, min(bd, fit, dpad) // _LANES * _LANES)
    while dpad % tile:
        tile -= _LANES
    return tile, dpad


def _pad_d(a, dpad):
    pad = dpad - a.shape[-1]
    if not pad:
        return a
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])


def _lane(t: int):
    return jax.lax.broadcasted_iota(jnp.int32, (t, _LANES), 1)


def _gram_cols(df_ref, r_ref, w, *, m: int, t: int):
    """This D tile's column-packed partial Grams (T, 128) for masked rows."""
    f32 = jnp.float32
    lane = _lane(t)
    r = r_ref[...].astype(f32) * w
    dfs = [df_ref[i].astype(f32) * w for i in range(m)]      # m x (T, bd)
    acc = jnp.zeros((t, _LANES), f32)
    for i in range(m):
        for j in range(i, m):
            col = jnp.sum(dfs[i] * dfs[j], axis=1, keepdims=True)
            acc = jnp.where((lane == i * m + j) | (lane == j * m + i), col,
                            acc)
        col = jnp.sum(dfs[i] * r, axis=1, keepdims=True)
        acc = jnp.where(lane == m * m + i, col, acc)
    return acc


def _col(tile, c: int, lane):
    """Lane ``c`` of a (T, 128) tile as a (T, 1) column."""
    return jnp.sum(jnp.where(lane == c, tile, 0.0), axis=1, keepdims=True)


def _apply_rows(x_ref, r_ref, dx_ref, df_ref, gam, w, *, m: int, t: int):
    """x + R - (dX + dF)^T gamma on rows with w > 0, x elsewhere (f32);
    ``gam`` is the (T, 128) tile with gamma_i in lane i."""
    f32 = jnp.float32
    lane = _lane(t)
    x = x_ref[...].astype(f32)
    r = r_ref[...].astype(f32)
    corr = jnp.zeros_like(x)
    for i in range(m):
        hist = dx_ref[i].astype(f32) + df_ref[i].astype(f32)
        corr = corr + _col(gam, i, lane) * hist
    return jnp.where(w > 0, x + r - corr, x)


def _gram_kernel(df_ref, r_ref, mask_ref, gu_ref, *, m: int, t: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        gu_ref[...] = jnp.zeros_like(gu_ref)

    gu_ref[...] += _gram_cols(df_ref, r_ref, mask_ref[...], m=m, t=t)


def taa_gram(dF, R, mask, *, bd: int = 512, interpret: bool = False):
    """dF: (m, T, D); R: (T, D); mask: (T,) f32 -> (G (T,m,m), u (T,m))."""
    m, t, d = dF.shape
    bd, dpad = _block_d(t, d, m, bd)
    dF, R = _pad_d(dF, dpad), _pad_d(R, dpad)
    gu = pl.pallas_call(
        functools.partial(_gram_kernel, m=m, t=t),
        grid=(dpad // bd,),
        in_specs=[
            pl.BlockSpec((m, t, bd), lambda di: (0, 0, di)),
            pl.BlockSpec((t, bd), lambda di: (0, di)),
            pl.BlockSpec((t, 1), lambda di: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t, _LANES), lambda di: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, _LANES), jnp.float32),
        interpret=interpret,
        name="_taa_gram",
    )(dF, R, mask.astype(jnp.float32).reshape(t, 1))
    return gu[:, :m * m].reshape(t, m, m), gu[:, m * m:m * m + m]


def _apply_kernel(x_ref, r_ref, dx_ref, df_ref, gam_ref, mask_ref, o_ref, *,
                  m: int, t: int):
    out = _apply_rows(x_ref, r_ref, dx_ref, df_ref, gam_ref[...],
                      mask_ref[...], m=m, t=t)
    o_ref[...] = out.astype(o_ref.dtype)


def taa_apply(x, R, dX, dF, gamma, mask, *, bd: int = 512,
              interpret: bool = False):
    """x, R: (T, D); dX, dF: (m, T, D); gamma: (T, m); mask: (T,) f32 ->
    x + mask * (R - (dX + dF)^T gamma)."""
    m, t, d = dX.shape
    bd, dpad = _block_d(t, d, m, bd)
    x, R, dX, dF = (_pad_d(a, dpad) for a in (x, R, dX, dF))
    gam = jnp.pad(gamma.astype(jnp.float32), ((0, 0), (0, _LANES - m)))
    row = pl.BlockSpec((t, bd), lambda di: (0, di))
    hist = pl.BlockSpec((m, t, bd), lambda di: (0, 0, di))
    out = pl.pallas_call(
        functools.partial(_apply_kernel, m=m, t=t),
        grid=(dpad // bd,),
        in_specs=[row, row, hist, hist,
                  pl.BlockSpec((t, _LANES), lambda di: (0, 0)),
                  pl.BlockSpec((t, 1), lambda di: (0, 0))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((t, dpad), x.dtype),
        interpret=interpret,
        name="_taa_apply",
    )(x, R, dX, dF, gam, mask.astype(jnp.float32).reshape(t, 1))
    return out[:, :d]


def _gauss_jordan(A, b, *, m: int):
    """Pivot-free Gauss-Jordan solve of A x = b, one system per row: A is
    an m x m list of (T, 1) columns (SPD + ridge), b a list of m columns.
    m is static, so the elimination unrolls fully: VPU-only, no gathers,
    no data-dependent control flow."""
    A = [list(r) for r in A]
    b = list(b)
    for k in range(m):
        piv = A[k][k]
        A[k] = [a / piv for a in A[k]]
        b[k] = b[k] / piv
        for i in range(m):
            if i != k:
                f = A[i][k]
                A[i] = [A[i][j] - f * A[k][j] for j in range(m)]
                b[i] = b[i] - f * b[k]
    return b


def _suffix_rows(ref, t: int):
    """In-place reverse cumulative sum over the rows of a (T, 128) ref."""
    def body(k, carry):
        row = t - 2 - k
        cur = ref[pl.ds(row, 1), :] + carry
        ref[pl.ds(row, 1), :] = cur
        return cur

    jax.lax.fori_loop(0, t - 1, body, ref[pl.ds(t - 1, 1), :])


def _round_kernel(x_ref, r_ref, dx_ref, df_ref, mask_ref, guard_ref, o_ref,
                  acc, gam, *, mode: str, lam: float, m: int, t: int):
    ph = pl.program_id(0)
    di = pl.program_id(1)
    w = mask_ref[...]

    @pl.when((ph == 0) & (di == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(ph == 0)
    def _gram_sweep():
        acc[...] += _gram_cols(df_ref, r_ref, w, m=m, t=t)

    @pl.when((ph == 1) & (di == 0))
    def _solve():
        lane = _lane(t)
        _suffix_rows(acc, t)                     # row s = sum over rows >= s
        suf = acc[...]
        total = jnp.broadcast_to(acc[pl.ds(0, 1), :], (t, _LANES))
        if mode == "taa":
            red = suf
        elif mode == "aa":
            red = total
        elif mode == "aa+":                      # global Gram, suffix u
            red = jnp.where(lane < m * m, total, suf)
        else:
            raise ValueError(mode)
        A = [[_col(red, i * m + j, lane) + (lam if i == j else 0.0)
              for j in range(m)] for i in range(m)]
        b = [_col(red, m * m + i, lane) for i in range(m)]
        gamma = _gauss_jordan(A, b, m=m)
        keep = guard_ref[...] <= 0
        g = jnp.zeros((t, _LANES), jnp.float32)
        for i in range(m):
            g = jnp.where(lane == i, jnp.where(keep, gamma[i], 0.0), g)
        gam[...] = g

    @pl.when(ph == 1)
    def _apply():
        out = _apply_rows(x_ref, r_ref, dx_ref, df_ref, gam[...], w, m=m, t=t)
        o_ref[...] = out.astype(o_ref.dtype)


def taa_round(x, R, dX, dF, mask, guard, *, mode: str = "taa",
              lam: float = 1e-8, bd: int = 512, interpret: bool = False):
    """Whole Theorem-3.2 round in one launch: Gram blocks, suffix cumsum,
    the T regularized (m, m) solves, and the history apply.

    x, R: (T, D); dX, dF: (m, T, D); mask: (T,) f32 window weights;
    guard: (T,) f32 — rows > 0 get gamma forced to 0 (Theorem 3.6
    safeguard; pass zeros for no safeguard).  Returns (T, D) in x.dtype.

    Grid (2, d_blocks): the out/x/dX index maps multiply by the phase id,
    pinning their block at 0 through the whole Gram sweep — the output
    block is only flushed after phase 1's first step has written it, so
    nothing undefined reaches HBM.
    """
    m, t, d = dF.shape
    bd, dpad = _block_d(t, d, m, bd)
    x, R, dX, dF = (_pad_d(a, dpad) for a in (x, R, dX, dF))
    col = pl.BlockSpec((t, 1), lambda ph, di: (0, 0))
    out = pl.pallas_call(
        functools.partial(_round_kernel, mode=mode, lam=lam, m=m, t=t),
        grid=(2, dpad // bd),
        in_specs=[
            pl.BlockSpec((t, bd), lambda ph, di: (0, di * ph)),
            pl.BlockSpec((t, bd), lambda ph, di: (0, di)),
            pl.BlockSpec((m, t, bd), lambda ph, di: (0, 0, di * ph)),
            pl.BlockSpec((m, t, bd), lambda ph, di: (0, 0, di)),
            col, col,
        ],
        out_specs=pl.BlockSpec((t, bd), lambda ph, di: (0, di * ph)),
        out_shape=jax.ShapeDtypeStruct((t, dpad), x.dtype),
        scratch_shapes=[pltpu.VMEM((t, _LANES), jnp.float32),
                        pltpu.VMEM((t, _LANES), jnp.float32)],
        interpret=interpret,
        name="_taa_round",
    )(x, R, dX, dF, mask.astype(jnp.float32).reshape(t, 1),
      guard.astype(jnp.float32).reshape(t, 1))
    return out[:, :d]
