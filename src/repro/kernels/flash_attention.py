"""Flash attention (forward) as Pallas TPU kernels.

Precision rule, both kernels: each dot takes its operands in the dtype they
arrive in and accumulates in f32 (``preferred_element_type=float32``).
bf16 operands make one MXU pass; tiles are never upcast to f32 before a
dot, which would make the multi-pass f32 product.  The scale, max, exp,
running sum ``l``, the accumulator and the final divide are f32; the
unnormalised probabilities enter the second dot in v's dtype.  No score or
probability is stored below f32 outside a dot's operands.

``flash_attention`` (causal / sliding window).  Grid: (batch*heads,
q_blocks, kv_blocks) — the kv axis is the minor (sequential) grid
dimension, so VMEM scratch accumulators (acc, m, l) carry across kv
iterations (the TPU grid is executed in order).  Per step the kernel holds
one (bq, d) query tile and one (bk, d) key/value tile in VMEM, streams
blocks from HBM, and maintains an online softmax.  Causal / sliding-window
masking is applied from block-relative positions; fully masked blocks are
skipped with pl.when (compute saving, the same trick the paper-era GPU
kernels use via early exit).  Block shapes default to (bq, d) = (128,
head_dim) and bk = 128 — (8, 128) lane-aligned and MXU-shaped for d in
{64, 128, 256}.

``dit_flash_attention`` (non-causal, the DiT's).  Every key sits in one
block, so the softmax is plain (no running max, no rescale), and the scores
are key-major: a (N, bq) tile per (head, batch) pair, whose max and sum run
down the sublanes instead of across lanes.  q, k and v come in as (D, N),
the layout XLA gives their projections, so their 72-row head tiles are
lane-dense and no transpose is written; the context leaves as (D, N).  A
grid step holds G pairs and computes them as batched dots.  On a TPU
v5e, at the benchmark's shapes vmapped over 8 slots (one layer, f32 inputs,
bf16 conversions included), this form takes
3.33 ms at 16 x 25 pairs of 256 tokens and 1.29 ms at 16 pairs of 1024
tokens, against 5.65 and 3.24 ms for the f32 einsums; with k as (N, D) it
took 3.88 and 1.37 ms, and a query-major tile (reductions across lanes, one
pair a loop step) 6.4 and 1.45 ms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, causal: bool, window: int, t_total: int,
                  s_total: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions (queries right-aligned when s < t: offset t - s)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (t_total - s_total)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: any (q, k) pair in this tile alive?
    q_max = qi * bq + bq - 1 + (t_total - s_total)
    q_min = qi * bq + (t_total - s_total)
    k_min, k_max = ki * bk, ki * bk + bk - 1
    alive = True
    if causal:
        alive = jnp.logical_and(alive, k_min <= q_max)
    if window:
        alive = jnp.logical_and(alive, k_max > q_min - window)

    @pl.when(alive)
    def _compute():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / np.sqrt(q.shape[-1]))
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    """q: (B, H, S, D); k, v: (B, H, T, D) -> (B, H, S, D)."""
    b, h, s, d = q.shape
    t = k.shape[2]
    assert s % bq == 0 and t % bk == 0, (s, t, bq, bk)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    grid = (b * h, s // bq, t // bk)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, t_total=t, s_total=s)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            # f32 accumulators persist across the (sequential) kv grid dim
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)


def _dit_flash_kernel(qt_ref, kt_ref, vt_ref, ot_ref):
    """G pairs: qt (G, d, bq), kt and vt (G, d, n) -> ot (G, d, bq)."""
    scale = 1.0 / np.sqrt(qt_ref.shape[1])
    s = jnp.einsum("gdn,gdq->gnq", kt_ref[...], qt_ref[...],
                   preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
    vt = vt_ref[...]
    ot = jnp.einsum("gdn,gnq->gdq", vt, p.astype(vt.dtype),
                    preferred_element_type=jnp.float32)
    ot_ref[...] = (ot / jnp.sum(p, axis=1, keepdims=True)).astype(ot_ref.dtype)


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) tile: rows padded to the dtype's
    sublane packing, cols to 128 lanes."""
    sub = 8 * 4 // itemsize
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def dit_blocks(pairs: int, n: int, d: int, m: int = 0):
    """(bq, G): query columns a block and (head, batch) pairs a block for
    ``pairs`` pairs of ``n`` tokens of head size ``d`` attending to ``m``
    keys (default ``n``; PixArt's cross-attention: 128 padded keys, G
    heads of one row, 16 at its cell's shapes).

    bq is n up to 256 tokens, else 256 (128 where 256 does not divide n).
    G is the largest divisor of ``pairs`` whose blocks fit 16 MiB of VMEM:
    the double-buffered bf16 q, k, v and f32 context blocks, and the f32
    scores, probabilities and their bf16 copy.  At the benchmark's shapes:

    =====  =======================  ====  ==
    n      pairs                    bq    G
    =====  =======================  ====  ==
    256    16 heads x 25 rows       256   16
    1024   16 heads x 1 row         256   4
    =====  =======================  ====  ==
    """
    m = m or n
    bq = n if n <= 256 else 256 if n % 256 == 0 else 128
    io = 2 * (_tile_bytes(d, bq, 2) + 2 * _tile_bytes(d, m, 2)
              + _tile_bytes(d, bq, 4))
    work = 2 * _tile_bytes(m, bq, 4) + _tile_bytes(m, bq, 2)
    cap = max(1, 16 * 2 ** 20 // (io + work))
    return bq, max(g for g in range(1, min(pairs, cap) + 1) if pairs % g == 0)


def dit_flash_attention(qt, kt, vt, *, interpret: bool = False):
    """Non-causal attention over DiT tokens.

    qt, kt, vt: (H, B, D, N) -> the context as (H, B, D, N).
    The precision of XLA's default f32 matmul on a TPU: q, k and v enter
    the MXU rounded to bf16, with f32 accumulation; the scores are scaled,
    and the softmax and context normalised, in f32.
    """
    h, b, d, n = qt.shape
    bq, g = dit_blocks(h * b, n, d)
    out = pl.pallas_call(
        _dit_flash_kernel,
        grid=(b * h // g, n // bq),
        in_specs=[
            pl.BlockSpec((g, d, bq), lambda p, qi: (p, 0, qi)),
            pl.BlockSpec((g, d, n), lambda p, qi: (p, 0, 0)),
            pl.BlockSpec((g, d, n), lambda p, qi: (p, 0, 0)),
        ],
        out_specs=pl.BlockSpec((g, d, bq), lambda p, qi: (p, 0, qi)),
        out_shape=jax.ShapeDtypeStruct((h * b, d, n), qt.dtype),
        name="_dit_flash",
        interpret=interpret,
    )(*(a.astype(jnp.bfloat16).reshape(h * b, d, n) for a in (qt, kt, vt)))
    return out.reshape(h, b, d, n)


def _dit_xflash_kernel(len_ref, qt_ref, kt_ref, vt_ref, ot_ref):
    """G heads of one batch row: qt (G, d, bq), kt and vt (G, d, m) -> ot
    (G, d, bq); keys at or past the row's length (len_ref, (1, 1)) are
    masked."""
    scale = 1.0 / np.sqrt(qt_ref.shape[1])
    s = jnp.einsum("gdn,gdq->gnq", kt_ref[...], qt_ref[...],
                   preferred_element_type=jnp.float32) * scale
    key = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(key < len_ref[...][None], s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
    vt = vt_ref[...]
    ot = jnp.einsum("gdn,gnq->gdq", vt, p.astype(vt.dtype),
                    preferred_element_type=jnp.float32)
    ot_ref[...] = (ot / jnp.sum(p, axis=1, keepdims=True)).astype(ot_ref.dtype)


def dit_cross_flash_attention(qt, kt, vt, lengths, *,
                              interpret: bool = False):
    """Non-causal attention of DiT tokens to a padded key sequence
    (PixArt's cross-attention to its caption, ``_dit_xflash``).

    qt: (H, B, D, N); kt, vt: (H, B, D, M) with M a multiple of 128;
    lengths: (B,) int32 keys that count in each row -> (H, B, D, N).

    The key-major form of ``dit_flash_attention`` with N_kv != N_q: the
    caption's keys, padded to 128 lanes, sit in one block, and a grid step
    holds G heads of one batch row, so one length masks the whole block.
    The length rides as a (1, 1) int32 block of its row, not as scalar
    prefetch: under the sampling engine's vmap over lanes a batched
    scalar-prefetch operand makes Pallas loop over the lanes one kernel
    call at a time, while a blocked operand gains a grid dimension as q, k
    and v do.  Precision as ``dit_flash_attention``."""
    h, b, d, n = qt.shape
    m = kt.shape[-1]
    assert m % 128 == 0, m
    bq, g = dit_blocks(h, n, d, m)
    out = pl.pallas_call(
        _dit_xflash_kernel,
        grid=(b, h // g, n // bq),
        in_specs=[
            pl.BlockSpec((None, 1, 1), lambda r, hg, qi: (r, 0, 0)),
            pl.BlockSpec((g, None, d, bq), lambda r, hg, qi: (hg, r, 0, qi)),
            pl.BlockSpec((g, None, d, m), lambda r, hg, qi: (hg, r, 0, 0)),
            pl.BlockSpec((g, None, d, m), lambda r, hg, qi: (hg, r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((g, None, d, bq),
                               lambda r, hg, qi: (hg, r, 0, qi)),
        out_shape=jax.ShapeDtypeStruct((h, b, d, n), qt.dtype),
        name="_dit_xflash",
        interpret=interpret,
    )(lengths.astype(jnp.int32).reshape(b, 1, 1),
      *(a.astype(jnp.bfloat16) for a in (qt, kt, vt)))
    return out
