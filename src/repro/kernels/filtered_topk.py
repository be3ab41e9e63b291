"""Pallas TPU kernel: fused distance + spatio-temporal filter + streaming
top-k — the paper's hot loop (Fig. 3: metadata aligned with the node block so
the predicate is evaluated during traversal, not post-hoc).

Per grid step, a ``[tn, d]`` candidate-vector tile and its ``[tn, mpad]``
metadata tile are resident in VMEM; the kernel

  1. computes the query-block distances on the MXU,
  2. evaluates the packed filter predicate on the VPU and masks failures to
     +inf,
  3. folds the tile into a running top-k kept in VMEM scratch via a
     K-step argmin extraction (one-hot masking, no scatter) followed by a
     bitonic merge of two sorted-K lists — all static-shape compare/exchange
     networks, i.e. Mosaic-friendly (no data-dependent control flow).

Grid order is (query tile, candidate tile) with the candidate axis innermost:
scratch initializes at j == 0 and the result is emitted at the last j
(flash-attention-style streaming reduction).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["filtered_topk_kernel_call", "FILTER_KINDS"]

FILTER_KINDS = ("none", "box", "ball", "box_not_ball", "box_ball")
_NEG = -1e30
_POS = 1e30


def _filter_mask(meta, params, kind):
    """meta [tn, mpad], params [4, mpad] -> bool [tn] (row layout: the
    beam-step kernel and its jnp twin score gathered candidate rows)."""
    mpad = meta.shape[-1]
    in_box = jnp.all((meta >= params[0]) & (meta <= params[1]), axis=-1)
    mc = params[3, 1].astype(jnp.int32)
    dim_mask = jax.lax.broadcasted_iota(jnp.int32, (mpad,), 0) < mc
    diff = meta - params[2]
    d2 = jnp.sum(jnp.where(dim_mask, diff * diff, 0.0), axis=-1)
    in_ball = d2 <= params[3, 0]
    if kind == "none":
        # padding rows carry meta = +2e30 and must still fail:
        return meta[:, 0] < _POS
    if kind == "box":
        return in_box
    if kind == "ball":
        return in_ball
    if kind == "box_ball":
        return in_box & in_ball
    return in_box & ~in_ball                       # box_not_ball


def param_columns(params):
    """Packed filter ``[4, mq]`` (box lo / hi, ball center, [r^2, ndim])
    -> the column layout ``[mq, 8]`` the scan kernels read: columns 0-2
    are lo, hi and center per metadata dim, columns 3 and 4 broadcast
    r^2 and the ball's ndim, so every operand of the transposed predicate
    is a static lane slice (no scalar extraction, no relayout)."""
    p = jnp.asarray(params, jnp.float32)
    mq = p.shape[1]
    r2 = jnp.broadcast_to(p[3, 0], (mq,))
    nd = jnp.broadcast_to(p[3, 1], (mq,))
    z = jnp.zeros((mq,), jnp.float32)
    return jnp.stack([p[0], p[1], p[2], r2, nd, z, z, z], axis=1)


def _filter_mask_t(meta_t, pcol, kind):
    """Transposed predicate over points on the lane axis: meta_t
    ``[mq, tn]``, pcol ``[mq, 8]`` from :func:`param_columns` -> bool
    ``[1, tn]``.  Same semantics as :func:`_filter_mask`; sublanes past
    the metadata width pass every test (box bounds default to +/-1e30,
    the ball's ndim stops at the center's length)."""
    mq = meta_t.shape[0]
    inside = (meta_t >= pcol[:, 0:1]) & (meta_t <= pcol[:, 1:2])
    in_box = jnp.min(jnp.where(inside, 1.0, 0.0), axis=0,
                     keepdims=True) > 0.5
    row = jax.lax.broadcasted_iota(jnp.int32, (mq, 1), 0).astype(jnp.float32)
    diff = meta_t - pcol[:, 2:3]
    d2 = jnp.sum(jnp.where(row < pcol[:, 4:5], diff * diff, 0.0), axis=0,
                 keepdims=True)
    in_ball = d2 <= pcol[0:1, 3:4]
    if kind == "none":
        # padding rows carry meta = +2e30 and must still fail:
        return meta_t[0:1, :] < _POS
    if kind == "box":
        return in_box
    if kind == "ball":
        return in_ball
    if kind == "box_ball":
        return in_box & in_ball
    return in_box & ~in_ball                       # box_not_ball


def param_rows(params, m: int):
    """Per-row packed filters ``[bq, 4, mpad]`` -> the row layout
    ``[bq, L]`` the per-row predicate reads (``L`` the lanes of ``3m + 2``
    columns, rounded up to 128): lanes ``[0, m)`` hold each row's box lo,
    ``[m, 2m)`` its box hi, ``[2m, 3m)`` its ball center, lane ``3m`` its
    r^2 and lane ``3m + 1`` its ball ndim, so every operand of
    :func:`_filter_mask_rows` is a static ``[bq, 1]`` lane slice."""
    p = jnp.asarray(params, jnp.float32)
    rows = jnp.concatenate([p[:, 0, :m], p[:, 1, :m], p[:, 2, :m],
                            p[:, 3, 0:2]], axis=1)
    lanes = -(-(3 * m + 2) // 128) * 128
    return jnp.pad(rows, ((0, 0), (0, lanes - rows.shape[1])))


def _filter_mask_rows(meta_t, prow, kind, m):
    """Per-row predicate over points on the lane axis: meta_t ``[mq,
    tn]``, prow ``[tq, L]`` from :func:`param_rows` -> bool ``[tq, tn]``
    (``[1, tn]`` for ``"none"``).  Each query row tests its own filter
    over the ``m`` real metadata dims only (the dims past them pass every
    shared test, see :func:`_filter_mask_t`), with the shared predicate's
    float32 operations: inclusive box compares, and the ball's ``diff *
    diff`` summed over its dims in order, so a row's mask is bit-for-bit
    what a shared call with its filter computes."""
    if kind == "none":
        # padding rows carry meta = +2e30 and must still fail:
        return meta_t[0:1, :] < _POS
    in_box = d2 = None
    nd = prow[:, 3 * m + 1:3 * m + 2]
    for c in range(m):
        v = meta_t[c:c + 1, :]                                  # [1, tn]
        if kind != "ball":
            inside = ((v >= prow[:, c:c + 1])
                      & (v <= prow[:, m + c:m + c + 1]))        # [tq, tn]
            in_box = inside if in_box is None else in_box & inside
        if kind != "box":
            diff = v - prow[:, 2 * m + c:2 * m + c + 1]
            sq = jnp.where(float(c) < nd, diff * diff, 0.0)
            d2 = sq if d2 is None else d2 + sq
    if kind == "box":
        return in_box
    in_ball = d2 <= prow[:, 3 * m:3 * m + 1]
    if kind == "ball":
        return in_ball
    if kind == "box_ball":
        return in_box & in_ball
    return in_box & ~in_ball                       # box_not_ball


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: on the CPU backend
    (tests, rehearsals) they must; on a TPU they never do — Mosaic
    compiles them, and a kernel Mosaic refuses raises instead of falling
    back.  The one place the mode is decided; every ``*_kernel_call``
    resolves ``interpret=None`` through it."""
    return jax.default_backend() == "cpu"


def _lanes(kpad: int) -> int:
    """Lane width of the running top-k state: the bitonic merge needs
    ``2 * kpad`` lanes, held in whole 128-lane vregs."""
    return max(128, 2 * kpad)


def _tile_topk(d, base, kpad, width):
    """``kpad`` rounds of min extraction over ``d [tq, tn]`` (one-hot
    masking, no scatter).  The r-th smallest distance lands in lane
    ``2 * kpad - 1 - r`` of a ``[tq, width]`` pair, i.e. lanes
    ``[kpad, 2 * kpad)`` hold the tile's top-kpad *descending* — the
    second half of a bitonic sequence, built in place by lane selects.
    Ties pick the lowest column, as ``argmin`` does."""
    tq, tn = d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, tn), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tq, width), 1)

    def extract(r, carry):
        d, out_d, out_i = carry
        mn = jnp.min(d, axis=1, keepdims=True)                  # [tq, 1]
        am = jnp.min(jnp.where(d == mn, col, tn), axis=1, keepdims=True)
        at = lane == 2 * kpad - 1 - r
        out_d = jnp.where(at, mn, out_d)
        out_i = jnp.where(at, jnp.where(mn < jnp.inf, base + am, -1), out_i)
        return jnp.where(col == am, jnp.inf, d), out_d, out_i

    init = (d, jnp.full((tq, width), jnp.inf, jnp.float32),
            jnp.full((tq, width), -1, jnp.int32))
    _, out_d, out_i = jax.lax.fori_loop(0, kpad, extract, init)
    return out_d, out_i


def _merge_sorted(run_d, run_i, tile_d, tile_i, kpad):
    """Bitonic merge of the running top-kpad (ascending, lanes
    ``[0, kpad)`` of ``run_*``) with a tile's top-kpad (descending, lanes
    ``[kpad, 2 * kpad)`` of ``tile_*``, see :func:`_tile_topk`) ->
    ascending top-kpad in lanes ``[0, kpad)`` of the returned pair.

    Each stage compares lane ``p`` with its partner ``p ^ stride``: a
    lower lane reads lane ``p + stride`` and an upper lane ``p - stride``,
    each a lane rotation (``pltpu.roll`` rotates like ``jnp.roll``), so
    the network needs no reshape, reversal or gather (all of which Mosaic
    refuses).  The lower lane keeps the min and swaps only on strict
    ``>``, so ties keep their order."""
    tq, width = run_d.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (tq, width), 1)
    d = jnp.where(lane < kpad, run_d, tile_d)
    i = jnp.where(lane < kpad, run_i, tile_i)
    stride = kpad
    while stride >= 1:
        is_lo = (lane & stride) == 0
        pd = jnp.where(is_lo, pltpu.roll(d, width - stride, 1),
                       pltpu.roll(d, stride, 1))
        pi = jnp.where(is_lo, pltpu.roll(i, width - stride, 1),
                       pltpu.roll(i, stride, 1))
        swap = (is_lo & (d > pd)) | (~is_lo & (pd > d))
        d = jnp.where(swap, pd, d)
        i = jnp.where(swap, pi, i)
        stride //= 2
    return d, i


def _fold_tile(d, ok, run_d, run_i, od_ref, oi_ref, *, kpad, tn, n_ctiles):
    """Shared tail of the scan kernels: mask one candidate tile's
    distances ``d [tq, tn]`` by the predicate ``ok`` (``[1, tn]`` shared
    by the query rows, or ``[tq, tn]`` per row), fold its
    top-kpad into the running list in VMEM scratch, and emit the list
    after the last candidate tile (grid axis 1)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full(run_d.shape, jnp.inf, jnp.float32)
        run_i[...] = jnp.full(run_i.shape, -1, jnp.int32)

    d = jnp.where(ok, d, jnp.inf)
    tile_d, tile_i = _tile_topk(d, j * tn, kpad, run_d.shape[1])
    nd, ni = _merge_sorted(run_d[...], run_i[...], tile_d, tile_i, kpad)
    run_d[...] = nd
    run_i[...] = ni

    @pl.when(j == n_ctiles - 1)
    def _emit():
        od_ref[...] = run_d[...]
        oi_ref[...] = run_i[...]


def _fused_kernel(q_ref, x_ref, s_ref, p_ref, od_ref, oi_ref,
                  run_d, run_i, *, metric, kind, kpad, tn, n_ctiles,
                  row_m):
    q = q_ref[...]
    x = x_ref[...]
    ip = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
    if metric == "l2":
        qf = q.astype(jnp.float32)
        xf = x.astype(jnp.float32)
        d = (jnp.sum(qf * qf, axis=1)[:, None] - 2.0 * ip
             + jnp.sum(xf * xf, axis=1)[None, :])
    else:
        d = -ip
    meta_t = jnp.transpose(s_ref[...])
    if row_m is None:
        ok = _filter_mask_t(meta_t, p_ref[...], kind)
    else:
        ok = _filter_mask_rows(meta_t, p_ref[...], kind, row_m)
    _fold_tile(d, ok, run_d, run_i, od_ref, oi_ref, kpad=kpad, tn=tn,
               n_ctiles=n_ctiles)


@functools.partial(jax.jit, static_argnames=("metric", "kind", "kpad", "tq",
                                             "tn", "m", "interpret"))
def filtered_topk_kernel_call(q, x, s_pad, params, *, kind: str, kpad: int,
                              metric: str = "l2", tq: int = 64, tn: int = 256,
                              m: Optional[int] = None,
                              interpret: Optional[bool] = None):
    """Fused filtered top-k.  Pre-padded inputs:
    q [bq, d] (bq % tq == 0, d % 128 == 0), x [n, d] (n % tn == 0),
    s_pad [n, mpad] metadata padded to 128 lanes (+2e30 in padding rows so
    they fail every predicate), params [4, mpad] packed filter
    (box lo/hi, ball center, [r^2, ball_ndim]) shared by every query row,
    or [bq, 4, mpad]: one packed filter per query row, each row tested
    against its own over the first ``m`` metadata columns (the real ones,
    required with per-row params).  kpad power of two <= tn.
    Returns (dists [bq, kpad] ascending, ids [bq, kpad], -1 for misses).
    ``interpret=None`` takes the mode from :func:`interpret_mode`.
    """
    assert kpad & (kpad - 1) == 0 and kpad <= tn
    if interpret is None:
        interpret = interpret_mode()
    bq, d = q.shape
    n, mpad = s_pad.shape
    grid = (bq // tq, n // tn)
    width = _lanes(kpad)
    if params.ndim == 3:
        assert params.shape[0] == bq and m is not None and 1 <= m <= mpad
        row_m = m
        p_in = param_rows(params, m)
        p_spec = pl.BlockSpec((tq, p_in.shape[1]), lambda i, j: (i, 0))
    else:
        row_m = None
        p_in = param_columns(params)
        p_spec = pl.BlockSpec((mpad, 8), lambda i, j: (0, 0))
    kern = functools.partial(_fused_kernel, metric=metric, kind=kind,
                             kpad=kpad, tn=tn, n_ctiles=grid[1], row_m=row_m)
    dd, ids = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((tn, mpad), lambda i, j: (j, 0)),
            p_spec,
        ],
        out_specs=[
            pl.BlockSpec((tq, width), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, width), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bq, width), jnp.float32),
            jax.ShapeDtypeStruct((bq, width), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, width), jnp.float32),
            pltpu.VMEM((tq, width), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, x, s_pad, p_in)
    return dd[:, :kpad], ids[:, :kpad]
