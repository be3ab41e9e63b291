"""Pallas TPU kernel: fused asymmetric-distance filtered top-k over int8
segment codes.

The quantized sibling of ``filtered_topk.py``: per grid step an int8
``[dq, tn]`` code tile and its fp32 ``[mq, tn]`` *transposed* metadata tile
are resident in VMEM; the kernel

  1. contracts the scale-folded fp32 query block against the raw int8
     codes on the MXU (``(q * scale) . codes == q . dequantize(codes)`` —
     the asymmetric-distance identity: the database stays int8, only the
     tiny query is touched at fp32),
  2. evaluates the same packed filter predicate as the fp32 kernel on the
     VPU (identical semantics over the transposed tile) and masks failures
     to +inf,
  3. folds the tile into a running top-k in VMEM scratch via the shared
     argmin-extraction + bitonic-merge networks of ``filtered_topk``.

Layout notes (why transposed): with points on the *lane* axis the code
tile is ``[dq, tn]`` (``dq`` = dim padded to the int8 sublane tile of 32)
and the metadata tile is ``[mq, tn]`` (``mq`` = meta dims + 1 padded to
the fp32 sublane tile of 8) — so a d=32, m=3 point costs 32 B of codes and
32 B of metadata on device instead of the fp32 layout's 512 B + 512 B.
The last metadata sublane carries the point's precomputed dequantized
squared norm (``xsq``); filter params never constrain sublanes >= m, so it
rides the predicate tile for free.  For L2 the kernel emits the partial
distance ``xsq - 2 * ip`` — the per-query constant ``||q||^2`` never
changes a row's ranking, so the wrapper adds it after the kernel to make
distances comparable with exact fp32 blocks.

Returns an *over-fetched* candidate list (the caller sizes ``kpad`` by its
rerank multiple); the exact fp32 rerank happens downstream
(``repro.quant.rerank``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .filtered_topk import (_filter_mask_t, _fold_tile, _lanes,
                            interpret_mode, param_columns)

__all__ = ["quant_filtered_topk_kernel_call"]



def _quant_fused_kernel(q_ref, c_ref, st_ref, p_ref, od_ref, oi_ref,
                        run_d, run_i, *, metric, kind, kpad, tn, n_ctiles):
    qs = q_ref[...]                                 # [tq, dq] scale-folded
    c = c_ref[...].astype(jnp.float32)              # [dq, tn] int8 -> f32
    st = st_ref[...]                                # [mq, tn] meta + xsq
    ip = jax.lax.dot_general(qs, c, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
    mq = st.shape[0]
    if metric == "l2":
        # partial asymmetric L2: ||q||^2 is added by the wrapper (a
        # per-query constant never reorders a query row's top-k)
        d = st[mq - 1:mq, :] - 2.0 * ip
    else:
        d = -ip
    _fold_tile(d, _filter_mask_t(st, p_ref[...], kind), run_d, run_i,
               od_ref, oi_ref, kpad=kpad, tn=tn, n_ctiles=n_ctiles)


@functools.partial(jax.jit, static_argnames=("metric", "kind", "kpad", "tq",
                                             "tn", "interpret"))
def quant_filtered_topk_kernel_call(qs, codes_t, st, params_t, *, kind: str,
                                    kpad: int, metric: str = "l2",
                                    tq: int = 64, tn: int = 256,
                                    interpret: Optional[bool] = None):
    """Fused asymmetric-distance filtered top-k.  Pre-padded inputs:
    qs [bq, dq] fp32 scale-folded queries (bq % tq == 0), codes_t [dq, n]
    int8 (n % tn == 0), st [mq, n] transposed fp32 metadata whose last
    sublane is the dequantized squared norm (+2e30 in padding columns so
    they fail every predicate), params_t [4, mq] packed filter.  kpad
    power of two <= tn.  Returns (dists [bq, kpad] ascending — for L2
    *without* the ||q||^2 term, ids [bq, kpad], -1 for misses).
    ``interpret=None`` takes the mode from ``interpret_mode()``.
    """
    assert kpad & (kpad - 1) == 0 and kpad <= tn
    if interpret is None:
        interpret = interpret_mode()
    bq, dq = qs.shape
    mq, n = st.shape
    grid = (bq // tq, n // tn)
    width = _lanes(kpad)
    kern = functools.partial(_quant_fused_kernel, metric=metric, kind=kind,
                             kpad=kpad, tn=tn, n_ctiles=grid[1])
    dd, ids = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, dq), lambda i, j: (i, 0)),
            pl.BlockSpec((dq, tn), lambda i, j: (0, j)),
            pl.BlockSpec((mq, tn), lambda i, j: (0, j)),
            pl.BlockSpec((mq, 8), lambda i, j: (0, 0)),
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
    )(qs, codes_t, st, param_columns(params_t))
    return dd[:, :kpad], ids[:, :kpad]
