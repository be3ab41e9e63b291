"""Pallas TPU kernel: tiled pairwise distance matrix (MXU contraction).

Computes squared-L2 (or negated inner-product) distances between a query
block and the candidate set, tiled so each grid step's working set
(``[tq, d] + [tn, d] + [tq, tn]``) stays in VMEM with 128-aligned matmul
dims.  Used by graph construction (exact kNN candidate generation) and by
the brute-force scan path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .filtered_topk import interpret_mode

__all__ = ["pairwise_dist_kernel_call"]


def _dist_kernel(q_ref, x_ref, o_ref, *, metric: str):
    q = q_ref[...]
    x = x_ref[...]
    ip = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if metric == "l2":
        qf = q.astype(jnp.float32)
        xf = x.astype(jnp.float32)
        qn = jnp.sum(qf * qf, axis=1)
        xn = jnp.sum(xf * xf, axis=1)
        o_ref[...] = qn[:, None] - 2.0 * ip + xn[None, :]
    else:
        o_ref[...] = -ip


@functools.partial(jax.jit, static_argnames=("metric", "tq", "tn", "interpret"))
def pairwise_dist_kernel_call(q, x, metric: str = "l2", tq: int = 128,
                              tn: int = 512,
                              interpret: Optional[bool] = None):
    """[bq, d] x [n, d] -> [bq, n] distances via a (bq/tq, n/tn) Pallas grid.

    Inputs must be pre-padded: bq % tq == 0, n % tn == 0, d % 128 == 0
    (see ``ops.pairwise_dist`` for the padding wrapper).
    """
    if interpret is None:
        interpret = interpret_mode()
    bq, d = q.shape
    n = x.shape[0]
    grid = (bq // tq, n // tn)
    return pl.pallas_call(
        functools.partial(_dist_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tq, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bq, n), jnp.float32),
        interpret=interpret,
    )(q, x)
