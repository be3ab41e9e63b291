"""Pure-jnp oracles for the Pallas kernels (the correctness references).

Every kernel in this package is validated against these functions across
shape/dtype sweeps in ``tests/test_kernels.py`` (interpret mode on CPU).
Matmuls run at ``Precision.HIGHEST``: on a TPU the default is one bf16
pass, and these distances must be the fp32 ones the kernels compute.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["pairwise_sq_l2", "pairwise_neg_ip", "filter_mask_ref",
           "filtered_topk_ref"]


def pairwise_sq_l2(q: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """[bq, d] x [n, d] -> squared L2 distances [bq, n] (fp32 accumulation)."""
    q = jnp.asarray(q)
    x = jnp.asarray(x)
    qn = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1)
    xn = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)
    ip = jnp.matmul(q, x.T, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    return qn[:, None] - 2.0 * ip + xn[None, :]


def pairwise_neg_ip(q: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Negated inner product (so smaller = more similar), fp32 accumulation."""
    return -jnp.matmul(q, x.T, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)


def filter_mask_ref(s: jnp.ndarray, kind: str, params: jnp.ndarray) -> jnp.ndarray:
    """Evaluate the packed filter encoding used by the fused kernel.

    ``params`` layout (rows of a [4, m] fp32 array):
      row 0: box lo       row 1: box hi
      row 2: ball center  row 3: [radius^2, ball_ndim, 0, ...]
    kinds: 'none' | 'box' | 'ball' | 'box_not_ball' | 'box_ball'
    """
    s = jnp.asarray(s, jnp.float32)
    m = s.shape[-1]
    in_box = jnp.all((s >= params[0, :m]) & (s <= params[1, :m]), axis=-1)
    mc = params[3, 1].astype(jnp.int32)
    dim_mask = jnp.arange(m) < mc
    d2 = jnp.sum(jnp.where(dim_mask, (s - params[2, :m]) ** 2, 0.0), axis=-1)
    in_ball = d2 <= params[3, 0]
    if kind == "none":
        return jnp.ones(s.shape[:-1], bool)
    if kind == "box":
        return in_box
    if kind == "ball":
        return in_ball
    if kind == "box_not_ball":
        return in_box & ~in_ball
    if kind == "box_ball":
        return in_box & in_ball
    raise ValueError(kind)


def filtered_topk_ref(q, x, s, kind: str, params, k: int, metric: str = "l2"):
    """Fused filtered exact top-k oracle.

    Returns (dists [bq, k] ascending, ids [bq, k]); failing candidates get
    +inf / -1.
    """
    d = pairwise_sq_l2(q, x) if metric == "l2" else pairwise_neg_ip(q, x)
    ok = filter_mask_ref(s, kind, jnp.asarray(params, jnp.float32))
    d = jnp.where(ok[None, :], d, jnp.inf)
    import jax
    neg, ids = jax.lax.top_k(-d, k)
    dd = -neg
    return dd, jnp.where(jnp.isfinite(dd), ids, -1)


def flash_decode_ref(q, k, v, lengths):
    """Oracle for the fused decode-attention kernel.
    q [bkv, g, hd], k/v [bkv, smax, hd], lengths [bkv] (inclusive prefix)."""
    qf = jnp.asarray(q, jnp.float32)
    kf = jnp.asarray(k, jnp.float32)
    vf = jnp.asarray(v, jnp.float32)
    hd = q.shape[-1]
    scores = jnp.einsum("bgd,bsd->bgs", qf, kf) / jnp.sqrt(hd)
    col = jnp.arange(k.shape[1])[None, None, :]
    scores = jnp.where(col <= lengths[:, None, None], scores, -1e30)
    import jax
    attn = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bgs,bsd->bgd", attn, vf).astype(q.dtype)
