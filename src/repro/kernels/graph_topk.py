"""Pallas beam-step kernel + stitched per-bucket graph traversal.

The graph read path for sealed segments (ROADMAP item 1, paper §4.3): a
bucketed shard pack can carry, next to its fp32 or int8 scan blocks, a
``[rows, cap, degp]`` adjacency block of *flattened bucket positions*
(``row * cap + col``) staged from each sealed segment's coarsest CubeGraph
layer.  This module traverses that block with a batched best-first beam
search whose hot step — neighbor-candidate distance + fused predicate mask
— is a Pallas kernel in the spirit of ``kernels/filtered_topk.py``:

  1. the traced outer loop (``lax.while_loop``, fixed-shape state exactly
     like ``core/search.py``) gathers the top-W frontier's neighbor
     positions and their vectors/metadata from the bucket block;
  2. the kernel scores the gathered ``[b, c, d]`` candidate tile on the
     MXU and evaluates the packed filter predicate on the VPU, emitting
     raw distances (for routing) and the predicate mask (for collection)
     in one pass;
  3. beam and result merges are masked top-k over fixed shapes.

Stitching rule: the beam is seeded with the union of entry points of every
temporally active segment in the bucket (``bucket_graph_seeds``), so a
bucket holding many segments is traversed in ONE pass — routing is
"all"-style inside the bucket (dead points were dropped at pack staging;
edges never cross segment boundaries, seeds are what stitch components),
while collection applies the predicate φ.

Quantized buckets traverse the same way: candidates are dequantized on
gather (``codes * scales``) and the kernel recomputes their norms, so one
kernel serves both layouts; the caller reranks quantized results exactly
at fp32, exactly as on the scan path.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .filtered_topk import (_filter_mask, _filter_mask_t, interpret_mode,
                            param_columns)
from .ops import block_mesh, encode_filter, next_pow2

__all__ = ["beam_step_scores", "bucket_graph_topk"]

_MPAD = 128                      # metadata lane padding (kernel layout)
_TQ = 8                          # query-tile rows per kernel program
_TC = 512                        # candidates per kernel program (VMEM)
_PAD_META = 2e30                 # metadata of padding candidates
INF = jnp.float32(np.inf)


def _beam_step_kernel(q_ref, cx_ref, cm_ref, p_ref, od_ref, ok_ref,
                      *, metric, kind):
    """One beam step's fused score: q [tq, dp], candidates cx [tq, c, dp]
    with metadata cm [tq, c, mpad] and the filter in column layout p
    [mpad, 8] (``param_columns``) -> raw distances od [tq, c] + predicate
    mask ok [tq, c] (int32 0/1).  Distances are *unmasked* (routing
    ignores φ); the caller combines both outputs for collection.  The
    predicate is the scan kernels' transposed one, row by row: each query
    row's metadata tile is transposed so its points lie on lanes."""
    q = q_ref[...]
    cx = cx_ref[...]
    tq, c, _ = cx.shape
    ip = jax.lax.dot_general(cx, q, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)  # [tq, c]
    if metric == "l2":
        qn = jnp.sum(q.astype(jnp.float32) ** 2, axis=1)
        xn = jnp.sum(cx.astype(jnp.float32) ** 2, axis=2)
        d = xn - 2.0 * ip + qn[:, None]
    else:
        d = -ip
    pcol = p_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (tq, c), 0)
    ok = jnp.zeros((tq, c), jnp.int32)
    for r in range(tq):
        ok_r = _filter_mask_t(jnp.transpose(cm_ref[r]), pcol, kind)
        ok = jnp.where((row == r) & ok_r, 1, ok)
    od_ref[...] = d
    ok_ref[...] = ok


def beam_step_scores(q, cand_x, cand_meta, params, *, kind: str,
                     metric: str = "l2", interpret: Optional[bool] = None):
    """Score one gathered candidate tile.  ``q [b, dp]`` (b % 8 == 0),
    ``cand_x [b, c, dp]``, ``cand_meta [b, c, mpad]``, ``params [4, mpad]``
    -> ``(dists [b, c] fp32 raw, ok [b, c] int32 predicate mask)``.
    Traced — safe to call from inside a ``lax.while_loop`` body.
    ``interpret=None`` takes the mode from ``interpret_mode()``.

    The candidate axis is tiled by ``_TC`` (padded up to a multiple when
    longer), so VMEM stays bounded however many candidates a step scores
    — the stitched seed set of a large bucket runs to thousands."""
    from jax.experimental import pallas as pl
    if interpret is None:
        interpret = interpret_mode()
    b, c, dp = cand_x.shape
    mpad = cand_meta.shape[-1]
    tc = c if c <= _TC else _TC
    cp = -(-c // tc) * tc
    if cp != c:                    # padding candidates: zero vector, and
        pad = ((0, 0), (0, cp - c), (0, 0))      # metadata failing φ
        cand_x = jnp.pad(cand_x, pad)
        cand_meta = jnp.pad(cand_meta, pad, constant_values=_PAD_META)
    kern = functools.partial(_beam_step_kernel, metric=metric, kind=kind)
    d, ok = pl.pallas_call(
        kern,
        grid=(b // _TQ, cp // tc),
        in_specs=[
            pl.BlockSpec((_TQ, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((_TQ, tc, dp), lambda i, j: (i, j, 0)),
            pl.BlockSpec((_TQ, tc, mpad), lambda i, j: (i, j, 0)),
            pl.BlockSpec((mpad, 8), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_TQ, tc), lambda i, j: (i, j)),
            pl.BlockSpec((_TQ, tc), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, cp), jnp.float32),
            jax.ShapeDtypeStruct((b, cp), jnp.int32),
        ],
        interpret=interpret,
    )(q, cand_x, cand_meta, param_columns(params))
    return d[:, :c], ok[:, :c]


def _score_candidates_jnp(q, cx, cm, params, *, kind: str, metric: str):
    """Pure-jnp twin of :func:`beam_step_scores` — the same dot_general /
    norm / ``_filter_mask`` math, inlined into the traced traversal loop.

    On CPU the Pallas kernel only runs in interpret mode, and a traversal
    makes one kernel call *per hop* (30-50 sequential calls), so interpret
    overhead dominates end-to-end latency by orders of magnitude; this
    twin compiles into the ``while_loop`` body as ordinary XLA.  A TPU
    keeps the fused kernel (``use_pallas``)."""
    b, c, _ = cx.shape
    ip = jax.lax.dot_general(cx, q, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
    if metric == "l2":
        qn = jnp.sum(q.astype(jnp.float32) ** 2, axis=1)
        xn = jnp.sum(cx.astype(jnp.float32) ** 2, axis=2)
        d = xn - 2.0 * ip + qn[:, None]
    else:
        d = -ip
    ok = _filter_mask(cm.reshape(b * c, -1), params, kind).reshape(b, c)
    return d, ok.astype(jnp.int32)


def _unique_mask(ids):
    # first occurrence of each id per row (candidate dedupe), [b, c] bool
    order = jnp.argsort(ids, axis=1)
    sorted_ids = jnp.take_along_axis(ids, order, axis=1)
    first = jnp.concatenate(
        [jnp.ones_like(sorted_ids[:, :1], bool),
         sorted_ids[:, 1:] != sorted_ids[:, :-1]], axis=1)
    out = jnp.zeros_like(first)
    b = ids.shape[0]
    return out.at[jnp.arange(b)[:, None], order].set(first)


def _merge_topk(ids_a, d_a, ids_b, d_b, k):
    ids = jnp.concatenate([ids_a, ids_b], axis=1)
    d = jnp.concatenate([d_a, d_b], axis=1)
    nd, sel = jax.lax.top_k(-d, k)
    return jnp.take_along_axis(ids, sel, axis=1), -nd


@functools.partial(jax.jit, static_argnames=(
    "k", "ef", "width", "max_iters", "kind", "metric", "m", "quantized",
    "use_pallas", "mesh"))
def _traverse(q, gids, nbrs, x, s, codes, st, scales, params, seeds,
              k, ef, width, max_iters, kind, metric, m, quantized,
              use_pallas, mesh=None):
    """Stitched best-first traversal over one bucket block.  All shapes are
    static per (bucket geometry, seed pad, k/ef/width) so repeat dispatches
    hit the jit cache.  Returns (positions [b, k], dists [b, k], hops).

    Over a mesh (``mesh`` is the one the block is partitioned over) the
    gathers stay XLA ops and the beam-step kernel runs under
    ``shard_map`` on the replicated candidate tile — XLA cannot partition
    a Mosaic call itself."""
    score = functools.partial(beam_step_scores, kind=kind, metric=metric)
    if use_pallas and mesh is not None:
        score = jax.shard_map(score, mesh=mesh, in_specs=(P(),) * 4,
                              out_specs=(P(), P()), check_vma=False)
    rows, cap = gids.shape
    b = q.shape[0]
    npos = rows * cap
    # ef-wide internal result list (classic ef-search): terminating against
    # the k-th result alone is too greedy and costs recall; the caller gets
    # the top-k slice of the ef-wide list
    kc = max(k, ef)

    def gather_score(pos):                 # pos [b, c] flattened positions
        safe = jnp.maximum(pos, 0)
        rv, cv = safe // cap, safe % cap
        gid = gids[rv, cv]                             # [b, c]
        if quantized:
            cx = codes[rv, :, cv].astype(jnp.float32) * scales[rv]
            meta = st[rv, :, cv]                       # [b, c, mq]
            mq = meta.shape[-1]
            cm = jnp.zeros(meta.shape[:2] + (_MPAD,), jnp.float32)
            cm = cm.at[..., :mq].set(meta)
        else:
            cx = x[rv, cv]                             # [b, c, dp]
            cm = s[rv, cv]                             # [b, c, mpad]
        if use_pallas:
            d, ok = score(q, cx, cm, params)
        else:
            d, ok = _score_candidates_jnp(q, cx, cm, params, kind=kind,
                                          metric=metric)
        return gid, d, ok.astype(bool)

    # ---- init from the stitched seed set (shared across the batch) -------
    S = seeds.shape[0]
    seed_b = jnp.broadcast_to(seeds[None, :], (b, S))
    gid0, d0, ok0 = gather_score(seed_b)
    valid0 = (seed_b >= 0) & (gid0 >= 0) & _unique_mask(seed_b)
    droute0 = jnp.where(valid0, d0, INF)
    dres0 = jnp.where(valid0 & ok0, d0, INF)

    visited = jnp.zeros((b, npos), bool)
    visited = visited.at[:, jnp.maximum(seeds, 0)].max(
        jnp.broadcast_to(seeds >= 0, (b, S)))

    pad_i = jnp.full((b, ef), -1, jnp.int32)
    pad_d = jnp.full((b, ef), INF)
    beam_pos, beam_d = _merge_topk(
        pad_i, pad_d, jnp.where(valid0, seed_b, -1), droute0, ef)
    beam_exp = jnp.zeros((b, ef), bool)
    res_pos, res_d = _merge_topk(
        jnp.full((b, kc), -1, jnp.int32), jnp.full((b, kc), INF),
        jnp.where(jnp.isfinite(dres0), seed_b, -1), dres0, kc)

    state = (beam_pos, beam_d, beam_exp, res_pos, res_d, visited,
             jnp.int32(0))

    def cond(st_):
        beam_pos, beam_d, beam_exp, _, res_d, _, it = st_
        frontier = jnp.where(beam_exp | (beam_pos < 0), INF, beam_d)
        best = jnp.min(frontier, axis=1)
        return (it < max_iters) & jnp.any(best < res_d[:, kc - 1])

    def body(st_):
        beam_pos, beam_d, beam_exp, res_pos, res_d, visited, it = st_
        frontier = jnp.where(beam_exp | (beam_pos < 0), INF, beam_d)
        kth = res_d[:, kc - 1]
        negd, sel = jax.lax.top_k(-frontier, width)
        exp_ok = (-negd) < kth[:, None]                # only expand improving
        exp_pos = jnp.where(
            exp_ok, jnp.take_along_axis(beam_pos, sel, axis=1), -1)
        beam_exp = beam_exp.at[jnp.arange(b)[:, None], sel].set(True)

        safe = jnp.maximum(exp_pos, 0)
        nb = nbrs[safe // cap, safe % cap]             # [b, w, degp]
        nb = jnp.where(exp_pos[:, :, None] >= 0, nb, -1)
        cand = nb.reshape(b, -1)

        gid, d, ok = gather_score(cand)
        fresh = (cand >= 0) & (gid >= 0)
        fresh &= ~jnp.take_along_axis(visited, jnp.maximum(cand, 0), axis=1)
        fresh &= _unique_mask(cand)
        droute = jnp.where(fresh, d, INF)
        dres = jnp.where(fresh & ok, d, INF)
        visited = visited.at[
            jnp.arange(b)[:, None], jnp.maximum(cand, 0)].max(fresh)

        ids2 = jnp.concatenate([beam_pos, jnp.where(fresh, cand, -1)],
                               axis=1)
        dd2 = jnp.concatenate([beam_d, droute], axis=1)
        ee2 = jnp.concatenate([beam_exp, jnp.zeros_like(cand, bool)], axis=1)
        ndd, sel2 = jax.lax.top_k(-dd2, ef)
        take = lambda a: jnp.take_along_axis(a, sel2, axis=1)
        beam_pos, beam_d, beam_exp = take(ids2), -ndd, take(ee2)

        res_pos, res_d = _merge_topk(
            res_pos, res_d, jnp.where(jnp.isfinite(dres), cand, -1), dres,
            kc)
        return (beam_pos, beam_d, beam_exp, res_pos, res_d, visited, it + 1)

    final = jax.lax.while_loop(cond, body, state)
    res_pos, res_d, hops = final[3], final[4], final[6]
    res_pos = jnp.where(jnp.isfinite(res_d), res_pos, -1)
    # deterministic (dist, gid) output ordering — same invariant as the
    # scan path's host_topk merge
    safe = jnp.maximum(res_pos, 0)
    g = jnp.where(res_pos >= 0, gids[safe // cap, safe % cap], -1)
    key = jnp.where(g >= 0, g, jnp.iinfo(jnp.int32).max)
    order = jnp.lexsort((key, res_d), axis=-1)
    g = jnp.take_along_axis(g, order, axis=1)[:, :k]
    res_d = jnp.take_along_axis(res_d, order, axis=1)[:, :k]
    return g, res_d, hops


def bucket_graph_topk(queries, bv, seeds, filt, k: int, *, m: int,
                      metric: str = "l2", ef: int = 64, width: int = 4,
                      max_iters: int = 128
                      ) -> Optional[Tuple[np.ndarray, np.ndarray, int]]:
    """Traverse one bucket's stitched graph block.

    ``queries [b, d]``; ``bv`` a ``BucketView`` carrying ``nbrs``;
    ``seeds`` the flattened positions from ``bucket_graph_seeds``; ``m``
    the true metadata width.  Returns ``(gids [b, k] int64 with -1
    misses, dists [b, k] fp32 ascending, hops)`` — fp32 buckets emit exact
    distances, quantized buckets emit asymmetric-distance candidates the
    caller must rerank.  Returns ``None`` when the filter has no kernel
    encoding or the bucket has no usable graph/seeds (caller falls back to
    the scan path).  Hops score with the fused kernel on a TPU and with
    its pure-jnp twin where kernels run interpreted — interpret-mode
    Pallas pays per-call overhead once per hop, which dominates traversal
    latency on CPU."""
    if bv.nbrs is None or len(seeds) == 0:
        return None
    enc = encode_filter(filt, m)
    if enc is None:
        return None
    kind, params = enc
    q = np.atleast_2d(np.asarray(queries, np.float32))
    b, d = q.shape
    quantized = bv.quantized
    dp = int(bv.codes.shape[1]) if quantized else int(bv.x.shape[2])
    qp = np.zeros((-(-b // _TQ) * _TQ, dp), np.float32)
    qp[:b, :d] = q
    sp = np.full(next_pow2(max(len(seeds), 4)), -1, np.int64)
    sp[: len(seeds)] = seeds
    k = int(k)
    ef = max(int(ef), k)
    g, dd, hops = _traverse(
        jnp.asarray(qp), bv.gids, bv.nbrs,
        bv.x, bv.s, bv.codes, bv.st, bv.scales,
        jnp.asarray(params), jnp.asarray(sp, jnp.int32),
        k, ef, int(width), int(max_iters), kind, metric, int(m),
        quantized, not interpret_mode(),
        block_mesh(bv.codes if quantized else bv.x))
    return (np.asarray(g[:b], np.int64), np.asarray(dd[:b], np.float32),
            int(hops))
