"""Exact fp32 rerank of a quantized scan's over-fetched candidates.

The quantized read path returns approximate ``(gid, dist)`` candidates;
this stage gathers the candidates' original fp32 vectors row by row (from
the manager's point store — the ledger that already serves point
lookups), uploads them once as a ``[b_pad, w_pad, d]`` block and scores
and selects them in one jitted program at ``Precision.HIGHEST``
(``repro.kernels.ops.rerank_topk``).  The block's shape follows the
padded query rows and the caller's candidate width, never the candidates
found, so no new candidate count compiles.  The result is normalized
through ``repro.distributed.segment_shards.host_topk`` so the output
obeys the same deterministic ``(dist, gid)`` tie-break as the
unquantized merge (``streaming.query.merge_topk``).  Downstream, the
reranked block is indistinguishable from an exact fp32 segment block.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import numpy as np

__all__ = ["rerank_exact"]

RERANK_ROWS = 64     # query rows pad to the int8 dispatch's query tile
_NO_GID = np.iinfo(np.int64).max


def rerank_exact(queries: np.ndarray, cand_gids: np.ndarray, k: int,
                 lookup: Callable, metric: str = "l2",
                 part_width: Optional[int] = None, parts: int = 1,
                 trace=None, registry=None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Candidate gids ``[b, s]`` (``-1`` padded) -> exact fp32
    ``(gids [b, k], dists [b, k])``.

    ``lookup(gids) -> (x, s, present)`` supplies the original fp32 vectors
    (``SegmentManager.get_points``); candidates whose row is gone
    (``present=False`` — only possible once every gid in their store chunk
    is dead) are dropped, which matches the downstream liveness filter.
    Each row's candidates are sorted by gid, and a gid repeated within a
    row (impossible from disjoint segment blocks, cheap to guard) is kept
    once, so distance ties at the k-th boundary resolve to the smallest
    gid — the exact ordering contract of ``host_topk``.

    The uploaded block is ``[b_pad, w_pad, d]``: ``b_pad`` the rows
    rounded up to :data:`RERANK_ROWS`, ``w_pad`` ``parts`` candidate
    lists of ``part_width`` rounded up to the lane width
    (``kernels.ops.rerank_width``; ``s`` when ``part_width`` is None).
    With ``part_width`` given, the rerank's signature joins the warm
    registry.  ``trace`` times ``rerank_gather`` (the host gather and the
    one upload; attributes ``slots`` = ``b_pad * w_pad`` and
    ``candidates``, the real ones) and ``rerank_score`` (the program and
    the wait for its result); ``registry`` counts ``rerank_rows_total``,
    ``rerank_candidates_total``, ``rerank_slots_total`` and
    ``rerank_upload_bytes_total``.
    """
    from ..distributed.segment_shards import host_topk
    from ..kernels.ops import rerank_topk, rerank_width, round_up
    from ..obs.metrics import NULL_REGISTRY
    from ..obs.trace import NULL_TRACE

    trace = NULL_TRACE if trace is None else trace
    registry = NULL_REGISTRY if registry is None else registry
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    cand = np.atleast_2d(np.asarray(cand_gids, np.int64))
    b, s = cand.shape
    w_pad = rerank_width(*((part_width, parts) if part_width is not None
                           else (s, 1)))
    if s > w_pad:
        raise ValueError(f"{s} candidates per row exceed the rerank's "
                         f"{w_pad} slots")
    b_pad = round_up(b, RERANK_ROWS)
    registry.counter("rerank_rows_total").inc(b)
    with trace.span("rerank_gather") as sp:
        srt = np.sort(np.where(cand >= 0, cand, _NO_GID), axis=1)
        valid = srt != _NO_GID
        valid[:, 1:] &= srt[:, 1:] != srt[:, :-1]
        if not valid.any():
            return (np.full((b, k), -1, np.int64),
                    np.full((b, k), np.inf, np.float32))
        x, _, present = lookup(srt[valid])
        valid[valid] = present
        d = queries.shape[1]
        xs = np.zeros((b_pad, w_pad, d), np.float32)
        xs[:b, :s][valid] = np.asarray(x, np.float32)[present]
        ok = np.zeros((b_pad, w_pad), bool)
        ok[:b, :s] = valid
        qp = np.zeros((b_pad, d), np.float32)
        qp[:b] = queries
        n_cand = int(valid.sum())
        nbytes = qp.nbytes + xs.nbytes + ok.nbytes
        args = jax.device_put((qp, xs, ok))
        sp.annotate(slots=b_pad * w_pad, candidates=n_cand)
    registry.counter("rerank_candidates_total").inc(n_cand)
    registry.counter("rerank_slots_total").inc(b_pad * w_pad)
    registry.counter("rerank_upload_bytes_total").inc(nbytes)
    with trace.span("rerank_score"):
        pos, dd = jax.device_get(rerank_topk(*args, k, metric=metric,
                                             part_width=part_width))
    gid_slots = np.full((b, w_pad), -1, np.int64)
    gid_slots[:, :s] = np.where(valid, srt, -1)
    g = np.take_along_axis(gid_slots, np.asarray(pos[:b], np.int64), axis=1)
    return host_topk(g, np.asarray(dd[:b], np.float32), k)
