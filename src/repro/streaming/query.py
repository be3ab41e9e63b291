"""Unified query path over the delta buffer and sealed segments.

Planning prunes segments whose ``[t_min, t_max]`` span misses the filter's
temporal bounds (extracted from its bounding box — half-open
``IntervalFilter`` windows work directly).  The query then fans out to the
delta buffer (exact fused-kernel scan) and the sealed segments — either one
stitched-graph beam search per segment (default) or, with
``StreamConfig.n_shards >= 1``, one jitted dispatch of the fused kernel
per non-empty, temporally unpruned capacity *bucket* of the manager's
size-bucketed shard pack (temporal pruning skips whole device blocks),
distributed across a device mesh when one is attached.

Merging is a direct exact merge of the per-segment ``(gid, dist)`` pairs:
every path reports the same fp32 distance for the same point and global ids
are disjoint across the delta buffer and segments, so concatenating the
candidate lists and taking the global top-k needs no re-rank — the global
point store stays off the hot path entirely.  The merged result is finally
filtered through the manager's liveness bitmap, which is what makes query
results immune to racing deletions/compactions (see the epoch guarantee in
``repro.streaming.manager``).

With ``StreamConfig(quantize="int8")`` the sealed-pack scan becomes
two-stage: the per-bucket dispatches run the fused asymmetric-distance
kernel over int8 codes and over-fetch ``rerank_multiple * k`` candidates,
which are reranked exactly at fp32 (``repro.quant.rerank``) before
entering the same merge — so the merged block is exact again and the
delta buffer / liveness semantics are untouched.

With ``StreamConfig(read_path="auto"|"graph")`` each sealed-pack dispatch
first runs the cost planner (``repro.streaming.planner``) over the pack's
buckets: buckets planned ``scan`` go through the exact same fused-kernel
calls as above (byte-for-byte — the planner never changes scan answers),
while buckets planned ``graph`` run the stitched beam traversal
(``repro.kernels.graph_topk``) seeded with the entry points of every
temporally unpruned segment resident in the bucket.  fp32 graph blocks
carry exact distances and join the merge directly; quantized graph blocks
are candidate sets that go through the same exact fp32 rerank as the scan
path.  Traversal results are approximate (recall target, not parity), so
``auto`` only picks graph where the planner prices it cheaper.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple

import numpy as np

from ..core import Filter
from ..obs.metrics import NULL_REGISTRY
from ..obs.trace import NULL_TRACE, block_ready
from .resilience import Deadline, QueryResult
from .segments import SegmentQueryStats

__all__ = ["GroupQuery", "merge_topk", "temporal_bounds", "query_segments",
           "query_segments_grouped"]


def temporal_bounds(filt: Optional[Filter], time_dim: int
                    ) -> Tuple[float, float]:
    """Filter -> (t_lo, t_hi) constraint on the time dim; ±inf if none."""
    if filt is None:
        return -np.inf, np.inf
    lo, hi = filt.bounding_box()
    if time_dim >= len(lo):
        return -np.inf, np.inf
    return float(lo[time_dim]), float(hi[time_dim])


def merge_topk(blocks_g: List[np.ndarray], blocks_d: List[np.ndarray],
               k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Exact top-k merge of per-segment/per-bucket ``(gid, dist)`` blocks.

    Blocks are ``[b, k_i]`` with ``-1`` id padding; distances are
    comparable across blocks (same metric over the same vectors), and gids
    are disjoint across blocks, so the top-k of the concatenation is the
    exact global answer.  ``np.argpartition`` narrows each row to ``k``
    candidates before sorting only that slice — O(total + k log k) per row
    instead of a full O(total log total) argsort — and the sort tie-breaks
    equal distances on gid, keeping results deterministic regardless of
    block order.  Returns ``(gids [b, k], dists [b, k])``.
    """
    from ..distributed.segment_shards import host_topk
    return host_topk(np.concatenate(blocks_g, axis=1),
                     np.concatenate(blocks_d, axis=1), k)


def _alive_filter(manager, gids: np.ndarray, dists: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Drop merged candidates whose gid has since been deleted/expired,
    keeping each row's order and -1/inf padding."""
    ok = gids >= 0
    ok[ok] = manager.alive[gids[ok]]
    if ok.all():
        return gids, dists
    order = np.argsort(~ok, axis=1, kind="stable")
    gids = np.take_along_axis(np.where(ok, gids, -1), order, axis=1)
    dists = np.take_along_axis(np.where(ok, dists, np.inf), order, axis=1)
    return gids, dists


def _plan_pack(manager, pack, filt, rp, t_lo, t_hi, obs, registry,
               deadline=None):
    """Run the cost planner over one ``PackView`` dispatch.

    Returns ``(plan, graph_caps)`` where ``graph_caps`` is the set of bucket
    capacities routed to the stitched traversal this dispatch.  Also records
    the plan on ``manager.last_plan`` and bumps the
    ``planner_decision_total{mode=...}`` counters — one increment per bucket
    decision, labelled like the pack gauges in ``obs/metrics.py``.

    With a running ``deadline`` the remaining budget (converted to cost
    units via ``PlannerCosts.cost_per_ms``) gates the cold modes: the
    planner refuses ``host_scan``/``admit_cheaper`` decisions the budget
    can't cover (mode ``"skip"`` — the caller omits those buckets and
    marks the result degraded).
    """
    from ..kernels.ops import encode_filter
    from .planner import PlannerCosts, plan_read_paths
    costs = manager.cfg.planner_costs or PlannerCosts()
    snap = (obs.bucket_stats.snapshot()
            if obs is not None and obs.bucket_stats is not None else {})
    # a filter the kernels cannot encode falls back to the host scan path
    # everywhere; the traversal kernel shares the same φ encoding, so it
    # is equally unavailable — force scan across the whole pack
    graph_ok = encode_filter(filt, pack.m) is not None
    deadline_cost = (None if deadline is None else
                     max(deadline.remaining_ms(), 0.0) * costs.cost_per_ms)
    plan = plan_read_paths(pack, rp, snap, costs, t_lo, t_hi,
                           graph_allowed=graph_ok,
                           deadline_cost=deadline_cost)
    manager.last_plan = plan
    for dec in plan.values():
        registry.counter(
            f'planner_decision_total{{mode="{dec.mode}"}}').inc()
    graph_caps = frozenset(c for c, dec in plan.items()
                           if dec.mode == "graph")
    return plan, graph_caps


def _graph_search_blocks(manager, pack, buckets, queries, filt, k,
                         t_lo, t_hi, metric, trace, registry,
                         observe=None, on_cold=None, deadline=None,
                         degrade=None):
    """Stitched-traversal dispatch for the buckets the planner sent to
    ``graph`` mode.

    fp32 buckets yield exact ``(gid, dist)`` blocks; quantized buckets
    yield over-fetched candidate blocks that are reranked exactly at fp32
    (union across graph buckets — gids are disjoint) before joining the
    merge.  A bucket whose traversal is unavailable after all (filter not
    encodable, no live seeds — the planner should have gated these) falls
    back to the ordinary scan for that bucket alone; the fallback threads
    the same ``observe`` / ``on_cold`` hooks as the main scan path, so a
    fallback dispatch still feeds ``BucketStats`` (and therefore the
    planner) instead of silently starving it.  Returns
    ``(blocks_g, blocks_d)`` lists.

    With a running ``deadline``, the remaining budget is checked before
    each bucket's traversal; once spent, the remaining buckets are
    skipped and reported through ``degrade("deadline_graph", n)`` — the
    caller marks the result degraded.
    """
    import dataclasses as _dc

    from ..distributed.segment_shards import (bucket_graph_seeds,
                                              pack_search, pack_search_blocks)
    from ..kernels.graph_topk import bucket_graph_topk
    cfg = manager.cfg
    quantized = pack.quantize is not None
    kk = max(k, cfg.rerank_multiple * k if quantized else k)
    blocks_g: List[np.ndarray] = []
    blocks_d: List[np.ndarray] = []
    cand_g: List[np.ndarray] = []
    for i, bv in enumerate(buckets):
        if deadline is not None and deadline.expired():
            if degrade is not None:
                degrade("deadline_graph", len(buckets) - i)
            break
        seeds = bucket_graph_seeds(bv, t_lo, t_hi)
        with trace.span("bucket_graph", cap=bv.cap, seeds=int(len(seeds))):
            out = bucket_graph_topk(
                queries, bv, seeds, filt, kk, m=pack.m, metric=metric,
                ef=max(cfg.graph_ef, kk), width=cfg.graph_width,
                max_iters=cfg.graph_max_iters)
            if out is not None:
                block_ready(out[:2])
        if out is None:                       # planner gate raced/failed
            sub = _dc.replace(pack, buckets=(bv,))
            if quantized:
                gg, dd = pack_search(
                    sub, queries, filt, k, t_lo=t_lo, t_hi=t_hi,
                    metric=metric, lookup=manager.get_points,
                    rerank_multiple=cfg.rerank_multiple, trace=trace,
                    observe=observe, on_cold=on_cold, registry=registry)
                blocks_g.append(gg)
                blocks_d.append(dd)
            else:
                for gg, dd in pack_search_blocks(
                        sub, queries, filt, k, t_lo=t_lo, t_hi=t_hi,
                        metric=metric, trace=trace, observe=observe,
                        on_cold=on_cold):
                    blocks_g.append(gg)
                    blocks_d.append(dd)
            continue
        gg, dd, hops = out
        registry.histogram("graph_hops").observe(float(hops))
        if quantized:
            cand_g.append(np.asarray(gg))
        else:
            blocks_g.append(np.asarray(gg))
            blocks_d.append(np.asarray(dd))
    if cand_g:
        from ..quant.rerank import rerank_exact
        with trace.span("graph_rerank",
                        candidates=int(sum(g.shape[1] for g in cand_g))):
            gg, dd = rerank_exact(queries, np.concatenate(cand_g, axis=1),
                                  k, manager.get_points, metric=metric,
                                  trace=trace, registry=registry)
        blocks_g.append(gg)
        blocks_d.append(dd)
    return blocks_g, blocks_d


def query_segments(manager, queries: np.ndarray, filt: Optional[Filter],
                   k: int = 10, ef: int = 64, return_stats: bool = False,
                   use_shards: Optional[bool] = None, trace=None,
                   read_path: Optional[str] = None,
                   deadline_ms: Optional[float] = None,
                   **search_kw):
    """Fan out one query batch across all live segments and merge top-k.

    Runs against a snapshot — ``(epoch, segment list, frozen delta copy)``
    — taken under the manager lock at entry, so concurrent compaction
    publishes never tear the segment list mid-query and concurrent
    ingests/seals never mutate the delta rows being scanned.  Returns
    ``(gids [b, k], dists [b, k])`` — plus a list of per-segment
    ``SegmentQueryStats`` when ``return_stats`` is set (pruned segments
    appear with ``pruned=True`` and zero search time; under the sharded
    path every searched segment reports the shared dispatch time).

    ``use_shards`` overrides ``StreamConfig.n_shards`` per call (True
    forces the sharded kernel scan, False the per-segment graph search).
    ``read_path`` overrides ``StreamConfig.read_path`` per call
    (``"scan"`` | ``"graph"`` | ``"auto"``): anything but ``"scan"`` runs
    the cost planner over the sealed pack and routes each bucket to the
    fused scan or the stitched graph traversal; the chosen plan is left on
    ``manager.last_plan`` for inspection.

    All reported timings (``search_ms``, trace spans) stop their clocks
    only after ``jax.block_until_ready`` on the dispatch results, so they
    measure device work rather than JAX's async enqueue.  ``trace``
    (``repro.obs.trace.QueryTrace``, or None for the shared no-op) opens
    one span per phase — delta scan, per-bucket dispatch, rerank, merge —
    and the manager's :class:`~repro.obs.metrics.BucketStats` accumulator
    receives one per-bucket observation per sharded query.

    ``deadline_ms`` (default ``StreamConfig.query_deadline_ms``; None =
    unbounded) starts a :class:`~.resilience.Deadline` for this call.
    The remaining budget is checked *between* bucket dispatches — sealed
    scans (resident and cold host streams alike), graph traversals, and
    the per-segment fan-out — never mid-kernel; once spent, the
    remaining buckets are skipped and the merged partial result is
    returned as a :class:`~.resilience.QueryResult` with
    ``degraded=True`` and per-reason skip counts (also counted in
    ``query_degraded_total{reason=...}``).  The delta buffer is always
    scanned (freshest data, one cheap exact dispatch), and the planner
    refuses cold decisions the budget can't cover (see
    ``streaming/planner.py``).  Without a deadline the path is
    unchanged: results are exact and ``degraded`` is always False.
    """
    t_all = time.perf_counter()
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    b = queries.shape[0]
    trace = NULL_TRACE if trace is None else trace
    obs = getattr(manager, "obs", None)
    registry = obs.registry if obs is not None else NULL_REGISTRY
    if deadline_ms is None:
        deadline_ms = manager.cfg.query_deadline_ms
    deadline = Deadline.start(deadline_ms)
    reasons: dict = {}

    def _degrade(reason: str, n: int = 1) -> None:
        reasons[reason] = reasons.get(reason, 0) + int(n)
        registry.counter(
            f'query_degraded_total{{reason="{reason}"}}').inc(n)
    observe = (obs.bucket_stats.observe
               if obs is not None and obs.bucket_stats is not None else None)
    t_lo, t_hi = temporal_bounds(filt, manager.time_dim)
    metric = manager.cfg.index_cfg.metric
    # one lock hold captures the whole consistent view: the segment list
    # (epoch guard) AND a frozen copy of the delta's live rows, so a racing
    # ingest/seal can never resize or reset the buffer mid-scan
    with trace.span("snapshot"):
        epoch, segments, delta = manager.snapshot()

    blocks_g: List[np.ndarray] = []
    blocks_d: List[np.ndarray] = []
    stats: List[SegmentQueryStats] = []

    if delta.n_live > 0:
        st = delta.stats()
        if delta.t_max >= t_lo and delta.t_min <= t_hi:
            with trace.span("delta_scan", rows=delta.n_live):
                t0 = time.perf_counter()
                ids, dd = delta.query(queries, filt, k, metric=metric)
                block_ready((ids, dd))
                st.search_ms = (time.perf_counter() - t0) * 1e3
            blocks_g.append(ids)
            blocks_d.append(dd)
        else:
            st.pruned = True
        stats.append(st)

    sharded = (manager.cfg.n_shards >= 1 if use_shards is None
               else bool(use_shards))
    live_segs = [g for g in segments if g.n_live > 0]
    if sharded and live_segs:
        from ..distributed.segment_shards import (PackView, pack_search,
                                                  pack_search_blocks)
        # None when every snapshot segment lost its last live point to a
        # racing delete — nothing sealed to search, fall through.
        pack = manager.shard_pack(epoch, live_segs)
        dt_ms = 0.0
        tier = getattr(manager, "tier", None)
        on_cold = None
        if pack is not None:
            # cost-based routing: with read_path != "scan" the planner
            # splits the pack's buckets into a scan subset (dispatched
            # through the exact same calls below — byte-for-byte the
            # forced-scan answer) and a graph subset (stitched traversal)
            rp = (manager.cfg.read_path if read_path is None
                  else str(read_path))
            scan_pack = pack
            graph_bvs: tuple = ()
            if tier is not None and isinstance(pack, PackView):
                # feed the query window's drift to the prefetch predictor
                # and count cold (streamed) dispatches as tier misses
                tier.note_window(t_lo, t_hi)

                def on_cold(cap, stage_bytes, _reg=registry):
                    _reg.counter("tier_miss_total").inc()
            if isinstance(pack, PackView) and rp != "scan":
                import dataclasses as _dc
                plan, graph_caps = _plan_pack(manager, pack, filt, rp,
                                              t_lo, t_hi, obs, registry,
                                              deadline=deadline)
                # deadline-refused buckets (mode "skip"): the planner
                # priced every cold route above the remaining budget —
                # omit them and answer degraded instead of stalling
                skip_caps = frozenset(c for c, dec in plan.items()
                                      if dec.mode == "skip")
                if skip_caps:
                    _degrade("deadline_planner", len(skip_caps))
                if tier is not None:
                    # the planner priced re-admission below streaming for
                    # these cold buckets: admit them now and dispatch the
                    # resident block this very query.  tier_admit refuses
                    # (returns None — keep the exact cold view) when the
                    # block no longer fits or the pack has moved past this
                    # query's snapshot epoch.
                    admitted = {}
                    for cap, dec in plan.items():
                        if dec.reason == "admit_cheaper":
                            nbv = manager.tier_admit(cap,
                                                     expect_epoch=epoch)
                            if nbv is not None:
                                admitted[cap] = nbv
                    if admitted:
                        pack = _dc.replace(
                            pack, buckets=tuple(admitted.get(bv.cap, bv)
                                                for bv in pack.buckets))
                        scan_pack = pack
                drop = graph_caps | skip_caps
                if drop:
                    graph_bvs = tuple(bv for bv in pack.buckets
                                      if bv.cap in graph_caps)
                    scan_pack = _dc.replace(
                        pack, buckets=tuple(bv for bv in pack.buckets
                                            if bv.cap not in drop))
            with trace.span("sealed_scan",
                            quantized=getattr(pack, "quantize", None)
                            is not None):
                t0 = time.perf_counter()
                if isinstance(pack, PackView) and deadline is not None:
                    # deadline-aware dispatch: one sub-view per bucket so
                    # the remaining budget is re-checked between bucket
                    # dispatches.  Per-bucket rerank-to-k blocks merge to
                    # the same exact (dist, gid) answer as the bulk union
                    # rerank — top-k of a union equals the merge of exact
                    # per-part top-ks under the shared tiebreak — so a
                    # query that finishes in time is bit-for-bit the
                    # no-deadline answer.
                    import dataclasses as _dc
                    bvs = scan_pack.buckets
                    for i, bv in enumerate(bvs):
                        if deadline.expired():
                            _degrade("deadline_sealed_scan", len(bvs) - i)
                            break
                        manager._fault("query.bucket")
                        sub = _dc.replace(scan_pack, buckets=(bv,))
                        if scan_pack.quantize is not None:
                            gg, dd = pack_search(
                                sub, queries, filt, k, t_lo=t_lo,
                                t_hi=t_hi, metric=metric,
                                lookup=manager.get_points,
                                rerank_multiple=manager.cfg.rerank_multiple,
                                trace=trace, observe=observe,
                                on_cold=on_cold, registry=registry)
                            blocks_g.append(gg)
                            blocks_d.append(dd)
                        else:
                            for gg, dd in pack_search_blocks(
                                    sub, queries, filt, k, t_lo=t_lo,
                                    t_hi=t_hi, metric=metric, trace=trace,
                                    observe=observe, on_cold=on_cold):
                                blocks_g.append(gg)
                                blocks_d.append(dd)
                elif isinstance(pack, PackView) and pack.quantize is not None:
                    # two-stage quantized read path: pack_search
                    # over-fetches rerank_multiple * k candidates from
                    # each unpruned bucket's int8 asymmetric-distance
                    # dispatch and reranks the union exactly at fp32
                    # (original vectors from the point store) — one exact
                    # (gid, dist) block for the merge
                    if scan_pack.buckets:
                        gg, dd = pack_search(
                            scan_pack, queries, filt, k, t_lo=t_lo,
                            t_hi=t_hi, metric=metric,
                            lookup=manager.get_points,
                            rerank_multiple=manager.cfg.rerank_multiple,
                            trace=trace, observe=observe, on_cold=on_cold,
                            registry=registry)
                        blocks_g.append(gg)
                        blocks_d.append(dd)
                elif isinstance(pack, PackView):
                    # one fused dispatch per unpruned capacity bucket;
                    # every bucket block joins the same exact (gid, dist)
                    # merge as the delta block below
                    if scan_pack.buckets:
                        for gg, dd in pack_search_blocks(
                                scan_pack, queries, filt, k, t_lo=t_lo,
                                t_hi=t_hi, metric=metric, trace=trace,
                                observe=observe, on_cold=on_cold):
                            blocks_g.append(gg)
                            blocks_d.append(dd)
                else:                     # legacy monolithic pack
                    gg, dd = pack_search(pack, queries, filt, k, t_lo=t_lo,
                                         t_hi=t_hi, metric=metric,
                                         trace=trace)
                    blocks_g.append(gg)
                    blocks_d.append(dd)
                if graph_bvs:
                    gb_g, gb_d = _graph_search_blocks(
                        manager, pack, graph_bvs, queries, filt, k,
                        t_lo, t_hi, metric, trace, registry,
                        observe=observe, on_cold=on_cold,
                        deadline=deadline, degrade=_degrade)
                    blocks_g.extend(gb_g)
                    blocks_d.extend(gb_d)
                # the per-bucket spans above already blocked on their own
                # results; this keeps the shared dispatch time honest even
                # if a future path returns device arrays here
                block_ready((blocks_g[-1] if blocks_g else None,
                             blocks_d[-1] if blocks_d else None))
                dt_ms = (time.perf_counter() - t0) * 1e3
            if tier is not None:
                # stage buckets the workload's window drift is about to
                # touch, off the query path (daemon thread, at most one)
                manager.maybe_prefetch()
        for seg in segments:
            st = seg.stats()
            if pack is None or seg.n_live == 0 \
                    or not seg.overlaps(t_lo, t_hi):
                st.pruned = True
            else:
                st.search_ms = dt_ms
            stats.append(st)
    else:
        for seg in segments:
            st = seg.stats()
            if seg.n_live == 0 or not seg.overlaps(t_lo, t_hi):
                st.pruned = True
                stats.append(st)
                continue
            if deadline is not None and deadline.expired():
                # budget spent: report the segment unsearched (pruned
                # with zero search time) and mark the answer degraded
                _degrade("deadline_segment")
                st.pruned = True
                stats.append(st)
                continue
            with trace.span("segment_scan", seg_id=seg.seg_id,
                            rows=seg.n_live):
                t0 = time.perf_counter()
                ids, dd = seg.query(queries, filt, k=k, ef=ef, **search_kw)
                block_ready((ids, dd))
                st.search_ms = (time.perf_counter() - t0) * 1e3
            blocks_g.append(ids)
            blocks_d.append(np.asarray(dd))
            stats.append(st)

    registry.counter("query_batches_total").inc()
    registry.counter("query_rows_total").inc(b)
    if reasons:
        registry.counter("query_degraded_queries_total").inc()
    if not blocks_g:
        out_g = np.full((b, k), -1, np.int64)
        out_d = np.full((b, k), np.inf, np.float32)
        registry.histogram("query_ms").observe(
            (time.perf_counter() - t_all) * 1e3)
        out = (out_g, out_d, stats) if return_stats else (out_g, out_d)
        return QueryResult(out, degraded=bool(reasons), reasons=reasons)

    with trace.span("merge", blocks=len(blocks_g)):
        out_g, out_d = merge_topk(blocks_g, blocks_d, k)
        out_g, out_d = _alive_filter(manager, out_g, out_d)
    registry.histogram("query_ms").observe(
        (time.perf_counter() - t_all) * 1e3)
    out = (out_g, out_d, stats) if return_stats else (out_g, out_d)
    return QueryResult(out, degraded=bool(reasons), reasons=reasons)


@dataclasses.dataclass
class GroupQuery:
    """One request group of a heterogeneous batched query: its own query
    rows, filter, ``k``, and per-call overrides (deadline, read path) —
    the unit :func:`query_segments_grouped` batches into shared per-bucket
    dispatches."""

    queries: np.ndarray
    filt: Optional[Filter] = None
    k: int = 10
    ef: int = 64
    deadline_ms: Optional[float] = None
    read_path: Optional[str] = None


def query_segments_grouped(manager, groups, trace=None, observe_group=None):
    """Continuous filtered batching: answer several heterogeneous
    :class:`GroupQuery` request groups in ONE pass over the manager's
    state — one snapshot, one delta scan per group, and one shared
    per-bucket sealed-pack dispatch where every group active in a bucket
    rides the same device-block read
    (:func:`repro.distributed.segment_shards.pack_search_blocks_grouped`).

    Answers are **bit-for-bit** what per-group :func:`query_segments`
    calls would return: the grouped kernel dispatch stacks a filter
    class's groups in one query axis and tests each row against its own
    group's filter with the solo predicate's float32 operations, the
    bucket skip set per group matches its solo temporal pruning, and each
    group merges with its own ``k`` and temporal mask through the same
    exact ``(dist, gid)`` merge.

    The shared fast path requires a batchable configuration — bucketed
    sealed pack (``n_shards >= 1``, ``incremental_pack``), fp32 blocks
    (``quantize=None``), and every group on the ``"scan"`` read path;
    anything else (quantized packs, planner/graph routing, legacy
    monolithic packs, unsharded managers) falls back to per-group
    :func:`query_segments` calls — same answers, no block sharing.

    Per-group deadlines (``GroupQuery.deadline_ms``, defaulting to
    ``StreamConfig.query_deadline_ms``) drop only the *lagging group*
    from remaining buckets — other groups keep scanning — and mark that
    group's :class:`~.resilience.QueryResult` degraded with
    ``deadline_sealed_scan`` skip counts, exactly like the solo path.

    ``observe_group(group_idx, cap, rows=, active_rows=, candidates=,
    candidate_slots=, cache_hit=)`` attributes each shared bucket
    dispatch back to the groups that rode it — the hook the serving tier
    uses for per-tenant ``BucketStats``.  ``trace`` records
    ``snapshot``, ``delta_scan``, ``sealed_scan_grouped`` (holding the
    bucket steps of ``pack_search_blocks_grouped``) and, per group,
    ``host_topk`` (the exact merge) and ``alive_filter``.  The counter
    ``grouped_device_merge_groups_total`` counts the groups the shared
    path finished on the device (its ratio to ``query_batches_total``
    says how often that path engages); ``grouped_kernel_rows_total`` and
    ``grouped_kernel_row_slots_total`` count the real query rows its
    class programs scanned and the padded rows their query tiles computed
    (their ratio is the share of the query tile doing real work).
    Returns one ``QueryResult((gids [b_i, k_i], dists [b_i, k_i]))`` per
    group, in input order.
    """
    trace = NULL_TRACE if trace is None else trace
    obs = getattr(manager, "obs", None)
    registry = obs.registry if obs is not None else NULL_REGISTRY
    cfg = manager.cfg
    groups = list(groups)
    if not groups:
        return []
    rps = [g.read_path if g.read_path is not None else cfg.read_path
           for g in groups]
    shared_ok = (cfg.n_shards >= 1 and cfg.incremental_pack
                 and cfg.quantize is None
                 and all(rp == "scan" for rp in rps))
    if not shared_ok:
        return [query_segments(manager, g.queries, g.filt, k=g.k, ef=g.ef,
                               trace=trace, read_path=g.read_path,
                               deadline_ms=g.deadline_ms)
                for g in groups]

    t_all = time.perf_counter()
    qs = [np.atleast_2d(np.asarray(g.queries, np.float32)) for g in groups]
    bounds = [temporal_bounds(g.filt, manager.time_dim) for g in groups]
    deadlines = [Deadline.start(g.deadline_ms if g.deadline_ms is not None
                                else cfg.query_deadline_ms) for g in groups]
    reasons: List[dict] = [{} for _ in groups]

    def _degrade(gi: int, reason: str, n: int = 1) -> None:
        reasons[gi][reason] = reasons[gi].get(reason, 0) + int(n)
        registry.counter(
            f'query_degraded_total{{reason="{reason}"}}').inc(n)

    observe = (obs.bucket_stats.observe
               if obs is not None and obs.bucket_stats is not None else None)
    metric = cfg.index_cfg.metric
    with trace.span("snapshot"):
        epoch, segments, delta = manager.snapshot()

    blocks_g: List[List[np.ndarray]] = [[] for _ in groups]
    blocks_d: List[List[np.ndarray]] = [[] for _ in groups]

    if delta.n_live > 0:
        for gi, (q, (t_lo, t_hi)) in enumerate(zip(qs, bounds)):
            if delta.t_max >= t_lo and delta.t_min <= t_hi:
                with trace.span("delta_scan", rows=delta.n_live,
                                group=gi):
                    ids, dd = delta.query(q, groups[gi].filt,
                                          groups[gi].k, metric=metric)
                    block_ready((ids, dd))
                blocks_g[gi].append(ids)
                blocks_d[gi].append(dd)

    live_segs = [s for s in segments if s.n_live > 0]
    if live_segs:
        from ..distributed.segment_shards import (
            PackView, pack_search_blocks_grouped)
        # None when every snapshot segment lost its last live point to a
        # racing delete — nothing sealed to search, fall through.
        pack = manager.shard_pack(epoch, live_segs)
        if isinstance(pack, PackView):
            tier = getattr(manager, "tier", None)
            on_cold = None
            if tier is not None:
                for t_lo, t_hi in bounds:
                    tier.note_window(t_lo, t_hi)

                def on_cold(cap, stage_bytes, _reg=registry):
                    _reg.counter("tier_miss_total").inc()
            def _kernel_rows(rows, slots, _reg=registry):
                _reg.counter("grouped_kernel_rows_total").inc(rows)
                _reg.counter("grouped_kernel_row_slots_total").inc(slots)

            pk_groups = [(qs[gi], groups[gi].filt, groups[gi].k,
                          bounds[gi][0], bounds[gi][1])
                         for gi in range(len(groups))]
            with trace.span("sealed_scan_grouped", groups=len(groups)):
                per = pack_search_blocks_grouped(
                    pack, pk_groups, metric=metric, trace=trace,
                    observe=observe, on_cold=on_cold,
                    deadlines=deadlines,
                    on_expired=lambda gi, n:
                        _degrade(gi, "deadline_sealed_scan", n),
                    fault=lambda: manager._fault("query.bucket"),
                    observe_group=observe_group,
                    on_device_merge=registry.counter(
                        "grouped_device_merge_groups_total").inc,
                    on_kernel_rows=_kernel_rows)
            for gi, bl in enumerate(per):
                for gg, dd in bl:
                    blocks_g[gi].append(gg)
                    blocks_d[gi].append(dd)
            if tier is not None:
                manager.maybe_prefetch()

    out: List[QueryResult] = []
    for gi, g in enumerate(groups):
        b = qs[gi].shape[0]
        registry.counter("query_batches_total").inc()
        registry.counter("query_rows_total").inc(b)
        if reasons[gi]:
            registry.counter("query_degraded_queries_total").inc()
        if not blocks_g[gi]:
            og = np.full((b, g.k), -1, np.int64)
            od = np.full((b, g.k), np.inf, np.float32)
        else:
            with trace.span("host_topk", blocks=len(blocks_g[gi]),
                            group=gi):
                og, od = merge_topk(blocks_g[gi], blocks_d[gi], g.k)
            with trace.span("alive_filter", group=gi):
                og, od = _alive_filter(manager, og, od)
        out.append(QueryResult((og, od), degraded=bool(reasons[gi]),
                               reasons=reasons[gi]))
    registry.histogram("query_ms").observe(
        (time.perf_counter() - t_all) * 1e3)
    return out
