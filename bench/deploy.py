"""A configuration's deployment through the program's public path: a
``MultiTenantStore`` loaded with ``create_collection``, ``insert`` and
``maintenance``, served by a ``CubeGraphService``, and warmed up on the
shapes the cell's traffic can reach."""
from __future__ import annotations

import time

import numpy as np

from .data import Corpus


def tenant_name(t: int) -> str:
    return f"t{t}"


def stream_config(cfg: dict):
    """The configuration's ``StreamConfig``: time-ordered segments of
    ``seal_max_points``, one shard stack, the stated read path and codes,
    CubeGraph build at the library's defaults."""
    from repro.core import CubeGraphConfig
    from repro.streaming import StreamConfig
    return StreamConfig(time_dim=2,
                        seal_max_points=int(cfg["seal_max_points"]),
                        compact_max_segments=int(cfg["compact_max_segments"]),
                        n_shards=1, read_path=cfg["read_path"],
                        quantize=cfg["quantize"],
                        rerank_multiple=int(cfg["rerank_multiple"]),
                        index_cfg=CubeGraphConfig())


def load(cfg: dict, corpus: Corpus, log=print):
    """Insert the corpus tenant by tenant in time-ordered batches of one
    segment, then run lifecycle ticks until the delta buffer is sealed."""
    from repro.serving.rag import Document
    from repro.serving.tenancy import MultiTenantStore
    store = MultiTenantStore(int(cfg["dim"]), 3, stream_cfg=stream_config(cfg))
    n_tenants = int(cfg["tenants"])
    for t in range(n_tenants):
        store.create_collection(tenant_name(t))
    tokens = np.zeros(1, np.int32)
    n = len(corpus.x)
    batch = int(cfg["seal_max_points"])
    t0 = time.perf_counter()
    for lo in range(0, n, batch):
        rows = np.arange(lo, min(lo + batch, n))
        for t in range(n_tenants):
            mine = rows[corpus.owner[rows] == t]
            if len(mine):
                store.insert(tenant_name(t), [
                    Document(doc_id=int(r), tokens=tokens,
                             embedding=corpus.x[r], metadata=corpus.s[r])
                    for r in mine])
    for _ in range(4):
        if store.manager.delta.n_live == 0:
            break
        store.maintenance()
    if store.manager.delta.n_live:
        raise RuntimeError("delta buffer not sealed after the load")
    log(f"load: {n} points in {len(store.manager.segments)} segments, "
        f"{time.perf_counter() - t0:.3f}s")
    return store


def make_service(cfg: dict, store):
    from repro.serving.service import AdmissionController, CubeGraphService
    return CubeGraphService(
        store, admission=AdmissionController(
            max_queue_per_tenant=int(cfg["max_queue_per_tenant"])),
        max_batch=int(cfg["max_batch"]))


def warm_shapes(cfg: dict, store, svc, reqs, to_filter, corpus: Corpus,
                groups, max_b: int, log=print, counter=None) -> int:
    """Drive ``manager.query_grouped`` -- the call ``CubeGraphService.flush``
    makes -- over the batch shapes the traffic's flushes hand it: G groups
    per filter kind for G in ``groups`` (a ``[lo, hi]`` range), each of
    1 to ``max_b`` requests, with the filters of the request stream
    ``reqs`` (``to_filter`` turns one into the program's).

    On the shared fp32 path the programs compiled follow (kind, G), b and
    (G, b); on a quantized pack every group goes its own way and they
    follow b (and the rerank's candidate count).  Shapes outside the range
    are left to the traffic warm-up that follows.  Returns the number of
    calls made."""
    from repro.streaming import GroupQuery
    by_kind = {}
    for t, spec in zip(reqs.tenant, reqs.specs):
        by_kind.setdefault(spec["kind"], []).append((int(t), spec))
    gen = np.random.default_rng(0)
    k = reqs.k

    def group(t, spec, b):
        rows = gen.choice(corpus.rows_of(t), size=b)
        return GroupQuery(corpus.x[rows].astype(np.float32),
                          store.scoped_filter(tenant_name(t),
                                              to_filter(spec)),
                          k=k, ef=svc.ef)

    calls = []
    b_all = list(range(1, max_b + 1))
    if cfg["quantize"] is None:
        g_max = min(len(v) for v in by_kind.values())
        for g in range(int(groups[0]), min(int(groups[1]), g_max) + 1):
            per_call = g * len(by_kind)
            for lo in range(0, len(b_all), per_call):
                sizes = b_all[lo:lo + per_call]
                sizes += [1] * (per_call - len(sizes))
                gqs, j = [], 0
                for members in by_kind.values():
                    for t, spec in members[:g]:
                        gqs.append(group(t, spec, sizes[j]))
                        j += 1
                calls.append(gqs)
    else:
        members = [m for v in by_kind.values() for m in v]
        for b in b_all:
            t, spec = members[b % len(members)]
            calls.append([group(t, spec, b)])
    t0 = time.perf_counter()
    for i, gqs in enumerate(calls):
        store.manager.query_grouped(gqs)
        if counter is not None and (i % 8 == 7 or i == len(calls) - 1):
            log(f"warm-up: {i + 1}/{len(calls)} calls, "
                f"{time.perf_counter() - t0:.3f}s, {counter.compiles} "
                f"compiles, {counter.cache_hits} cache loads so far")
    return len(calls)


def bucket_layout(store):
    """Per sealed bucket: its live points and its segments' time spans
    (the input of ``kernel_costs.dispatches``)."""
    mgr = store.manager
    epoch, segs, _ = mgr.snapshot()
    view = mgr.shard_pack(epoch, [g for g in segs if g.n_live > 0])
    out = []
    for bv in view.buckets:
        rows = np.asarray(bv.seg_ids) >= 0
        out.append({"points": int(np.sum(np.asarray(bv.fill)[rows])),
                    "spans": [(float(a), float(b)) for a, b in
                              zip(np.asarray(bv.t_min)[rows],
                                  np.asarray(bv.t_max)[rows])]})
    return out, view


def block_bytes(view) -> int:
    """Device bytes of the pack's blocks, walked as ``chip_smoke.py``'s
    ``block_arrays`` does."""
    total = 0
    for bv in view.buckets:
        for name in ("x", "s", "gids", "codes", "st", "scales", "nbrs"):
            arr = getattr(bv, name, None)
            if arr is not None:
                total += int(arr.size) * arr.dtype.itemsize
    return total
