#!/usr/bin/env python3
"""On-chip smoke run of the retrieval engine's main path.

Drives ingest -> seal into CubeGraph segments -> bucketed device pack ->
fused Pallas kernels -> ``CubeGraphService``, through the public entry
points, at SIFT1M's shape (ANN-benchmarks ``sift-128-euclidean``: 128-d
vectors) with seeded (lon, lat, t) metadata split over four tenants.  The
corpus is 2^18 points by default, cut from SIFT1M's 10^6: a cold load
(the seal's CubeGraph builds, mostly their XLA compiles) took 327.7 s at
2^19 on one v5e, and the graph leg's traversal budget below was sized
and checked at 2^18.  Every phase checks its answers against a float64 numpy
brute force and fails the run on any miss:

* fp32 scan through the service (grouped dispatch): recall@10 >= 0.99,
  no failures, no degraded answers, no grouped->solo fallback;
* graph traversal (beam-step kernel) on a replica restored from a
  snapshot of the same store: recall@10 >= 0.95, every bucket traversed,
  and the compiled beam step equal to its jnp twin.  Under the smoke's
  filters (about 1.5% of the bucket passes) the library's default
  traversal budget falls far short (recall 0.32 at 2^19), so the replica
  runs with ``GRAPH_BUDGET``;
* int8 codes with fp32 rerank on another restored replica: recall@10
  >= 0.95;
* every pack block lives on the TPU and no worker recorded an error.

Usage::

    python chip_smoke.py                 # one chip, the run above
    python chip_smoke.py --four-chips    # only the mesh-sharded pack on a
                                         # 4-device mesh vs one device:
                                         # bit-for-bit answers, blocks on
                                         # all 4 devices
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal --n 16384

Without a TPU it exits non-zero before any work; ``--cpu-rehearsal`` runs
the same checks at a small size on the CPU (kernels interpreted) and never
prints the ok line.  The last line of a passing chip run is
``{"ok": true, "device": {...}}``.  Timings printed along the way are
smoke timings of this run, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_TENANTS = 4
K = 10
QUERIES_PER_GROUP = 6
# traversal budget of the graph replica (StreamConfig graph_ef,
# graph_width, graph_max_iters): at 2^18 points and the smoke's filters
# it reaches recall@10 0.975 on the CPU, where the defaults (128, 8, 256)
# reach 0.556
GRAPH_BUDGET = dict(graph_ef=2048, graph_width=64, graph_max_iters=4096)


class SmokeFailure(RuntimeError):
    """A phase's answer check failed."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# corpus and requests
# ---------------------------------------------------------------------------
def make_corpus(n, seed):
    """SIFT1M-shaped vectors + (lon, lat, t) metadata from ``--seed``,
    in time order, each point owned by one of ``N_TENANTS`` tenants."""
    import numpy as np

    from repro.core.workloads import make_dataset
    x, s = make_dataset(n, 128, 3, seed=seed)
    order = np.argsort(s[:, 2], kind="stable")
    x, s = x[order], s[order]
    owner = np.random.default_rng(seed + 1).integers(0, N_TENANTS, n)
    return x, s, owner


def make_filters(seed):
    """Per tenant: one windowed box and one box+ball (ball over lon/lat,
    interval over t) — the two kernel filter kinds of the main path."""
    import numpy as np

    from repro.core import BallFilter, BoxFilter, ComposeFilter, \
        IntervalFilter
    rng = np.random.default_rng(seed + 2)
    out = []
    for _ in range(N_TENANTS):
        x0, y0 = rng.uniform(0.0, 0.5, 2)
        t0 = rng.uniform(0.0, 0.75)
        box = BoxFilter(lo=np.float32([x0, y0, t0]),
                        hi=np.float32([x0 + 0.5, y0 + 0.5, t0 + 0.25]))
        cx, cy = rng.uniform(0.3, 0.7, 2)
        t1 = rng.uniform(0.0, 0.75)
        ball = ComposeFilter(
            BallFilter(center=np.float32([cx, cy]), radius=np.float32(0.3)),
            IntervalFilter(dim=2, lo=np.float32(t1),
                           hi=np.float32(t1 + 0.25)), "and")
        out.append((box, ball))
    return out


def filter_mask(filt, s32):
    """numpy twin of the kernel predicate over float32 metadata."""
    import numpy as np

    from repro.core import BallFilter, BoxFilter, ComposeFilter
    if isinstance(filt, BoxFilter):
        return np.all((s32 >= filt.lo) & (s32 <= filt.hi), axis=1)
    if isinstance(filt, ComposeFilter):
        ball, iv = filt.a, filt.b
        assert isinstance(ball, BallFilter) and filt.op == "and"
        c = np.asarray(ball.center, np.float32)
        d2 = np.sum((s32[:, :len(c)] - c) ** 2, axis=1, dtype=np.float32)
        r = np.float32(ball.radius)
        v = s32[:, iv.dim]
        return (d2 <= r * r) & (v >= iv.lo) & (v <= iv.hi)
    raise TypeError(filt)


def make_requests(x, owner, filters, seed, start_id):
    """``QUERIES_PER_GROUP`` queries per (tenant, filter): a perturbed
    point of the tenant's own corpus, so every filter window has near
    neighbours."""
    import numpy as np
    rng = np.random.default_rng(seed)
    reqs = []
    rid = start_id
    for t in range(N_TENANTS):
        mine = np.flatnonzero(owner == t)
        for filt in filters[t]:
            for _ in range(QUERIES_PER_GROUP):
                q = x[rng.choice(mine)] + 0.1 * rng.standard_normal(
                    x.shape[1]).astype(np.float32)
                reqs.append((rid, f"t{t}", q.astype(np.float32), filt))
                rid += 1
    return reqs


class Oracle:
    """float64 brute force over one tenant's points under one filter."""

    def __init__(self, x, s, owner):
        import numpy as np
        self.x, self.s32, self.owner = x, s.astype(np.float32), owner

    def topk(self, tenant, q, filt, k):
        import numpy as np
        t = int(tenant[1:])
        idx = np.flatnonzero((self.owner == t)
                             & filter_mask(filt, self.s32))
        xv = self.x[idx].astype(np.float64)
        d = np.sum((xv - q.astype(np.float64)) ** 2, axis=1)
        top = np.argsort(d, kind="stable")[:k]
        return idx[top], d[top]


def recall_and_err(results, reqs, oracle, gid_to_row, k=K):
    """Mean recall@k against the oracle and the largest absolute error of
    the returned distances against float64 (over returned rows)."""
    import numpy as np
    recs, err = [], 0.0
    for rid, tenant, q, filt in reqs:
        gids, dists = results[rid]
        want, _ = oracle.topk(tenant, q, filt, k)
        got_rows = gid_to_row(np.asarray(gids)[np.asarray(gids) >= 0])
        recs.append(len(set(got_rows.tolist()) & set(want.tolist()))
                    / max(len(want), 1))
        if len(got_rows):
            xv = oracle.x[got_rows].astype(np.float64)
            d64 = np.sum((xv - q.astype(np.float64)) ** 2, axis=1)
            got_d = np.asarray(dists, np.float64)[:len(got_rows)]
            err = max(err, float(np.max(np.abs(got_d - d64))))
    return float(np.mean(recs)), err


# ---------------------------------------------------------------------------
# store, service and checks
# ---------------------------------------------------------------------------
def stream_cfg(n, n_shards=1, **kw):
    """The deployment: time-ordered segments of 16384 points (16 at 2^18),
    no compaction merges during the load, default CubeGraph build
    parameters."""
    from repro.core import CubeGraphConfig
    from repro.streaming import StreamConfig
    return StreamConfig(time_dim=2, seal_max_points=min(16384, n // 16),
                        compact_max_segments=64, n_shards=n_shards,
                        index_cfg=CubeGraphConfig(), **kw)


def load_store(x, s, owner, cfg, mesh=None):
    """Insert the corpus tenant by tenant in time-ordered batches of one
    segment each, then run the lifecycle tick until the delta is sealed.
    Returns ``(store, gid_to_row)``."""
    import numpy as np

    from repro.serving.rag import Document
    from repro.serving.tenancy import MultiTenantStore
    store = MultiTenantStore(128, 3, stream_cfg=cfg, shard_mesh=mesh)
    for t in range(N_TENANTS):
        store.create_collection(f"t{t}")
    tokens = np.zeros(1, np.int32)
    n = len(x)
    batch = cfg.seal_max_points
    row_of_gid = np.full(n, -1, np.int64)
    t0 = time.perf_counter()
    for lo in range(0, n, batch):
        rows = np.arange(lo, min(lo + batch, n))
        for t in range(N_TENANTS):
            mine = rows[owner[rows] == t]
            if not len(mine):
                continue
            docs = [Document(doc_id=int(r), tokens=tokens, embedding=x[r],
                             metadata=s[r]) for r in mine]
            gids = store.insert(f"t{t}", docs)
            row_of_gid[gids] = mine
        log(f"  loaded {lo + len(rows)}/{n} points, "
            f"{len(store.manager.segments)} segments, "
            f"{time.perf_counter() - t0:.1f}s")
    for _ in range(4):
        if store.manager.delta.n_live == 0:
            break
        store.maintenance()
    check(store.manager.delta.n_live == 0, "delta buffer not sealed")
    return store, (lambda g: row_of_gid[np.asarray(g, np.int64)])


def pack_view(store):
    mgr = store.manager
    epoch, segs, _ = mgr.snapshot()
    return mgr.shard_pack(epoch, [g for g in segs if g.n_live > 0])


def block_arrays(view):
    for bv in view.buckets:
        for name in ("x", "s", "gids", "codes", "st", "scales", "nbrs"):
            arr = getattr(bv, name, None)
            if arr is not None:
                yield bv.cap, name, arr


def check_blocks_on(view, platform, n_devices):
    """Every resident pack block lives on ``platform``; over a mesh its
    shards cover ``n_devices`` distinct devices."""
    n = 0
    for cap, name, arr in block_arrays(view):
        devs = arr.sharding.device_set
        check(all(d.platform == platform for d in devs),
              f"bucket {cap} {name} on {devs}, not {platform}")
        check(len(devs) == n_devices,
              f"bucket {cap} {name} spans {len(devs)} devices, "
              f"not {n_devices}")
        if n_devices > 1:
            shard_devs = {sh.device for sh in arr.addressable_shards}
            check(len(shard_devs) == n_devices,
                  f"bucket {cap} {name} shards on {len(shard_devs)} devices")
        n += 1
    check(n > 0, "pack holds no device blocks")
    return n


def check_health(store, label):
    health = store.manager.supervisor.health()
    bad = {k: v["errors"] for k, v in health.items() if v["errors"]}
    check(not bad, f"{label}: workers recorded errors {bad}")


def serve(store, reqs):
    """Submit every request to a fresh service, flush once; returns
    ``({req_id: (gids, dists)}, seconds)`` after checking each answer is
    a non-degraded ``ServeResult``."""
    from repro.serving.service import CubeGraphService, ServeRequest, \
        ServeResult
    svc = CubeGraphService(store, max_batch=64)
    for rid, tenant, q, filt in reqs:
        rej = svc.submit(ServeRequest(req_id=rid, tenant=tenant,
                                      query_emb=q, filt=filt, k=K))
        check(rej is None, f"request {rid} rejected: {rej}")
    t0 = time.perf_counter()
    out = svc.flush()
    dt = time.perf_counter() - t0
    check(len(out) == len(reqs), f"{len(out)} answers for {len(reqs)}")
    res = {}
    for rid, r in out.items():
        check(isinstance(r, ServeResult), f"request {rid} failed: {r}")
        check(not r.degraded, f"request {rid} degraded: {r.reasons}")
        res[rid] = (r.gids, r.dists)
    fallback = store.metrics.counter("retrieval_group_fallback_total").value
    check(fallback == 0, f"{fallback} grouped->solo fallbacks")
    return res, dt


def check_beam_step(store, view, reqs):
    """The compiled beam-step kernel against its jnp twin on 8 queries x
    4096 real bucket rows, for a box and a box+ball request."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.graph_topk import (_score_candidates_jnp,
                                          beam_step_scores)
    from repro.kernels.ops import encode_filter
    bv = view.buckets[0]
    c = min(4096, bv.cap)
    qs = jnp.asarray(np.stack([q for _, _, q, _ in reqs[:8]]))
    cx = jnp.broadcast_to(bv.x[0, :c][None], (8, c, bv.x.shape[2]))
    cm = jnp.broadcast_to(bv.s[0, :c][None], (8, c, bv.s.shape[2]))
    mismatch, d_err = 0, 0.0
    for _, tenant, _, filt in (reqs[0], reqs[QUERIES_PER_GROUP]):
        kind, params = encode_filter(store.scoped_filter(tenant, filt),
                                     store.manager.m)
        pj = jnp.asarray(params)
        dk, okk = beam_step_scores(qs, cx, cm, pj, kind=kind)
        dj, okj = _score_candidates_jnp(qs, cx, cm, pj, kind=kind,
                                        metric="l2")
        mismatch += int(np.sum(np.asarray(okk) != np.asarray(okj)))
        d_err = max(d_err, float(np.max(np.abs(np.asarray(dk)
                                               - np.asarray(dj)))))
    log(f"beam-step kernel vs jnp twin: {mismatch} predicate mismatches, "
        f"max |dist diff| {d_err:.3e} over 2x8x{c} candidates")
    check(mismatch == 0 and d_err <= 1e-2,
          "beam-step kernel disagrees with its jnp twin")


def restore_replica(root, cfg, mesh=None):
    """A read-only replica warm-started from a store snapshot, under its
    own configuration (read path, codes, mesh)."""
    from repro.serving.tenancy import MultiTenantStore
    return MultiTenantStore.restore(root, 128, 3, stream_cfg=cfg,
                                    shard_mesh=mesh, resume=False)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def one_chip(args, platform):
    import jax.numpy as jnp
    import numpy as np

    n = args.n
    t0 = time.perf_counter()
    x, s, owner = make_corpus(n, args.seed)
    filters = make_filters(args.seed)
    oracle = Oracle(x, s, owner)
    cfg = stream_cfg(n)
    log(f"corpus: N={n} d=128 m=3 tenants={N_TENANTS} "
        f"seal_max_points={cfg.seal_max_points} "
        f"(generated in {time.perf_counter() - t0:.1f}s)")

    t0 = time.perf_counter()
    store, gid_to_row = load_store(x, s, owner, cfg)
    load_s = time.perf_counter() - t0
    view = pack_view(store)
    n_blocks = check_blocks_on(view, platform, 1)
    log(f"load: {load_s:.1f}s for N={n}; segments={len(store.manager.segments)}"
        f" buckets={len(view.buckets)} pack_device_bytes={view.nbytes} "
        f"blocks_on_{platform}={n_blocks}")

    # -- fp32 scan through the service's grouped dispatch -----------------
    reqs = make_requests(x, owner, filters, args.seed + 10, 0)
    res, first_s = serve(store, reqs)
    for t in range(N_TENANTS):
        snap = store.collections[f"t{t}"].bucket_stats.snapshot()
        check(snap, f"tenant t{t}: no grouped bucket observations "
                    "(grouped dispatch not used)")
    rec, err = recall_and_err(res, reqs, oracle, gid_to_row)
    warm = make_requests(x, owner, filters, args.seed + 11, len(reqs))
    res_w, warm_s = serve(store, warm)
    rec_w, err_w = recall_and_err(res_w, warm, oracle, gid_to_row)
    log(f"fp32 scan: requests={len(reqs) + len(warm)} failures=0 degraded=0 "
        f"grouped_fallbacks=0 recall@10={min(rec, rec_w):.4f} "
        f"max_abs_dist_err_vs_f64={max(err, err_w):.3e}")
    log(f"smoke timing (not a metric): first flush {first_s:.2f}s incl. "
        f"compiles, warm flush {warm_s:.3f}s for {len(warm)} requests")
    check(min(rec, rec_w) >= 0.99, f"fp32 recall@10 {min(rec, rec_w)}")

    # -- precision of the exact jnp paths against the kernel ---------------
    # every query against an 8192-row slab, MXU-sized: the jnp reference at
    # HIGHEST (what the exact jnp paths use) and at default precision
    from repro.kernels import ref
    qs = np.stack([q for _, _, q, _ in reqs])
    slab = x[:8192]
    d64 = (np.sum(qs.astype(np.float64) ** 2, axis=1)[:, None]
           - 2.0 * qs.astype(np.float64) @ slab.astype(np.float64).T
           + np.sum(slab.astype(np.float64) ** 2, axis=1)[None, :])
    qj, xj = jnp.asarray(qs), jnp.asarray(slab)
    d_hi = np.asarray(ref.pairwise_sq_l2(qj, xj), np.float64)
    d_def = np.asarray(jnp.sum(qj * qj, axis=1)[:, None]
                       - 2.0 * jnp.matmul(qj, xj.T)
                       + jnp.sum(xj * xj, axis=1)[None, :], np.float64)
    err_hi = float(np.max(np.abs(d_hi - d64)))
    err_def = float(np.max(np.abs(d_def - d64)))
    log(f"precision vs f64, {qs.shape[0]}x{len(slab)} distances: "
        f"jnp_highest={err_hi:.3e} jnp_default={err_def:.3e} "
        f"(kernel answers above: {max(err, err_w):.3e})")
    scale = float(np.max(np.abs(d64)))
    check(err_hi <= 1e-5 * scale,
          f"jnp reference at HIGHEST is off by {err_hi} (scale {scale})")
    check_health(store, "fp32 store")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        t0 = time.perf_counter()
        store.snapshot_to(root)
        snap_s = time.perf_counter() - t0
        del store, view
        # -- graph traversal (beam-step kernel) on a restored replica -----
        t0 = time.perf_counter()
        g_store = restore_replica(
            root, dataclasses.replace(cfg, read_path="graph",
                                      **GRAPH_BUDGET))
        g_view = pack_view(g_store)
        check_blocks_on(g_view, platform, 1)
        check(all(bv.nbrs is not None for bv in g_view.buckets),
              "graph replica pack carries no adjacency")
        restore_s = time.perf_counter() - t0
        check_beam_step(g_store, g_view, reqs)
        gres = {}
        t0 = time.perf_counter()
        for rid, tenant, q, filt in reqs:
            ans = g_store.retrieve(tenant, q, filt, k=K, read_path="graph")
            check(not ans.degraded, f"graph request {rid} degraded")
            modes = {d.mode for d in g_store.manager.last_plan.values()}
            check(modes == {"graph"}, f"graph request {rid} planned {modes}")
            gres[rid] = (ans.gids[0], ans.dists[0])
        graph_s = time.perf_counter() - t0
        grec, gerr = recall_and_err(gres, reqs, oracle, gid_to_row)
        log(f"graph: requests={len(reqs)} recall@10={grec:.4f} "
            f"max_abs_dist_err_vs_f64={gerr:.3e} (snapshot {snap_s:.1f}s, "
            f"restore+pack {restore_s:.1f}s; smoke timing {graph_s:.2f}s "
            f"incl. compiles)")
        check(grec >= 0.95, f"graph recall@10 {grec}")
        check_health(g_store, "graph replica")
        del g_store, g_view

        # -- int8 codes + exact fp32 rerank on a restored replica ---------
        q_store = restore_replica(
            root, dataclasses.replace(cfg, quantize="int8"))
        q_view = pack_view(q_store)
        check_blocks_on(q_view, platform, 1)
        qres, q_s = serve(q_store, reqs)
        qrec, qerr = recall_and_err(qres, reqs, oracle, gid_to_row)
        log(f"int8: requests={len(reqs)} recall@10={qrec:.4f} "
            f"max_abs_dist_err_vs_f64={qerr:.3e} "
            f"pack_device_bytes={q_view.nbytes} (smoke timing {q_s:.2f}s "
            f"incl. compiles)")
        check(qrec >= 0.95, f"int8 recall@10 {qrec}")
        check_health(q_store, "int8 replica")


def four_chips(args, platform):
    """The mesh-sharded pack over a 4-device ``"shard"`` mesh against the
    same pack on one device: bit-for-bit answers, blocks on all 4."""
    import numpy as np

    from repro.distributed.segment_shards import make_shard_mesh
    n = args.n
    x, s, owner = make_corpus(n, args.seed)
    filters = make_filters(args.seed)
    cfg = stream_cfg(n, n_shards=4)
    mesh = make_shard_mesh(4)
    check(mesh.devices.size == 4, f"mesh of {mesh.devices.size} devices")
    t0 = time.perf_counter()
    store, _ = load_store(x, s, owner, cfg, mesh=mesh)
    view = pack_view(store)
    n_blocks = check_blocks_on(view, platform, 4)
    log(f"load: {time.perf_counter() - t0:.1f}s for N={n} n_shards=4 over "
        f"a 4-device mesh; pack_device_bytes={view.nbytes} "
        f"blocks_spanning_4_devices={n_blocks}")
    reqs = make_requests(x, owner, filters, args.seed + 10, 0)
    res_mesh, mesh_s = serve(store, reqs)
    check_health(store, "mesh store")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        store.snapshot_to(root)
        one = restore_replica(root, cfg)
        check_blocks_on(pack_view(one), platform, 1)
        res_one, one_s = serve(one, reqs)
        check_health(one, "one-device store")
    same = [np.array_equal(res_mesh[r][0], res_one[r][0])
            and np.array_equal(res_mesh[r][1], res_one[r][1])
            for r, _, _, _ in reqs]
    log(f"four chips: {sum(same)}/{len(same)} answers bit-for-bit equal "
        f"(gid, dist) mesh vs one device; smoke timing mesh {mesh_s:.2f}s, "
        f"one device {one_s:.2f}s incl. compiles")
    check(all(same), "mesh and one-device answers differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh parity phase")
    ap.add_argument("--n", type=int, default=1 << 18,
                    help="corpus points (a multiple of 16 * tenants)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="allow a CPU backend; never prints the ok line")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu" and not args.cpu_rehearsal:
        print(f"error: no TPU (JAX sees {info['platform']}); this smoke "
              "run needs the chip", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if info["count"] < need:
        print(f"error: {need} devices needed, {info['count']} present",
              file=sys.stderr)
        return 2
    check(args.n % (16 * N_TENANTS) == 0, "--n must divide by 64")

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(args, info["platform"])
    except Exception:  # noqa: BLE001 — any failed phase fails the run
        traceback.print_exc()
        print("FAILED", file=sys.stderr)
        return 1
    log(f"smoke run wall time: {time.perf_counter() - t0:.1f}s")
    if args.cpu_rehearsal:
        log("cpu rehearsal passed (no ok line off the chip)")
        return 0
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
