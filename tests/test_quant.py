"""Quantized read path: codec contract (scale-bounded round-trip error),
asymmetric-distance kernel vs the dequantized oracle, two-stage exactness
and the deterministic (dist, gid) tie-break, fp32 A/B parity with
``quantize=None``, snapshot/restore without re-encoding, and dispatch
compile warming."""
import os
import tempfile

import numpy as np
import pytest

from repro.core import BoxFilter, ComposeFilter, CubeGraphConfig, IntervalFilter
from repro.core.workloads import (ground_truth, make_box_filter, make_dataset,
                                  make_polygon_filter, recall)
from repro.distributed.segment_shards import (SegmentShardSource,
                                              build_bucketed_pack,
                                              build_shard_pack, host_topk,
                                              pack_search)
from repro.kernels import (dispatch_trace_count, quant_meta_rows,
                           sharded_quant_filtered_topk, warm_sharded_shapes)
from repro.quant import dequantize, encode_segment, fit_scales, quantize
from repro.streaming import SegmentManager, StreamConfig

IDX_CFG = CubeGraphConfig(n_layers=2, m_intra=8, m_cross=3)


# ---------------------------------------------------------------------------
# Codec contract
# ---------------------------------------------------------------------------
def _check_codec_contract(x):
    sq = encode_segment(x)
    assert sq.codes.dtype == np.int8
    assert np.abs(sq.codes.astype(np.int32)).max(initial=0) <= 127
    deq = dequantize(sq.codes, sq.scales)
    # per-dimension scale bound: |x - deq| <= scale/2 (+ fp32 slack)
    bound = sq.scales[None, :] * 0.5 * (1 + 1e-5) + 1e-12
    assert (np.abs(x - deq) <= bound).all()
    # stored norms are the *dequantized* norms, bit-for-bit
    assert np.allclose(sq.xsq, np.einsum("nd,nd->n", deq, deq), rtol=1e-6)


@pytest.mark.parametrize("seed,n,d,spread", [
    (0, 200, 8, 1.0), (1, 50, 32, 100.0), (2, 1, 4, 0.01), (3, 300, 16, 1e4),
])
def test_codec_roundtrip_error_within_scale_bound(seed, n, d, spread):
    """Deterministic codec property incl. wildly different per-dim ranges
    and an all-zero dimension (scale floor)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    x *= spread * rng.uniform(0.01, 1.0, size=(1, d)).astype(np.float32)
    x[:, d // 2] = 0.0                      # zero-variance dim stays exact
    _check_codec_contract(x)
    deq = dequantize(quantize(x, fit_scales(x)), fit_scales(x))
    assert (deq[:, d // 2] == 0.0).all()


try:                                     # richer search space when available
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 10_000), n=st.integers(1, 120),
           d=st.integers(1, 48),
           log_spread=st.floats(-3, 5, allow_nan=False))
    def test_codec_roundtrip_error_hypothesis(seed, n, d, log_spread):
        """Hypothesis variant of the scale-bound contract."""
        rng = np.random.default_rng(seed)
        x = (rng.normal(size=(n, d)) * 10.0 ** log_spread).astype(np.float32)
        _check_codec_contract(x)
except ImportError:                      # pragma: no cover - optional dep
    pass


# ---------------------------------------------------------------------------
# Asymmetric-distance kernel
# ---------------------------------------------------------------------------
def _quant_stack(seed, g, n, d=32, m=3, cap=768):
    """Transposed quantized shard stack + per-shard dequantized oracles."""
    from repro.kernels import PAD_META
    rng = np.random.default_rng(seed)
    dq, mq = max(32, -(-d // 32) * 32), quant_meta_rows(m)
    x = rng.normal(size=(g, n, d)).astype(np.float32)
    s = rng.uniform(size=(g, n, m)).astype(np.float32)
    codes = np.zeros((g, dq, cap), np.int8)
    stt = np.full((g, mq, cap), PAD_META, np.float32)
    scales = np.zeros((g, dq), np.float32)
    deqs = []
    for gi in range(g):
        sq = encode_segment(x[gi])
        codes[gi, :d, :n] = sq.codes.T
        stt[gi, :, :n] = 0.0
        stt[gi, :m, :n] = s[gi].T
        stt[gi, mq - 1, :n] = sq.xsq
        scales[gi, :d] = sq.scales
        deqs.append(dequantize(sq.codes, sq.scales))
    return x, s, codes, stt, scales, deqs


@pytest.mark.parametrize("seed,g,n,k", [(0, 1, 300, 5), (1, 3, 700, 17)])
def test_quant_kernel_matches_dequantized_oracle(seed, g, n, k):
    """The fused int8 kernel's distances equal exact fp32 distances against
    the *dequantized* vectors, for every filter kind incl. the jnp
    fallback — i.e. quantization error lives only in the codes, never in
    the kernel."""
    import jax.numpy as jnp
    x, s, codes, stt, scales, deqs = _quant_stack(seed, g, n)
    rng = np.random.default_rng(seed + 9)
    q = rng.normal(size=(5, 32)).astype(np.float32)
    filters = [None,
               make_box_filter(3, 0.5, seed=seed),
               ComposeFilter(BoxFilter(lo=np.zeros(3, np.float32),
                                       hi=np.ones(3, np.float32)),
                             IntervalFilter(dim=2, lo=np.float32(0.3)),
                             "and"),
               make_polygon_filter(3, 0.6, seed=seed)]   # jnp fallback
    for filt in filters:
        ids, dd = sharded_quant_filtered_topk(q, codes, stt, scales, filt,
                                              k, m=3)
        ids, dd = np.asarray(ids), np.asarray(dd)
        for gi in range(g):
            dist = ((q[:, None, :] - deqs[gi][None, :, :]) ** 2).sum(-1)
            if filt is not None:
                ok = np.asarray(filt.contains(jnp.asarray(s[gi])))
                dist = np.where(ok[None, :], dist, np.inf)
            ref = np.sort(dist, axis=1)[:, :k]
            got = dd[gi]
            fin = np.isfinite(ref)
            assert np.allclose(got[fin], ref[fin], rtol=1e-4, atol=1e-4), \
                f"filter {filt}"
            assert (ids[gi][~np.isfinite(got)] == -1).all()


# ---------------------------------------------------------------------------
# Two-stage path: exactness, tie-break, A/B parity
# ---------------------------------------------------------------------------
def _quant_sources(seed, n_segments, d=24, m=3):
    rng = np.random.default_rng(seed)
    sources, gid0 = [], 0
    for sid in range(n_segments):
        n = int(rng.integers(150, 500))
        x = rng.normal(size=(n, d)).astype(np.float32)
        s = rng.uniform(size=(n, m))
        g = np.arange(gid0, gid0 + n, dtype=np.int64)
        gid0 += n
        q8 = encode_segment(x)
        sources.append(SegmentShardSource(
            sid, x, s, g, float(s[:, m - 1].min()), float(s[:, m - 1].max()),
            codes=q8.codes, scales=q8.scales, xsq=q8.xsq))
    return sources


def _lookup_for(sources):
    x_all = np.concatenate([s.x for s in sources])
    g_all = np.concatenate([s.gids for s in sources])
    by_gid = np.zeros((int(g_all.max()) + 1, x_all.shape[1]), np.float32)
    by_gid[g_all] = x_all
    return lambda gids: (by_gid[np.asarray(gids, np.int64)], None,
                         np.ones(len(gids), bool))


def test_two_stage_equals_fp32_path_with_full_overfetch():
    """With the over-fetch covering every live point, the reranked
    quantized result must recover exactly the fp32 pack's gids (the rerank
    is exact, so only candidate misses could differ — and there are
    none)."""
    sources = _quant_sources(7, 3)
    lookup = _lookup_for(sources)
    qp = build_bucketed_pack(sources, n_shards=2, quantize="int8")
    fp = build_shard_pack(sources, n_shards=2)
    rng = np.random.default_rng(8)
    q = rng.normal(size=(6, 24)).astype(np.float32)
    for filt in (None, make_box_filter(3, 0.6, seed=7)):
        gi, di = pack_search(qp, q, filt, k=10, lookup=lookup,
                             rerank_multiple=10_000)
        gf, df = pack_search(fp, q, filt, k=10)
        assert np.array_equal(gi, gf)
        assert np.allclose(np.where(np.isfinite(di), di, 0),
                           np.where(np.isfinite(df), df, 0), atol=1e-4)


def test_reranked_tiebreak_is_deterministic_dist_gid():
    """Duplicated vectors in different segments produce exact distance
    ties; the reranked output must order them by ascending gid — the same
    contract ``host_topk`` / ``merge_topk`` enforce — regardless of
    segment insertion order."""
    rng = np.random.default_rng(21)
    base = rng.normal(size=(40, 24)).astype(np.float32)
    dup = base[:3].copy()                    # rows duplicated in every seg
    orders = [(0, 1, 2), (2, 0, 1)]
    results = []
    for perm in orders:
        sources = []
        for slot, sid in enumerate(perm):
            x = np.concatenate([dup, base[10 + 10 * sid: 20 + 10 * sid]])
            s = rng.uniform(size=(len(x), 3))
            g = np.arange(sid * 1000, sid * 1000 + len(x), dtype=np.int64)
            q8 = encode_segment(x)
            sources.append(SegmentShardSource(
                sid, x, s, g, 0.0, 1.0, codes=q8.codes, scales=q8.scales,
                xsq=q8.xsq))
        lookup = _lookup_for(sources)
        pack = build_bucketed_pack(sorted(sources, key=lambda t: t.seg_id),
                                   n_shards=2, quantize="int8")
        gi, di = pack_search(pack, dup[:1], None, k=5, lookup=lookup,
                             rerank_multiple=100)
        results.append((gi, di))
    g0, d0 = results[0]
    for gi, di in results[1:]:
        assert np.array_equal(g0, gi) and np.array_equal(d0, di)
    # the three exact duplicates tie at distance 0 -> ascending gid
    assert g0[0, :3].tolist() == [0, 1000, 2000]
    assert np.allclose(d0[0, :3], d0[0, 0])
    # and the ordering matches host_topk's on the same (gid, dist) rows
    hg, hd = host_topk(g0, d0, 5)
    assert np.array_equal(hg, g0) and np.array_equal(hd, d0)


def test_fp32_path_bit_for_bit_unchanged_when_quantize_none():
    """A/B parity: with ``quantize=None`` the bucketed pack holds fp32
    blocks (no codes), dispatches the fp32 kernel, and answers bit-for-bit
    like the legacy monolithic fp32 pack — proving the quant plumbing
    changed nothing on the baseline path."""
    sources = _quant_sources(13, 3)
    pack = build_bucketed_pack(sources, n_shards=2)          # quantize=None
    assert pack.quantize is None
    for b in pack.buckets.values():
        assert b.codes is None and b.x is not None
    view = pack.view()
    assert view.quantize is None
    legacy = build_shard_pack(sources, n_shards=2)
    rng = np.random.default_rng(13)
    q = rng.normal(size=(5, 24)).astype(np.float32)
    for filt in (None, make_box_filter(3, 0.5, seed=13)):
        gb, db = pack_search(pack, q, filt, k=12)
        gl, dl = pack_search(legacy, q, filt, k=12)
        assert np.array_equal(db, dl)                        # bit-for-bit
        uniq = np.ones_like(gb, bool)
        uniq[:, 1:] &= db[:, 1:] != db[:, :-1]
        uniq[:, :-1] &= db[:, :-1] != db[:, 1:]
        assert np.array_equal(gb[uniq], gl[uniq])


# ---------------------------------------------------------------------------
# Manager integration
# ---------------------------------------------------------------------------
def _mgr(quantize, seed=31, n=1600, d=24, rerank_multiple=4):
    x, s = make_dataset(n, d, 3, seed=seed)
    s[:, 2] = np.arange(n) / n
    mgr = SegmentManager(d, 3, StreamConfig(
        time_dim=2, seal_max_points=400, n_shards=2, quantize=quantize,
        rerank_multiple=rerank_multiple, index_cfg=IDX_CFG))
    mgr.ingest(x, s)
    return mgr, x, s


def test_manager_quantized_recall_and_memory():
    """End-to-end acceptance mirror: the quantized manager reaches
    recall@10 >= 0.95 at the default over-fetch while holding >= 3x fewer
    sealed-pack device bytes than the fp32 manager on the same stream."""
    mq, x, s = _mgr("int8")
    mf, _, _ = _mgr(None)
    rng = np.random.default_rng(32)
    q = (x[rng.integers(0, len(x), 8)]
         + 0.05 * rng.normal(size=(8, 24)).astype(np.float32))
    f = ComposeFilter(BoxFilter(lo=np.zeros(3, np.float32),
                                hi=np.ones(3, np.float32)),
                      IntervalFilter(dim=2, lo=np.float32(0.1)), "and")
    gt, _ = ground_truth(x, s, q, f, 10, valid=mq.alive)
    ids_q, _ = mq.query(q, f, k=10)
    ids_f, _ = mf.query(q, f, k=10)
    assert recall(ids_f, gt) >= 0.99          # fp32 path is exact
    assert recall(ids_q, gt) >= 0.95          # acceptance bar
    nb_q = mq.stats()["pack_nbytes"]
    nb_f = mf.stats()["pack_nbytes"]
    assert nb_q > 0 and nb_f / nb_q >= 3.0
    assert mq.stats()["quantize"] == "int8"


def test_quantized_incremental_pack_matches_cold_rebuild():
    """Deletes / compaction / reseals keep the incrementally maintained
    quantized pack answering identically to a forced cold rebuild of the
    same segments (codes are attached to segments, so both paths stack the
    same bytes)."""
    mgr, x, s = _mgr("int8", seed=41)
    rng = np.random.default_rng(42)
    q = rng.normal(size=(5, 24)).astype(np.float32)
    mgr.query(q, None, k=8)                   # cold-build + record sigs
    mgr.delete(rng.integers(0, len(x), 150))
    mgr.ingest(x[:300] + 1.0, s[:300] * [1, 1, 0] + [0, 0, 1.5])
    mgr.seal()
    mgr.compact()
    for filt in (None, make_box_filter(3, 0.6, seed=41)):
        gi, di = mgr.query(q, filt, k=12)
        mgr._pack = None                      # force from-scratch rebuild
        gr, dr = mgr.query(q, filt, k=12)
        assert np.array_equal(di, dr)
        assert np.array_equal(gi, gr)


def test_quantized_snapshot_restore_never_requantizes(tmp_path,
                                                      monkeypatch):
    """Snapshot/restore round-trips the codec payload bit-for-bit: the
    restored replica answers identically and never calls the encoder."""
    mgr, x, s = _mgr("int8", seed=51, n=1200)
    mgr.delete(np.arange(0, 300, 3))
    rng = np.random.default_rng(52)
    q = rng.normal(size=(6, 24)).astype(np.float32)
    ids0, dd0 = mgr.query(q, None, k=10)
    snap = os.path.join(str(tmp_path), "snap")
    mgr.snapshot_to(snap)

    import repro.quant.codec as codec

    def _boom(*a, **k):
        raise AssertionError("restore re-quantized a segment")
    monkeypatch.setattr(codec, "encode_segment", _boom)
    m2 = SegmentManager.restore(snap, resume=False)
    for s1, s2 in zip(mgr.segments, m2.segments):
        assert s2.quant is not None and s2.quant.kind == "int8"
        assert np.array_equal(s1.quant.codes, s2.quant.codes)
        assert np.array_equal(s1.quant.scales, s2.quant.scales)
    ids1, dd1 = m2.query(q, None, k=10)
    assert np.array_equal(ids0, ids1) and np.array_equal(dd0, dd1)


def test_live_snapshot_rows_stay_aligned_after_deletes():
    """``SealedSegment.live_snapshot`` derives vectors, metadata, gids AND
    the codec payload from one read of the validity mask, so its row
    counts always agree — the input contract of the lock-free cold pack
    build."""
    mgr, x, s = _mgr("int8", seed=81, n=900)
    seg = mgr.segments[0]
    mgr.delete(seg.gids[::3])
    xl, sl, gl, quant = seg.live_snapshot()
    assert len(xl) == len(sl) == len(gl) == quant.n
    assert quant.n == seg.n_live
    # payload rows are the sealed codes of exactly the surviving rows
    keep = np.nonzero(seg.index.valid)[0]
    assert np.array_equal(quant.codes, seg.quant.codes[keep])


def test_pre_quant_snapshot_gains_codec_at_compaction(tmp_path):
    """A pre-quantization snapshot restored under ``quantize='int8'``
    works immediately (on-the-fly pack encode) and a compaction GC-rewrite
    upgrades the rewritten segment with a persisted codec payload."""
    mgr, x, s = _mgr(None, seed=91, n=900)
    snap = os.path.join(str(tmp_path), "snap")
    mgr.snapshot_to(snap)
    cfg = StreamConfig(time_dim=2, seal_max_points=400, n_shards=2,
                       quantize="int8", index_cfg=IDX_CFG)
    m2 = SegmentManager.restore(snap, cfg=cfg, resume=False)
    assert all(seg.quant is None for seg in m2.segments)
    rng = np.random.default_rng(92)
    q = rng.normal(size=(4, 24)).astype(np.float32)
    ids, _ = m2.query(q, None, k=8)           # on-the-fly encode fallback
    assert (ids >= 0).any()
    victim = m2.segments[0]
    m2.delete(victim.gids[: int(0.6 * len(victim.gids))])
    m2.compact()                              # GC rewrite -> codec fitted
    rewritten = [seg for seg in m2.segments if seg.seg_id == victim.seg_id]
    assert rewritten and rewritten[0].quant is not None
    assert rewritten[0].quant.kind == "int8"
    ids2, _ = m2.query(q, None, k=8)
    assert (ids2 >= 0).any()


def test_config_validation_and_serving_plumb():
    """Invalid quantize configs fail fast; DocumentStore(quantize=) wires
    the knob into the streaming manager."""
    with pytest.raises(ValueError, match="n_shards"):
        SegmentManager(8, 3, StreamConfig(quantize="int8", n_shards=0))
    with pytest.raises(ValueError, match="unknown quantize"):
        SegmentManager(8, 3, StreamConfig(quantize="int3", n_shards=1))
    with pytest.raises(ValueError, match="incremental_pack"):
        SegmentManager(8, 3, StreamConfig(quantize="int8", n_shards=1,
                                          incremental_pack=False))
    from repro.serving.rag import Document, DocumentStore
    rng = np.random.default_rng(61)
    docs = [Document(i, np.arange(4, dtype=np.int32),
                     rng.normal(size=16).astype(np.float32),
                     rng.uniform(size=3)) for i in range(600)]
    with pytest.raises(ValueError, match="streaming"):
        DocumentStore(docs, quantize="int8")
    store = DocumentStore(
        docs, streaming=True, quantize="int8",
        stream_cfg=StreamConfig(seal_max_points=200, index_cfg=IDX_CFG))
    assert store.manager.cfg.quantize == "int8"
    assert store.manager.cfg.n_shards >= 1
    hits = store.retrieve(docs[5].embedding, None, k=3)
    assert docs[5] in hits[0]


# ---------------------------------------------------------------------------
# Compile warming
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("quantize", [None, "int8"])
def test_bucket_growth_is_pre_traced_off_the_query_path(quantize):
    """After a query has recorded its dispatch signature, a bucket
    doubling (or a fresh bucket) is pre-traced AND pre-compiled at seal
    time — including the mesh sharding of the real blocks, since jit
    caches per input sharding — so the next query triggers zero new
    dispatch traces and zero new executables (the exp12 residual-spike
    fix)."""
    from repro.distributed.segment_shards import make_shard_mesh
    from repro.kernels import ops
    rng = np.random.default_rng(71)

    def batch(n, t0):
        x = rng.normal(size=(n, 16)).astype(np.float32)
        s = rng.uniform(size=(n, 3))
        s[:, 2] = t0 + np.linspace(0, .1, n)
        return x, s

    mesh = make_shard_mesh()
    mgr = SegmentManager(16, 3, StreamConfig(
        time_dim=2, seal_max_points=1 << 30, n_shards=2, quantize=quantize,
        index_cfg=IDX_CFG), shard_mesh=mesh)
    x, s = batch(300, 0.0)
    mgr.ingest(x, s)
    mgr.seal()
    q = rng.normal(size=(4, 16)).astype(np.float32)
    mgr.query(q, None, k=5)                   # record sig + cold build
    for i in range(3):                        # grow past the initial slots
        x, s = batch(300, float(i + 1))
        mgr.ingest(x, s)
        mgr.seal()
    # the dispatch the query path uses for this config (k=5 -> kpad=8)
    factory = (ops._sharded_quant_dispatch if quantize
               else ops._sharded_kernel_dispatch)
    dispatch = factory("none", 8, "l2", 64, 256, mesh)
    compiled_before = dispatch._cache_size()
    traces_before = dispatch_trace_count()
    ids, _ = mgr.query(q, None, k=5)
    assert dispatch_trace_count() == traces_before
    assert dispatch._cache_size() == compiled_before
    assert (ids >= 0).any()
    # manual warming API: a recorded signature warms matching shapes
    mode = "int8" if quantize else "fp32"
    spec = ({"mode": "int8", "rows": 8, "cap": 512, "dq": 32,
             "mq": quant_meta_rows(3)} if quantize
            else {"mode": "fp32", "rows": 8, "cap": 512, "dpad": 128})
    assert warm_sharded_shapes([spec]) >= 1, mode
