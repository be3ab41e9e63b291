"""Multi-tenant serving tier: heterogeneous-batch bit parity, admission
control backpressure, per-request deadlines, value-based filter batching,
tenant isolation under racing writers, and snapshot round-trips.

The load-bearing claims here are *equalities*, not trends:

* a mixed-tenant mixed-filter service flush answers each request
  **bit-for-bit** like a solo ``MultiTenantStore.retrieve`` (and a
  heterogeneous ``DocumentStore.retrieve_grouped`` batch like per-request
  ``retrieve`` calls) — continuous filtered batching is a pure
  performance transform;
* one tenant's answers equal a dedicated single-tenant store's answers
  regardless of what another tenant inserts/deletes concurrently —
  isolation is correctness, not best-effort filtering.
"""
import dataclasses
import threading

import numpy as np
import pytest

from repro.core import (BallFilter, BoxFilter, ComposeFilter,
                        CubeGraphConfig, IntervalFilter)
from repro.obs import TraceLog
from repro.serving.batching import (RetrievalBatcher, RetrievalFailure,
                                    RetrievalRequest, _filter_key)
from repro.serving.rag import Document, DocumentStore
from repro.serving.service import (AdmissionController, CubeGraphService,
                                   ServeRequest)
from repro.serving.tenancy import (MultiTenantStore, TenantIsolationError,
                                   TenantQuotaError)
from repro.streaming import StreamConfig

IDX_CFG = CubeGraphConfig(n_layers=2, m_intra=8, m_cross=4)
D, M = 8, 3


def _stream_cfg(**kw):
    kw.setdefault("time_dim", 2)
    kw.setdefault("seal_max_points", 64)
    kw.setdefault("n_shards", 2)
    kw.setdefault("index_cfg", IDX_CFG)
    return StreamConfig(**kw)


def _docs(rng, n, base=0):
    return [Document(doc_id=base + i,
                     tokens=np.arange(3, dtype=np.int32),
                     embedding=rng.standard_normal(D).astype(np.float32),
                     metadata=np.array([rng.uniform(0, 10),
                                        rng.uniform(0, 10), float(i)]))
            for i in range(n)]


def _two_tenant_store(rng, n=150, **cfg_kw):
    store = MultiTenantStore(D, M, stream_cfg=_stream_cfg(**cfg_kw))
    for tenant, base in (("a", 0), ("b", 10_000)):
        store.create_collection(tenant)
        store.insert(tenant, _docs(rng, n, base=base))
    store.maintenance()
    return store


def _filters():
    return (BoxFilter(lo=np.float32([0, 0, -1e9]),
                      hi=np.float32([8, 8, 1e9])),
            ComposeFilter(BoxFilter(lo=np.float32([0, 0, -1e9]),
                                    hi=np.float32([9, 9, 1e9])),
                          IntervalFilter(dim=2, lo=10.0, hi=120.0), "and"),
            None)


# ---------------------------------------------------------------------------
# Continuous filtered batching: bit-for-bit parity
# ---------------------------------------------------------------------------
def test_service_flush_bit_equals_solo_retrieve():
    """A mixed-tenant mixed-filter mixed-k flush through the service must
    return, per request, exactly the gids/dists/documents a solo
    tenant-scoped ``retrieve`` returns — the heterogeneous batch shares
    per-bucket device reads without perturbing any answer."""
    rng = np.random.default_rng(0)
    store = _two_tenant_store(rng)
    svc = CubeGraphService(store)
    filters = _filters()
    reqs = [ServeRequest(req_id=rid, tenant=("a", "b")[rid % 2],
                         query_emb=rng.standard_normal(D)
                         .astype(np.float32),
                         filt=filters[rid % 3], k=(5, 10)[rid % 2])
            for rid in range(12)]
    for r in reqs:
        assert svc.submit(r) is None
    answers = svc.flush()
    assert set(answers) == {r.req_id for r in reqs}
    for r in reqs:
        sr = answers[r.req_id]
        solo = store.retrieve(r.tenant, r.query_emb, r.filt, k=r.k)
        assert np.array_equal(sr.gids, solo.gids[0])
        assert np.array_equal(sr.dists, solo.dists[0])
        assert [d.doc_id for d in sr.docs] == \
            [d.doc_id for d in solo.docs[0]]
        assert not sr.degraded
        # answers are retained for pollers too
        assert svc.take_result(r.req_id) is sr
    assert svc.take_result(reqs[0].req_id) is None      # popped once


def test_grouped_failure_falls_back_counted(monkeypatch):
    """A failing grouped dispatch falls back to per-group solo queries:
    the answers still equal solo ``retrieve``, and the fallback is
    counted and recorded in health, never passed off as grouped."""
    rng = np.random.default_rng(3)
    store = _two_tenant_store(rng)
    svc = CubeGraphService(store)
    filters = _filters()
    reqs = [ServeRequest(req_id=rid, tenant=("a", "b")[rid % 2],
                         query_emb=rng.standard_normal(D)
                         .astype(np.float32),
                         filt=filters[rid % 3], k=5)
            for rid in range(6)]

    def boom(*a, **kw):
        raise RuntimeError("grouped dispatch failed")

    monkeypatch.setattr(store.manager, "query_grouped", boom)
    counter = store.metrics.counter("retrieval_group_fallback_total")
    assert counter.value == 0
    for r in reqs:
        assert svc.submit(r) is None
    answers = svc.flush()
    assert counter.value == 1
    health = store.manager.supervisor.health()["serving.grouped"]
    assert health["errors"] == 1
    assert "grouped dispatch failed" in health["last_error"]
    for r in reqs:
        sr = answers[r.req_id]
        solo = store.retrieve(r.tenant, r.query_emb, r.filt, k=r.k)
        assert np.array_equal(sr.gids, solo.gids[0])
        assert np.array_equal(sr.dists, solo.dists[0])
        assert not sr.degraded


def test_document_store_retrieve_grouped_parity():
    """``DocumentStore.retrieve_grouped`` over heterogeneous (filter, k)
    requests returns per-request rows identical to solo ``retrieve``."""
    rng = np.random.default_rng(1)
    store = DocumentStore(_docs(rng, 200), index_cfg=IDX_CFG,
                          streaming=True, stream_cfg=_stream_cfg())
    store.maintenance()
    filters = (BoxFilter(lo=np.float32([0, 0, -1e9]),
                         hi=np.float32([7, 7, 1e9])),
               BallFilter(center=np.float32([5, 5]),
                          radius=np.float32(4.0)),
               None)
    reqs = [RetrievalRequest(req_id=rid,
                             query_emb=rng.standard_normal(D)
                             .astype(np.float32),
                             filt=filters[rid % 3], k=(4, 9)[rid % 2])
            for rid in range(9)]
    grouped = store.retrieve_grouped(reqs)
    for r in reqs:
        solo = store.retrieve(r.query_emb, r.filt, k=r.k)[0]
        assert [d.doc_id for d in grouped[r.req_id]] == \
            [d.doc_id for d in solo]
        assert grouped[r.req_id].degraded == solo.degraded


# ---------------------------------------------------------------------------
# Admission control: explicit over_quota backpressure
# ---------------------------------------------------------------------------
def test_admission_over_quota_backpressure():
    rng = np.random.default_rng(2)
    store = _two_tenant_store(rng, n=80)
    svc = CubeGraphService(store, admission=AdmissionController(
        max_queue_per_tenant=3))
    q = rng.standard_normal(D).astype(np.float32)
    failures = []
    for rid in range(5):
        res = svc.submit(ServeRequest(req_id=rid, tenant="a", query_emb=q))
        if res is not None:
            failures.append(res)
    assert len(failures) == 2
    assert all(isinstance(f, RetrievalFailure) and f.reason == "over_quota"
               for f in failures)
    # rejections are poll-visible and counted per tenant
    assert svc.take_result(failures[0].req_id).reason == "over_quota"
    snap = store.metrics.snapshot()["counters"]
    assert snap['tenant_rejected_total{tenant="a"}'] == 2
    # tenant b is unaffected by a's full queue
    assert svc.submit(ServeRequest(req_id=99, tenant="b",
                                   query_emb=q)) is None
    # admitted requests still answer normally
    answers = svc.flush()
    assert sum(1 for v in answers.values()
               if not isinstance(v, RetrievalFailure)) == 4
    with pytest.raises(KeyError):
        svc.submit(ServeRequest(req_id=100, tenant="nobody", query_emb=q))


def test_admission_global_cap():
    rng = np.random.default_rng(3)
    store = _two_tenant_store(rng, n=80)
    svc = CubeGraphService(store, admission=AdmissionController(
        max_queue_per_tenant=64, max_queue_total=2))
    q = rng.standard_normal(D).astype(np.float32)
    outcomes = [svc.submit(ServeRequest(req_id=i, tenant=("a", "b")[i % 2],
                                        query_emb=q)) for i in range(4)]
    assert [o is None for o in outcomes] == [True, True, False, False]


# ---------------------------------------------------------------------------
# Per-request deadlines / degraded propagation
# ---------------------------------------------------------------------------
def test_deadline_degrades_only_its_own_group():
    """An already-expired deadline on one request degrades *that* answer
    (with a per-reason skip count) while the other tenants/groups in the
    same flush answer completely."""
    rng = np.random.default_rng(4)
    store = _two_tenant_store(rng)
    svc = CubeGraphService(store)
    q = rng.standard_normal(D).astype(np.float32)
    svc.submit(ServeRequest(req_id=0, tenant="a", query_emb=q,
                            deadline_ms=0.0))
    svc.submit(ServeRequest(req_id=1, tenant="b", query_emb=q))
    answers = svc.flush()
    assert answers[0].degraded
    assert answers[0].reasons.get("deadline_sealed_scan", 0) >= 1
    assert not answers[1].degraded
    assert (answers[1].gids >= 0).any()
    snap = store.metrics.snapshot()["counters"]
    assert snap['tenant_degraded_total{tenant="a"}'] == 1


def test_retrieval_batcher_deadline_and_degraded_rows():
    """Satellite: ``RetrievalRequest.deadline_ms`` flows through
    ``DocumentStore.retrieve(deadline_ms=...)`` and each returned row
    carries the query's degraded markers."""
    rng = np.random.default_rng(5)
    store = DocumentStore(_docs(rng, 200), index_cfg=IDX_CFG,
                          streaming=True, stream_cfg=_stream_cfg())
    store.maintenance()
    batcher = RetrievalBatcher(store)
    q = rng.standard_normal(D).astype(np.float32)
    batcher.submit(RetrievalRequest(req_id=0, query_emb=q, filt=None,
                                    k=5, deadline_ms=0.0))
    batcher.submit(RetrievalRequest(req_id=1, query_emb=q, filt=None, k=5))
    rows = batcher.flush()
    assert rows[0].degraded and rows[0].reasons
    assert not rows[1].degraded and len(rows[1]) > 0
    # solo retrieve agrees on the degraded marker shape
    solo = store.retrieve(q, None, k=5, deadline_ms=0.0)[0]
    assert solo.degraded and solo.reasons.get("deadline_sealed_scan", 0) >= 1


# ---------------------------------------------------------------------------
# Value-based filter keys (satellite regression)
# ---------------------------------------------------------------------------
def test_filter_key_is_value_based():
    """Two equal-valued but *distinct* filter objects key identically (so
    they batch together); different shapes/dtypes with the same bytes do
    NOT collide."""
    lo, hi = np.float32([0, 0, -1e9]), np.float32([8, 8, 1e9])
    f1 = BoxFilter(lo=lo.copy(), hi=hi.copy())
    f2 = BoxFilter(lo=lo.copy(), hi=hi.copy())
    assert f1 is not f2
    assert _filter_key(f1, 5) == _filter_key(f2, 5)
    assert _filter_key(f1, 5) != _filter_key(f2, 6)
    # equal-valued compositions too (object leaves recurse by value)
    c1 = ComposeFilter(BoxFilter(lo=lo.copy(), hi=hi.copy()),
                       IntervalFilter(dim=2, lo=1.0, hi=2.0), "and")
    c2 = ComposeFilter(BoxFilter(lo=lo.copy(), hi=hi.copy()),
                       IntervalFilter(dim=2, lo=1.0, hi=2.0), "and")
    assert _filter_key(c1, 5) == _filter_key(c2, 5)
    assert _filter_key(c1, 5) != _filter_key(
        ComposeFilter(BoxFilter(lo=lo.copy(), hi=hi.copy()),
                      IntervalFilter(dim=2, lo=1.0, hi=2.5), "and"), 5)
    # same bytes, different shape / dtype must stay distinct
    flat = BoxFilter(lo=np.float32([1, 2]), hi=np.float32([3, 4]))
    col = BoxFilter(lo=np.float32([[1], [2]]), hi=np.float32([[3], [4]]))
    assert _filter_key(flat, 5) != _filter_key(col, 5)
    as_int = BoxFilter(lo=np.int32([1, 2]), hi=np.int32([3, 4]))
    assert _filter_key(flat, 5) != _filter_key(as_int, 5)


def test_equal_valued_filters_batch_together():
    """Regression: the batcher used to group by object identity, issuing
    one store dispatch per *instance* of the same filter value.  Equal
    values must share one batched ``retrieve`` call."""
    rng = np.random.default_rng(6)
    store = DocumentStore(_docs(rng, 150), index_cfg=IDX_CFG,
                          streaming=True, stream_cfg=_stream_cfg())
    store.maintenance()
    calls = []
    inner = store.retrieve
    store.retrieve = lambda *a, **kw: (calls.append(1) or inner(*a, **kw))
    batcher = RetrievalBatcher(store)
    for rid in range(4):
        batcher.submit(RetrievalRequest(
            req_id=rid, query_emb=rng.standard_normal(D)
            .astype(np.float32),
            filt=BoxFilter(lo=np.float32([0, 0, -1e9]),
                           hi=np.float32([8, 8, 1e9])), k=5))
    rows = batcher.flush()
    assert len(calls) == 1, "equal-valued filters were not batched"
    assert set(rows) == {0, 1, 2, 3}


# ---------------------------------------------------------------------------
# Tenant isolation: quotas, ownership, racing writers
# ---------------------------------------------------------------------------
def test_quota_and_ownership_errors():
    rng = np.random.default_rng(7)
    store = MultiTenantStore(D, M, stream_cfg=_stream_cfg())
    store.create_collection("a", quota_points=100)
    store.create_collection("b")
    a_gids = store.insert("a", _docs(rng, 90))
    b_gids = store.insert("b", _docs(rng, 50, base=10_000))
    with pytest.raises(TenantQuotaError):
        store.insert("a", _docs(rng, 20, base=500))
    assert store.collection("a").n_live == 90           # nothing ingested
    # deleting makes room again
    store.delete("a", a_gids[:40])
    store.insert("a", _docs(rng, 20, base=500))
    with pytest.raises(TenantIsolationError):
        store.delete("a", b_gids[:2])
    assert store.collection("b").n_live == 50           # nothing deleted
    with pytest.raises(TenantIsolationError):
        store.materialize("a", np.asarray([[int(b_gids[0])]]))


def test_concurrent_cross_tenant_race_is_invisible():
    """Satellite: tenant b races inserts/deletes/maintenance against
    tenant a's retrieves on the shared substrate.  Every answer tenant a
    observes — mid-race and after — must be bit-for-bit the answer of a
    dedicated single-tenant oracle store that never saw tenant b."""
    rng = np.random.default_rng(8)
    a_docs = _docs(rng, 150)
    store = MultiTenantStore(D, M, stream_cfg=_stream_cfg())
    store.create_collection("a")
    store.create_collection("b")
    store.insert("a", a_docs)
    store.insert("b", _docs(rng, 100, base=10_000))
    store.maintenance()
    oracle = DocumentStore(a_docs, index_cfg=IDX_CFG, streaming=True,
                           stream_cfg=_stream_cfg())
    oracle.maintenance()
    qs = rng.standard_normal((3, D)).astype(np.float32)
    filt = BoxFilter(lo=np.float32([0, 0, -1e9]),
                     hi=np.float32([8, 8, 1e9]))
    expect_gids, expect_dists = oracle.manager.query(qs, filt, k=10)
    expect_ids = [[a_docs[g].doc_id for g in row if g >= 0]
                  for row in np.asarray(expect_gids)]

    errors, answers = [], []
    b_rng = np.random.default_rng(9)

    def churn_b():
        try:
            for i in range(4):
                gids = store.insert(
                    "b", _docs(b_rng, 30, base=20_000 + 100 * i))
                store.delete("b", gids[::3])
                store.maintenance()
        except Exception as exc:  # noqa: BLE001 — surface in main thread
            errors.append(exc)

    def read_a():
        try:
            for _ in range(8):
                answers.append(store.retrieve("a", qs, filt, k=10))
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=churn_b),
               threading.Thread(target=read_a)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    answers.append(store.retrieve("a", qs, filt, k=10))  # post-race too
    for ans in answers:
        assert np.array_equal(ans.dists, np.asarray(expect_dists,
                                                    np.float32))
        assert [[d.doc_id for d in row] for row in ans.docs] == expect_ids


# ---------------------------------------------------------------------------
# Traced flush: the serve.flush span tree
# ---------------------------------------------------------------------------
FLUSH_STEPS = {"serve.group", "serve.query_grouped", "serve.finish"}
QUERY_STEPS = {"snapshot", "delta_scan", "sealed_scan_grouped", "host_topk",
               "alive_filter"}
BUCKET_STEPS = {"group_stack", "kernel_launch", "device_wait", "readback"}


def _requests(rng, base, n=12, k=(5, 10)):
    filters = _filters()
    return [ServeRequest(req_id=base + rid, tenant=("a", "b")[rid % 2],
                         query_emb=rng.standard_normal(D)
                         .astype(np.float32),
                         filt=filters[rid % 3], k=k[rid % len(k)])
            for rid in range(n)]


def _traced_flush(svc, reqs):
    log = TraceLog()
    svc.trace_log = log
    try:
        for r in reqs:
            assert svc.submit(r) is None
        answers = svc.flush()
    finally:
        svc.trace_log = None
    assert len(log) == 1
    return answers, log.traces()[0]


def test_traced_flush_bit_equals_untraced():
    """Tracing a flush changes no answer: gids, dists and documents are
    bit-for-bit those of the same requests flushed untraced."""
    rng = np.random.default_rng(12)
    store = _two_tenant_store(rng)
    svc = CubeGraphService(store)
    reqs = _requests(rng, 0)
    for r in reqs:
        assert svc.submit(r) is None
    plain = svc.flush()
    again = [dataclasses.replace(r, req_id=r.req_id + 100, enqueued_at=0.0)
             for r in reqs]
    traced, trace = _traced_flush(svc, again)
    assert trace.root.attrs["requests"] == len(reqs)
    for r in reqs:
        a, b = plain[r.req_id], traced[r.req_id + 100]
        assert np.array_equal(a.gids, b.gids)
        assert np.array_equal(a.dists, b.dists)
        assert [d.doc_id for d in a.docs] == [d.doc_id for d in b.docs]


def test_traced_flush_span_tree_accounts_for_the_flush():
    """A traced flush records the documented span tree, and the direct
    children of ``serve.flush`` cover at least 95% of it (best of 3)."""
    rng = np.random.default_rng(13)
    store = _two_tenant_store(rng)
    svc = CubeGraphService(store)
    for r in _requests(rng, 0):                 # compile outside the trace
        assert svc.submit(r) is None
    svc.flush()
    best = 0.0
    for rnd in range(3):
        _, trace = _traced_flush(svc, _requests(rng, 100 * (rnd + 1)))
        root = trace.root
        assert root.name == "serve.flush"
        assert {"requests", "groups", "queue_wait_ms", "compiles",
                "cache_loads"} <= set(root.attrs)
        assert root.attrs["groups"] == 6 and root.attrs["queue_wait_ms"] > 0
        assert {c.name for c in root.children} == FLUSH_STEPS
        qg = [c for c in root.children if c.name == "serve.query_grouped"]
        assert len(qg) == 1
        assert {c.name for c in qg[0].children} == QUERY_STEPS
        buckets = [s for s in root.walk()
                   if s.name == "bucket_dispatch_grouped"]
        assert buckets
        for b in buckets:
            assert {c.name for c in b.children} == BUCKET_STEPS
            # per class one host build and one program; per bucket one
            # wait and one copy
            names = [c.name for c in b.children]
            assert names.count("group_stack") == names.count("kernel_launch")
            assert names[-2:] == ["device_wait", "readback"]
            assert names.count("readback") == 1
        for fin in (c for c in root.children if c.name == "serve.finish"):
            assert [c.name for c in fin.children] == ["materialize"]
        covered = sum(c.duration_ms for c in root.children)
        assert covered <= root.duration_ms * (1 + 1e-6)
        best = max(best, covered / root.duration_ms)
        if best >= 0.95:
            break
    assert best >= 0.95, f"flush steps cover only {best:.1%} of the flush"


def test_traced_flush_annotations_share_the_profiler_clock(monkeypatch):
    """Every span of a flush opens a ``cubegraph.<name>`` profiler
    annotation on the flushing thread, while the exported tree keeps the
    bare names."""
    import jax
    rng = np.random.default_rng(14)
    store = _two_tenant_store(rng)
    svc = CubeGraphService(store)
    for r in _requests(rng, 0):
        assert svc.submit(r) is None
    svc.flush()
    opened = []

    class Recorder:
        def __init__(self, name, **kw):
            opened.append((name, threading.get_ident()))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    _, trace = _traced_flush(svc, _requests(rng, 100))
    spans = list(trace.root.walk())
    assert [name for name, _ in opened] == \
        ["cubegraph." + s.name for s in spans]
    assert {tid for _, tid in opened} == {threading.get_ident()}
    assert trace.to_dict()["name"] == "serve.flush"


def test_compile_in_traced_flush_names_its_step():
    """A shape first seen inside a traced flush (a new k) compiles: the
    compile lands on the step that caused it, on the flush root, and in
    the process-wide ``xla_compiles_total``."""
    rng = np.random.default_rng(15)
    store = _two_tenant_store(rng)
    svc = CubeGraphService(store)
    for r in _requests(rng, 0):
        assert svc.submit(r) is None
    svc.flush()
    counters = store.manager.stats()["obs"]["metrics"]["counters"]
    before = counters["xla_compiles_total"]
    _, trace = _traced_flush(svc, _requests(rng, 100, k=(17,)))
    root = trace.root
    assert root.attrs["compiles"] > 0
    assert root.attrs["compiles"] == sum(
        s.attrs.get("compiles", 0) for s in root.walk() if s is not root)
    steps = {s.name for s in root.walk()
             if s is not root and s.attrs.get("compiles", 0)}
    # the delta scan and the grouped program each compile for the new k;
    # the host build, the wait and the copy compile nothing
    assert steps == {"delta_scan", "kernel_launch"}
    # compiles land on the innermost open span only
    assert not any(s.attrs.get("compiles", 0) for s in root.walk()
                   if s.name in ("serve.query_grouped",
                                 "sealed_scan_grouped",
                                 "bucket_dispatch_grouped"))
    after = store.manager.stats()["obs"]["metrics"]["counters"]
    assert after["xla_compiles_total"] - before >= root.attrs["compiles"]
    # the same requests again: every shape is warm
    _, trace = _traced_flush(svc, _requests(rng, 200, k=(17,)))
    assert trace.root.attrs["compiles"] == 0
    assert trace.root.attrs["cache_loads"] == 0


# ---------------------------------------------------------------------------
# Snapshot / restore
# ---------------------------------------------------------------------------
def test_multi_tenant_snapshot_roundtrip(tmp_path):
    rng = np.random.default_rng(10)
    store = MultiTenantStore(D, M, stream_cfg=_stream_cfg())
    store.create_collection("a", quota_points=500)
    store.create_collection("b")
    store.insert("a", _docs(rng, 120))
    b_gids = store.insert("b", _docs(rng, 80, base=10_000))
    store.delete("b", b_gids[:10])
    store.maintenance()
    q = rng.standard_normal((2, D)).astype(np.float32)
    before = store.retrieve("a", q, _filters()[0], k=8)

    store.snapshot_to(str(tmp_path / "snap"))
    restored = MultiTenantStore.restore(str(tmp_path / "snap"), D, M)
    after = restored.retrieve("a", q, _filters()[0], k=8)
    assert np.array_equal(before.gids, after.gids)
    assert np.array_equal(before.dists, after.dists)
    assert [[d.doc_id for d in row] for row in before.docs] == \
        [[d.doc_id for d in row] for row in after.docs]
    assert restored.collection("a").quota_points == 500
    assert restored.collection("b").n_live == 70
    # tid allocation resumes past restored collections
    assert restored.create_collection("c").tid == 3


# ---------------------------------------------------------------------------
# Async loop + traffic harness smoke
# ---------------------------------------------------------------------------
def test_async_loop_answers_polled_requests():
    rng = np.random.default_rng(11)
    store = _two_tenant_store(rng, n=80)
    svc = CubeGraphService(store)
    svc.start(interval_ms=1.0)
    try:
        q = rng.standard_normal(D).astype(np.float32)
        assert svc.submit(ServeRequest(req_id=0, tenant="a",
                                       query_emb=q, k=5)) is None
        deadline = 30.0
        import time
        t0 = time.monotonic()
        res = None
        while res is None and time.monotonic() - t0 < deadline:
            res = svc.take_result(0)
            if res is None:
                time.sleep(0.01)
    finally:
        svc.stop()
    assert res is not None and not isinstance(res, RetrievalFailure)
    solo = store.retrieve("a", q, None, k=5)
    assert np.array_equal(res.gids, solo.gids[0])
    assert np.array_equal(res.dists, solo.dists[0])


def test_workload_smoke_report_schema():
    """The geo-temporal harness's smoke configuration produces the full
    SLO report schema with the isolation check green — the same
    invocation CI runs via ``python -m repro.serving.workload --smoke``."""
    from repro.serving.workload import SLO_REPORT_KEYS, _smoke
    report = _smoke()
    assert all(key in report for key in SLO_REPORT_KEYS)
    assert report["isolation_ok"] and report["isolation_checks"] > 0
    assert report["n_requests"] > 0
    assert report["n_answered"] == report["n_requests"]
    assert report["recall_at_10"] >= 0.95
