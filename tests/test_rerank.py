"""The int8 path's fixed-shape fp32 rerank (``repro.quant.rerank``):
agreement with a plain float64 rerank of the same candidates, one traced
program whatever the candidates found, and the served int8 path held to
the benchmark's plain reference within the Deep10M configuration's
limits."""
import numpy as np
import pytest

from repro.core import BoxFilter, CubeGraphConfig
from repro.core.workloads import make_dataset
from repro.kernels import dispatch_trace_count, ops
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import QueryTrace
from repro.quant import rerank_exact
from repro.streaming import SegmentManager, StreamConfig

D = 24


def _store(n, seed, dup=0):
    """A lookup over ``n`` gaussian rows (the first ``dup`` repeated as the
    next ``dup``: exact distance ties) with every 7th row absent."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, D)).astype(np.float32)
    x[dup:2 * dup] = x[:dup]
    present = np.arange(n) % 7 != 3

    def lookup(gids):
        g = np.asarray(gids, np.int64)
        return np.where(present[g, None], x[g], 0.0), None, present[g]
    return x, present, lookup


def _numpy_rerank(q, cand, k, x, present):
    """Float64 rerank of each row's distinct present candidates, ordered by
    (distance, gid)."""
    out_g = np.full((len(q), k), -1, np.int64)
    out_d = np.full((len(q), k), np.inf)
    for i, row in enumerate(cand):
        g = np.unique(row[row >= 0])
        g = g[present[g]]
        d = np.sum((x[g].astype(np.float64) - q[i].astype(np.float64)) ** 2,
                   axis=1)
        top = np.lexsort((g, d))[:k]
        out_g[i, :len(top)], out_d[i, :len(top)] = g[top], d[top]
    return out_g, out_d


@pytest.mark.parametrize("b,s,k,dup", [(1, 40, 10, 0), (3, 80, 10, 6),
                                       (70, 33, 5, 4), (2, 6, 10, 0)])
def test_rerank_matches_a_float64_rerank_of_the_same_candidates(b, s, k,
                                                                 dup):
    """Same rows in the same order (distance, then gid: duplicated vectors
    tie exactly and go to the lower gid), repeated, padded and absent
    candidates dropped, fewer than k candidates padded with -1 / +inf.
    Distances within 8 float32 roundings of ||x||^2 + ||q||^2, the size of
    the terms the float32 expansion ||x||^2 - 2 x.q + ||q||^2 cancels."""
    n = 300
    x, present, lookup = _store(n, seed=b * 100 + s, dup=dup)
    rng = np.random.default_rng(s)
    q = rng.normal(size=(b, D)).astype(np.float32)
    cand = rng.integers(0, n, size=(b, s))
    cand[:, ::5] = -1                       # padding
    cand[:, 1] = cand[:, 2]                 # a gid repeated within a row
    if dup:
        cand[:, 3], cand[:, 4] = 0, dup     # an exact tie
        q[:, :] = x[0] + 0.01 * q
    g, d = rerank_exact(q, cand, k, lookup)
    rg, rd = _numpy_rerank(q, cand, k, x, present)
    assert g.shape == (b, k) and d.shape == (b, k)
    assert np.array_equal(g, rg)
    fin = np.isfinite(rd)
    assert np.array_equal(np.isfinite(d), fin)
    scale = (np.sum(x[np.maximum(rg, 0)].astype(np.float64) ** 2, axis=2)
             + np.sum(q.astype(np.float64) ** 2, axis=1)[:, None])
    tol = 8 * np.finfo(np.float32).eps * scale
    assert np.all(np.abs(d[fin] - rd[fin]) <= tol[fin])
    if dup:
        assert g[0, 0] == 0 and g[0, 1] == dup and d[0, 0] == d[0, 1]


def test_rerank_traces_one_program_across_union_sizes_and_deletes():
    """A quantized manager's queries, whose filters pass from none to many
    of the over-fetched candidates, and again after deleting rows: the
    rerank's program is traced once, and so are the int8 dispatches."""
    x, s = make_dataset(1600, D, 3, seed=5)
    s[:, 2] = np.arange(1600) / 1600
    mgr = SegmentManager(D, 3, StreamConfig(
        time_dim=2, seal_max_points=400, n_shards=2, quantize="int8",
        rerank_multiple=4, index_cfg=CubeGraphConfig(n_layers=2, m_intra=8,
                                                     m_cross=3)))
    mgr.ingest(x, s)
    mgr.seal()
    q = x[:3] + 0.01
    reg = mgr.obs.registry
    cands = reg.counter("rerank_candidates_total")

    def ask(side):
        lo = np.clip(s[0] - side, 0, 1)
        hi = np.clip(s[0] + side, 0, 1)
        before = cands.value
        gids, _ = mgr.query(q, BoxFilter(lo, hi), k=5)
        return cands.value - before, gids

    ask(1.0)                                  # traces the programs
    rerank = ops._rerank_dispatch(5, "l2")
    traces0, programs0 = dispatch_trace_count(), rerank._cache_size()
    sizes = set()
    for side in (0.02, 0.08, 0.12, 0.16, 0.22, 0.3, 1.0):
        n_cand, _ = ask(side)
        sizes.add(n_cand)
    mgr.delete(np.arange(0, 1600, 2))
    for side in (0.12, 1.0):
        n_cand, gids = ask(side)
        sizes.add(n_cand)
        assert np.all(gids[gids >= 0] % 2 == 1)
    assert len(sizes) >= 5, sizes
    assert dispatch_trace_count() == traces0
    assert rerank._cache_size() == programs0


def test_rerank_spans_and_counters():
    """``rerank_gather`` and ``rerank_score`` time the rerank's two steps;
    the counters say how many of the padded slots were real."""
    x, present, lookup = _store(200, seed=3)
    cand = np.arange(60).reshape(2, 30)
    reg = MetricsRegistry()
    trace = QueryTrace("rerank")
    with trace.span("rerank_fp32"):
        rerank_exact(x[:2], cand, 10, lookup, part_width=40, parts=2,
                     trace=trace, registry=reg)
    tree = trace.finish().root.children[0]
    assert [c.name for c in tree.children] == ["rerank_gather",
                                               "rerank_score"]
    real = int(present[:60].sum())
    assert tree.children[0].attrs["slots"] == 64 * 128
    assert tree.children[0].attrs["candidates"] == real
    counters = reg.snapshot()["counters"]
    assert counters["rerank_rows_total"] == 2
    assert counters["rerank_candidates_total"] == real
    assert counters["rerank_slots_total"] == 64 * 128
    assert counters["rerank_upload_bytes_total"] == 64 * (D + 128 * D) * 4 \
        + 64 * 128
    with pytest.raises(ValueError):
        rerank_exact(x[:2], np.arange(100).reshape(1, 100), 10, lookup,
                     part_width=10, parts=1)


def test_served_int8_path_holds_the_plain_reference():
    """Service -> query_grouped -> int8 scan -> fp32 rerank at the Deep10M
    configuration's rehearsal size, against the benchmark's float64
    reference within the configuration's limits; the flush's trace holds
    the rerank's two steps under ``rerank_fp32``."""
    from bench import catalog, check, deploy, harness
    from bench.data import STREAM_REQUESTS, make_corpus
    from repro.obs import TraceLog
    from repro.serving.service import ServeRequest

    cell = catalog.cell("deep10m-geo4-int8-rerank.closed")
    cfg = harness.rehearsal_config(cell.config)
    gen, ref = catalog.generator(cell), catalog.reference(cell)
    corpus = make_corpus(cfg, 2147483659)
    store = deploy.load(cfg, corpus, log=lambda *_: None)
    svc = deploy.make_service(cfg, store)
    svc.trace_log = TraceLog()
    reqs = gen.requests(cell.traffic, corpus, int(cfg["tenants"]), 64,
                        2147483659, stream=STREAM_REQUESTS, normalize=True)
    for i in range(64):
        assert svc.submit(ServeRequest(
            req_id=i, tenant=deploy.tenant_name(int(reqs.tenant[i])),
            query_emb=reqs.q[i], filt=gen.to_program_filter(reqs.specs[i]),
            k=reqs.k)) is None
    out = svc.flush()
    answers = {i: harness.served_rows(out[i])[:2] for i in range(64)}
    numbers = check.compare(answers, reqs, np.arange(64),
                            ref.Oracle(corpus))
    correct, checks = check.verdict(numbers, cell.config["limits"])
    assert correct, checks
    (flush,) = svc.trace_log.traces()
    reranks = [sp for sp in flush.root.walk() if sp.name == "rerank_fp32"]
    assert len(reranks) == 64
    for sp in reranks:
        assert [c.name for c in sp.children] == ["rerank_gather",
                                                 "rerank_score"]
    counters = store.manager.stats()["obs"]["metrics"]["counters"]
    assert counters["rerank_rows_total"] == 64
    assert 0 < counters["rerank_candidates_total"] \
        < counters["rerank_slots_total"] == 64 * 64 * 128
