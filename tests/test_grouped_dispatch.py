"""The grouped bucket dispatch finishes each filter class on the device.

``pack_search_blocks_grouped`` runs one program per (bucket, filter
class): the class's query rows stacked in one query axis, each row with
its group's filter, through one fused kernel call over the bucket's
block, then each row's shard merge with its group's ``k`` and temporal
mask.  The claims held here are equalities: every group's candidate
blocks are bit-for-bit what a solo ``pack_search_blocks`` call returns
for it, whatever the mix of k, filter kinds, query rows, temporal
windows and deadlines; a second call with the same padded class rows and
other group counts, k values or filters traces nothing; and the manager
counts the groups the on-device finish answered and the query rows its
class programs scanned.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import BallFilter, BoxFilter, CubeGraphConfig
from repro.core.workloads import make_polygon_filter
from repro.distributed.segment_shards import (SegmentShardSource,
                                              build_bucketed_pack,
                                              make_shard_mesh,
                                              pack_search_blocks,
                                              pack_search_blocks_grouped)
from repro.kernels import dispatch_trace_count
from repro.streaming import GroupQuery, SegmentManager, StreamConfig

D, M = 32, 3
INF = np.inf
# (segment points, time span): two segments per capacity bucket, so a
# group's temporal window can keep some rows of a bucket and drop others
SEGMENTS = [(200, (0.0, 1.0)), (180, (2.0, 3.0)),
            (700, (4.0, 5.0)), (650, (6.0, 7.0))]


def _pack(mesh=None):
    rng = np.random.default_rng(5)
    sources, gid0 = [], 0
    for sid, (n, (t0, t1)) in enumerate(SEGMENTS):
        s = np.concatenate([rng.uniform(size=(n, 2)),
                            np.linspace(t0, t1, n)[:, None]], axis=1)
        sources.append(SegmentShardSource(
            sid, rng.normal(size=(n, D)).astype(np.float32), s,
            np.arange(gid0, gid0 + n, dtype=np.int64), t0, t1))
        gid0 += n
    view = build_bucketed_pack(sources, n_shards=2, mesh=mesh).view()
    assert len(view.buckets) == 2
    return view


@pytest.fixture(scope="module")
def view():
    return _pack()


def _box(seed, lo=0.1, hi=0.9):
    rng = np.random.default_rng(seed)
    a = rng.uniform(0.0, lo, size=2)
    b = rng.uniform(hi, 1.0, size=2)
    return BoxFilter(lo=np.float32([a[0], a[1], -1e9]),
                     hi=np.float32([b[0], b[1], 1e9]))


def _ball(seed):
    rng = np.random.default_rng(seed)
    return BallFilter(center=rng.uniform(0.3, 0.7, size=2).astype(np.float32),
                      radius=np.float32(0.45))


def _q(seed, b=2):
    return np.random.default_rng(seed).normal(size=(b, D)).astype(np.float32)


class _ExpiresAfter:
    """A deadline that expires on its ``n + 1``-th check."""

    def __init__(self, n):
        self.left = n

    def expired(self):
        self.left -= 1
        return self.left < 0


# each case: groups (queries, filter, k, t_lo, t_hi) and their deadlines
CASES = {
    "one_class_mixed_k": [(_q(1), _box(1), 9, -INF, INF),
                          (_q(2), _box(2), 12, -INF, INF),
                          (_q(3), _box(3), 16, -INF, INF)],
    "two_classes": [(_q(4), _box(4), 10, -INF, INF),
                    (_q(5), _ball(5), 10, -INF, INF),
                    (_q(6), _box(6), 7, -INF, INF),
                    (_q(7), _ball(7), 10, -INF, INF)],
    "rows_1_3_65": [(_q(8, 1), _box(8), 10, -INF, INF),
                    (_q(9, 3), _box(9), 10, -INF, INF),
                    (_q(10, 65), _box(10), 10, -INF, INF)],
    # the second group's window drops the second bucket whole, the third
    # keeps one row pair of the first bucket
    "temporal_masks": [(_q(11), _box(11), 10, -INF, INF),
                       (_q(12), _box(12), 10, 0.0, 1.5),
                       (_q(13), _box(13), 10, 2.5, 6.5)],
    "class_of_one": [(_q(14), _box(14), 10, -INF, INF),
                     (_q(15), _box(15), 10, -INF, INF),
                     (_q(16), _ball(16), 10, -INF, INF)],
    "deadline_after_first_bucket": [(_q(17), _box(17), 10, -INF, INF),
                                    (_q(18), _ball(18), 10, -INF, INF),
                                    (_q(19), _box(19), 10, -INF, INF)],
    # k past the first bucket's capacity (256): whole shard lists merge
    "k_past_capacity": [(_q(20), _box(20), 300, -INF, INF),
                        (_q(21), _box(21), 260, -INF, INF)],
    "no_kernel_encoding": [(_q(22), _box(22), 10, -INF, INF),
                           (_q(23), make_polygon_filter(M, 0.6, seed=23),
                            10, -INF, INF),
                           (_q(24), _box(24), 10, -INF, INF)],
    # 65 one-query groups of one class: their rows cross a 64-row tile
    "rows_cross_tile": [(_q(100 + i, 1), _box(100 + i), 10, -INF, INF)
                        for i in range(65)],
    "mixed_rows_1_2_5": [(_q(25, 1), _ball(25), 10, -INF, INF),
                         (_q(26, 2), _ball(26), 10, -INF, INF),
                         (_q(27, 5), _ball(27), 10, -INF, INF),
                         (_q(28, 2), _ball(28), 10, -INF, INF)],
    # one-query groups sharing a tile: the second drops the first bucket's
    # second row pair and the whole second bucket, the third keeps only
    # the rows the second drops
    "rows_drop_other_shards": [(_q(29, 1), _box(29), 10, -INF, INF),
                               (_q(30, 1), _box(30), 10, 0.0, 1.5),
                               (_q(31, 1), _box(31), 10, 1.5, 6.5),
                               (_q(32, 1), _box(32), 10, 5.5, 6.5)],
}


def _solo(view, groups, expire_after):
    out = []
    for gi, (q, f, k, t_lo, t_hi) in enumerate(groups):
        v = view
        if gi in expire_after:
            v = dataclasses.replace(view,
                                    buckets=view.buckets[:expire_after[gi]])
        out.append(pack_search_blocks(v, q, f, k, t_lo=t_lo, t_hi=t_hi))
    return out


def _assert_blocks_equal(got, want):
    assert len(got) == len(want)
    for g_blocks, w_blocks in zip(got, want):
        assert len(g_blocks) == len(w_blocks)
        for (gg, gd), (wg, wd) in zip(g_blocks, w_blocks):
            assert gg.dtype == wg.dtype and gd.dtype == wd.dtype
            assert np.array_equal(gg, wg)
            assert np.array_equal(gd, wd)


@pytest.mark.parametrize("case", list(CASES))
def test_grouped_blocks_bit_equal_solo(view, case):
    """Every group's candidate blocks, bucket by bucket, are bit-for-bit
    those of its solo dispatch (a group dropped by its deadline keeps the
    blocks of the buckets before it expired)."""
    groups = CASES[case]
    deadlines, expire_after, expired = None, {}, []
    if case == "deadline_after_first_bucket":
        deadlines = [None, _ExpiresAfter(1), None]
        expire_after = {1: 1}
    got = pack_search_blocks_grouped(
        view, groups, deadlines=deadlines,
        on_expired=lambda gi, n: expired.append((gi, n)))
    assert expired == [(1, 1)] if deadlines else expired == []
    _assert_blocks_equal(got, _solo(view, groups, expire_after))
    if case == "temporal_masks":
        assert [len(b) for b in got] == [2, 1, 2]
    if case == "rows_drop_other_shards":
        assert [len(b) for b in got] == [2, 1, 2, 1]


def test_grouped_blocks_bit_equal_solo_on_mesh():
    """The same on a mesh-placed pack: the kernel runs per device under
    ``shard_map`` and the merge follows in the same program."""
    view = _pack(mesh=make_shard_mesh())
    groups = CASES["two_classes"] + CASES["temporal_masks"]
    _assert_blocks_equal(pack_search_blocks_grouped(view, groups),
                         _solo(view, groups, {}))


def test_second_call_with_other_k_and_filters_traces_nothing(view):
    """A class's program is keyed on its shapes (kind, kpad, padded rows,
    bucket geometry), never on the groups' k or filter values: the same
    shapes with other k and filters trace nothing."""
    first = [(_q(30), _box(30), 9, -INF, INF),
             (_q(31), _box(31), 16, -INF, INF),
             (_q(32), _ball(32), 10, -INF, INF)]
    second = [(_q(33, 5), _box(33, 0.3, 0.6), 12, -INF, INF),
              (_q(34, 7), _box(34, 0.2, 0.7), 11, -INF, INF),
              (_q(35, 1), _ball(35), 13, -INF, INF)]
    pack_search_blocks_grouped(view, first)
    t0 = dispatch_trace_count()
    got = pack_search_blocks_grouped(view, second)
    assert dispatch_trace_count() == t0
    _assert_blocks_equal(got, _solo(view, second, {}))


def test_group_count_traces_nothing(view):
    """The group count is no part of a class's program: 4 and then 32
    one-query groups of one class fill one 64-row tile and run the same
    program, so the second call traces nothing."""
    four = [(_q(50 + i, 1), _box(50 + i), 10, -INF, INF) for i in range(4)]
    many = [(_q(60 + i, 1), _box(60 + i), 10, -INF, INF) for i in range(32)]
    pack_search_blocks_grouped(view, four)
    t0 = dispatch_trace_count()
    got = pack_search_blocks_grouped(view, many)
    assert dispatch_trace_count() == t0
    _assert_blocks_equal(got, _solo(view, many, {}))


def _manager(**kw):
    cfg = StreamConfig(time_dim=2, seal_max_points=120, n_shards=2,
                       index_cfg=CubeGraphConfig(n_layers=2, m_intra=8,
                                                 m_cross=4), **kw)
    mgr = SegmentManager(D, M, cfg)
    rng = np.random.default_rng(3)
    for i in range(3):
        s = rng.uniform(size=(120, M))
        s[:, 2] = i + np.linspace(0.0, 0.5, 120)
        mgr.ingest(rng.normal(size=(120, D)).astype(np.float32), s)
        mgr.seal()
    return mgr


def _merged(mgr):
    return mgr.stats()["obs"]["metrics"]["counters"].get(
        "grouped_device_merge_groups_total", 0)


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_device_merge_counter(quantize):
    """``grouped_device_merge_groups_total`` rises by the groups a flush
    finishes on the device — every group of the shared fp32 path — and
    stays put on a quantized pack and on the solo path."""
    mgr = _manager(quantize=quantize)
    groups = [GroupQuery(_q(40 + i), _box(40 + i), k=5) for i in range(3)]
    groups.append(GroupQuery(_q(43), _ball(43), k=8))
    mgr.query(_q(44), _box(44), k=5)                      # solo path
    assert _merged(mgr) == 0
    mgr.query_grouped(groups)
    assert _merged(mgr) == (len(groups) if quantize is None else 0)
    mgr.query(_q(45), _box(45), k=5)
    assert _merged(mgr) == (len(groups) if quantize is None else 0)


def _counters(mgr):
    return mgr.stats()["obs"]["metrics"]["counters"]


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_kernel_row_counters(quantize):
    """``grouped_kernel_rows_total`` counts the real query rows the class
    programs scanned and ``grouped_kernel_row_slots_total`` the padded
    rows their tiles computed: 5 one-query groups of one class fill 5 of
    each dispatched bucket's 64 rows.  A quantized pack takes the
    per-group path and counts neither."""
    mgr = _manager(quantize=quantize)
    mgr.query_grouped([GroupQuery(_q(70 + i, 1), _box(70 + i), k=5)
                       for i in range(5)])
    c = _counters(mgr)
    rows = c.get("grouped_kernel_rows_total", 0)
    slots = c.get("grouped_kernel_row_slots_total", 0)
    if quantize is not None:
        assert rows == slots == 0
        return
    assert rows > 0 and rows % 5 == 0
    assert rows / slots == 5 / 64


def _lists(case, rows=3, b=4, w=8, cap=40):
    """Shard-local candidate lists as the kernel returns them: per shard
    and query row, ascending distances with misses (-1, +inf) last."""
    rng = np.random.default_rng(7)
    if case == "signed_zeros":
        dd = np.where(rng.random((rows, b, w)) < 0.5, -0.0, 0.0)
    else:          # small integers (negative too, as -ip): ties everywhere
        dd = np.sort(rng.integers(-3, 4, size=(rows, b, w)), axis=2)
    dd = dd.astype(np.float32)
    ids = rng.integers(0, cap, size=(rows, b, w)).astype(np.int32)
    active = np.ones(rows, bool)
    if case == "misses_and_inactive":
        miss = np.arange(w) >= rng.integers(0, w, size=(rows, b, 1))
        ids = np.where(miss, -1, ids)
        dd = np.where(miss, np.inf, dd).astype(np.float32)
        active[1] = False
    gids = rng.permutation(rows * cap).reshape(rows, cap).astype(np.int32)
    return ids, dd, active, gids


@pytest.mark.parametrize("case", ["ties", "signed_zeros",
                                  "misses_and_inactive"])
@pytest.mark.parametrize("k", [1, 5, 24])
def test_sort_free_merge_equals_top_k_merge(case, k):
    """The grouped program's merge takes k rounds of least-pair extraction
    where the solo merge sorts (``lax.top_k``); on ties, -0.0 against
    +0.0, misses and dropped shards both return the same bits."""
    import jax.numpy as jnp
    from repro.distributed.segment_shards import _merge_shard_topk
    from repro.kernels.ops import _merge_lists
    ids, dd, active, gids = _lists(case)
    want = _merge_shard_topk(jnp.asarray(ids), jnp.asarray(dd),
                             jnp.asarray(gids), jnp.asarray(active), k)
    got = _merge_lists(jnp.asarray(ids), jnp.asarray(dd),
                       jnp.asarray((ids >= 0) & active[:, None, None]),
                       jnp.asarray(gids), k)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype
        assert np.array_equal(g.view(np.int32), w.view(np.int32))
