"""The fused fp32 kernel with one packed filter per query row.

``filtered_topk_kernel_call`` selects its predicate by the shape of its
params: ``[4, mpad]`` is one filter shared by every query row, ``[bq, 4,
mpad]`` one filter per row, tested over the ``m`` real metadata dims.
The claims held here are equalities: a per-row call whose rows all carry
the same filter returns the shared call's bits, and a per-row call whose
rows carry different filters returns, row by row, the bits of a shared
call with that row's filter -- with metadata placed exactly on box edges
and on ball boundaries, where one rounding of the predicate would flip
a point in or out.
"""
import numpy as np
import pytest

from repro.kernels.filtered_topk import FILTER_KINDS, filtered_topk_kernel_call

D, M, MPAD = 128, 3, 128
N, N_REAL = 512, 500        # two candidate tiles; the last 12 rows padding
TQ, TN, BQ = 8, 256, 24     # three query tiles
GRID = 16.0                 # metadata on multiples of 1/16 for half the points


def _data(seed):
    """Queries, vectors and metadata padded as the kernel takes them: half
    the points on a 1/16 grid (exactly on the filters' box edges and ball
    boundaries below), half uniform; padding rows carry ``PAD_META``."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(BQ, D)).astype(np.float32)
    x = np.zeros((N, D), np.float32)
    x[:N_REAL] = rng.normal(size=(N_REAL, D))
    s = np.zeros((N, MPAD), np.float32)
    meta = rng.uniform(size=(N_REAL, M))
    on_grid = rng.random(N_REAL) < 0.5
    meta[on_grid] = np.round(meta[on_grid] * GRID) / GRID
    s[:N_REAL, :M] = meta
    s[N_REAL:] = 2e30
    return q, x, s


def _filter(rng, kind):
    """One packed ``[4, MPAD]`` filter of ``kind``, on the 1/16 grid: box
    bounds are grid values, the ball's center is one and its r^2 the exact
    squared distance to another grid point, over 2 or 3 dims."""
    p = np.zeros((4, MPAD), np.float32)
    p[0, :] = -1e30
    p[1, :] = 1e30
    p[3, 0] = 1e30
    if kind in ("box", "box_ball", "box_not_ball"):
        lo = rng.integers(0, 8, size=M) / GRID
        hi = rng.integers(8, 17, size=M) / GRID
        p[0, :M], p[1, :M] = lo, hi
    if kind in ("ball", "box_ball", "box_not_ball"):
        nd = int(rng.integers(2, M + 1))
        c = rng.integers(4, 13, size=nd) / GRID
        off = rng.integers(1, 5, size=nd) / GRID
        p[2, :nd] = c
        p[3, 0] = np.float32(np.sum(off.astype(np.float32) ** 2))
        p[3, 1] = nd
    return p


def _call(q, x, s, params, kind, kpad, m=None):
    dd, ids = filtered_topk_kernel_call(q, x, s, params, kind=kind,
                                        kpad=kpad, tq=TQ, tn=TN, m=m)
    return np.asarray(dd), np.asarray(ids)


def _same_bits(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        assert np.array_equal(g.view(np.int32), w.view(np.int32))


@pytest.mark.parametrize("kpad", [16, 64])
@pytest.mark.parametrize("kind", FILTER_KINDS)
def test_per_row_params_of_one_filter_equal_shared_call(kind, kpad):
    """Every row given the same filter: the per-row call returns the
    shared-params call's distances and ids, bit for bit."""
    q, x, s = _data(1)
    p = _filter(np.random.default_rng(2), kind)
    want = _call(q, x, s, p, kind, kpad)
    got = _call(q, x, s, np.broadcast_to(p, (BQ, 4, MPAD)), kind, kpad, m=M)
    _same_bits(got, want)
    assert (want[1] >= 0).any()


@pytest.mark.parametrize("kpad", [16, 64])
@pytest.mark.parametrize("kind", FILTER_KINDS)
def test_per_row_params_equal_one_shared_call_per_row(kind, kpad):
    """Rows with different filters: row r of the per-row call is row r of
    a shared call with row r's filter, bit for bit, across query tiles."""
    q, x, s = _data(3)
    rng = np.random.default_rng(4)
    params = np.stack([_filter(rng, kind) for _ in range(BQ)])
    got_d, got_i = _call(q, x, s, params, kind, kpad, m=M)
    for r in range(BQ):
        want_d, want_i = _call(q, x, s, params[r], kind, kpad)
        _same_bits((got_d[r], got_i[r]), (want_d[r], want_i[r]))


def test_boundary_points_are_exercised():
    """The data above does put points exactly on box edges and on ball
    boundaries (so the equalities test the inclusive compares)."""
    _, _, s = _data(3)
    rng = np.random.default_rng(4)
    on_edge = on_sphere = 0
    for _ in range(BQ):
        p = _filter(rng, "box_ball")
        meta = s[:N_REAL, :M]
        on_edge += int(np.sum((meta == p[0, :M]) | (meta == p[1, :M])))
        nd = int(p[3, 1])
        diff = meta[:, :nd] - p[2, :nd]
        on_sphere += int(np.sum(np.sum(diff * diff, axis=1) == p[3, 0]))
    assert on_edge > 0 and on_sphere > 0
