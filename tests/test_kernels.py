"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.filters import (BallFilter, BoxFilter, ComposeFilter,
                                IntervalFilter)
from repro.core.workloads import (make_ball_filter, make_box_filter,
                                  make_compose_filter, make_dataset,
                                  make_polygon_filter, ground_truth)
from repro.kernels import filtered_topk, pairwise_dist
from repro.kernels import ref
from repro.kernels.ops import encode_filter


@pytest.mark.parametrize("bq,n,d", [(4, 64, 16), (16, 300, 48), (33, 513, 130),
                                    (1, 1000, 96), (128, 256, 128)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_distance_kernel_shapes(bq, n, d, metric):
    rng = np.random.default_rng(bq * 1000 + n + d)
    q = rng.normal(size=(bq, d)).astype(np.float32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    got = np.asarray(pairwise_dist(q, x, metric=metric))
    want = np.asarray(ref.pairwise_sq_l2(q, x) if metric == "l2"
                      else ref.pairwise_neg_ip(q, x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_distance_kernel_dtypes(dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(8, 64)), dtype)
    x = jnp.asarray(rng.normal(size=(128, 64)), dtype)
    got = np.asarray(pairwise_dist(q, x))
    want = np.asarray(ref.pairwise_sq_l2(q, x))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("mk,kind", [
    (make_box_filter, "box"),
    (make_ball_filter, "ball"),
    (make_compose_filter, "box_not_ball"),
])
@pytest.mark.parametrize("m", [2, 3])
def test_filter_encoding_matches_object(mk, kind, m):
    f = mk(m, 0.1, seed=11)
    enc = encode_filter(f, m)
    if enc is None:
        pytest.skip("no kernel encoding for this m (jnp fallback path)")
    got_kind, params = enc
    rng = np.random.default_rng(2)
    s = rng.uniform(0, 1, size=(2000, m)).astype(np.float32)
    want = np.asarray(f.contains(jnp.asarray(s)))
    sp = np.full((2000, 128), 0.0, np.float32)
    sp[:, :m] = s
    got = np.asarray(ref.filter_mask_ref(jnp.asarray(sp), got_kind,
                                         jnp.asarray(params)))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("bq,n,d,k", [(4, 200, 32, 5), (16, 1000, 64, 10),
                                      (7, 333, 100, 20), (32, 2048, 128, 50)])
def test_filtered_topk_vs_ground_truth(bq, n, d, k):
    x, s = make_dataset(n, d, 2, seed=n)
    rng = np.random.default_rng(1)
    q = x[rng.integers(0, n, bq)] + 0.01
    f = make_box_filter(2, 0.1, seed=n)
    ids, dd = filtered_topk(q, x, s, f, k)
    gt_i, gt_d = ground_truth(x, s, q, f, k)
    for a, b in zip(np.asarray(ids), gt_i):
        assert set(a[a >= 0]) == set(b[b >= 0])
    np.testing.assert_allclose(
        np.where(np.isfinite(np.asarray(dd)), np.asarray(dd), 0),
        np.where(np.isfinite(gt_d), gt_d, 0), rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("mkf", [make_ball_filter, make_compose_filter,
                                 make_polygon_filter])
def test_filtered_topk_filter_shapes(mkf):
    """Complex filter shapes (kernel path where encodable, jnp fallback else)."""
    x, s = make_dataset(800, 32, 2, seed=3)
    q = x[:8] + 0.01
    f = mkf(2, 0.1, seed=4)
    ids, dd = filtered_topk(q, x, s, f, 10)
    gt_i, _ = ground_truth(x, s, q, f, 10)
    for a, b in zip(np.asarray(ids), gt_i):
        assert set(a[a >= 0]) == set(b[b >= 0])


def test_interval_halfopen_encoding():
    """[t0, inf) encodes as 'box' with NO synthetic upper bound: the packed
    hi row keeps its pass-all default and only padding rows (meta=+2e30)
    fail it."""
    f = IntervalFilter(dim=2, lo=jnp.float32(0.4))
    enc = encode_filter(f, 3)
    assert enc is not None
    kind, params = enc
    assert kind == "box"
    assert params[0, 2] == np.float32(0.4)
    assert np.all(params[1, :] >= 1e30)          # upper edge untouched
    x, s = make_dataset(600, 16, 3, seed=9)
    ids, _ = filtered_topk(x[:6], x, s, f, 10)
    gt_i, _ = ground_truth(x, s, x[:6], f, 10)
    for a, b in zip(np.asarray(ids), gt_i):
        assert set(a[a >= 0]) == set(b[b >= 0])
    assert np.all(s[np.asarray(ids)[np.asarray(ids) >= 0], 2] >= 0.4)


@pytest.mark.parametrize("lo,hi", [(0.3, None), (None, 0.6), (0.2, 0.7)])
def test_interval_and_box_composition(lo, hi):
    """box AND interval folds into one packed box (open ends stay open)."""
    box = BoxFilter(lo=jnp.asarray([0.1, 0.1, 0.0]),
                    hi=jnp.asarray([0.9, 0.9, 1.0]))
    iv = IntervalFilter(dim=2,
                        lo=None if lo is None else jnp.float32(lo),
                        hi=None if hi is None else jnp.float32(hi))
    f = ComposeFilter(box, iv, "and")
    enc = encode_filter(f, 3)
    assert enc is not None and enc[0] == "box"
    x, s = make_dataset(600, 16, 3, seed=10)
    ids, _ = filtered_topk(x[:6], x, s, f, 10)
    gt_i, _ = ground_truth(x, s, x[:6], f, 10)
    for a, b in zip(np.asarray(ids), gt_i):
        assert set(a[a >= 0]) == set(b[b >= 0])


def test_ball_and_interval_box_ball_kind():
    """ball AND interval uses the fused 'box_ball' kind (no jnp fallback)."""
    ball = BallFilter(center=jnp.asarray([0.5, 0.5]), radius=jnp.float32(0.35))
    iv = IntervalFilter(dim=2, lo=jnp.float32(0.25), hi=jnp.float32(0.9))
    f = ComposeFilter(ball, iv, "and")
    enc = encode_filter(f, 3)
    assert enc is not None and enc[0] == "box_ball"
    x, s = make_dataset(800, 24, 3, seed=11)
    ids, _ = filtered_topk(x[:6], x, s, f, 10)
    gt_i, _ = ground_truth(x, s, x[:6], f, 10)
    for a, b in zip(np.asarray(ids), gt_i):
        assert set(a[a >= 0]) == set(b[b >= 0])
    # the ref oracle agrees with the object predicate for this kind
    rng = np.random.default_rng(12)
    sp = np.full((1500, 128), 2e30, np.float32)
    sp[:, :3] = rng.uniform(0, 1, size=(1500, 3))
    want = np.asarray(f.contains(jnp.asarray(sp[:, :3])))
    got = np.asarray(ref.filter_mask_ref(jnp.asarray(sp[:, :3]), enc[0],
                                         jnp.asarray(enc[1])))
    assert np.array_equal(got, want)


def test_filtered_topk_empty_filter():
    """A filter matching nothing returns all -1 / inf."""
    x, s = make_dataset(200, 16, 2, seed=5)
    f = BoxFilter(lo=jnp.asarray([5.0, 5.0]), hi=jnp.asarray([6.0, 6.0]))
    ids, dd = filtered_topk(x[:4], x, s, f, 10)
    assert np.all(np.asarray(ids) == -1)
    assert np.all(~np.isfinite(np.asarray(dd)))


def test_filtered_topk_sorted():
    x, s = make_dataset(500, 24, 3, seed=6)
    f = make_box_filter(3, 0.2, seed=7)
    _, dd = filtered_topk(x[:8], x, s, f, 16)
    dd = np.asarray(dd)
    finite = np.where(np.isfinite(dd), dd, 1e30)
    assert np.all(np.diff(finite, axis=1) >= -1e-5)


@pytest.mark.parametrize("bkv,g,smax,hd,ts", [
    (4, 8, 512, 128, 128), (2, 16, 1024, 128, 256), (8, 8, 256, 256, 128)])
def test_flash_decode_vs_oracle(bkv, g, smax, hd, ts):
    from repro.kernels.flash_decode import flash_decode_kernel_call
    from repro.kernels.ref import flash_decode_ref
    rng = np.random.default_rng(bkv * 100 + g)
    q = rng.normal(size=(bkv, g, hd)).astype(np.float32)
    k = rng.normal(size=(bkv, smax, hd)).astype(np.float32)
    v = rng.normal(size=(bkv, smax, hd)).astype(np.float32)
    lengths = rng.integers(1, smax, size=bkv).astype(np.int32)
    got = np.asarray(flash_decode_kernel_call(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(lengths), ts=ts))
    want = np.asarray(flash_decode_ref(q, k, v, jnp.asarray(lengths)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_decode_bf16():
    from repro.kernels.flash_decode import flash_decode_kernel_call
    from repro.kernels.ref import flash_decode_ref
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.bfloat16)
    lengths = jnp.asarray([100, 255], jnp.int32)
    got = np.asarray(flash_decode_kernel_call(q, k, v, lengths, ts=128),
                     np.float32)
    want = np.asarray(flash_decode_ref(q, k, v, lengths), np.float32)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("kind,c", [("box", 24), ("box_ball", 24),
                                    ("box_ball", 600)])
def test_beam_step_kernel_matches_jnp_twin(kind, c):
    """The graph path's fused beam-step kernel (what a TPU runs per hop)
    and its jnp twin (what the CPU runs) score a gathered tile alike —
    also past one candidate tile (c > 512, padded to two)."""
    from repro.core.filters import BallFilter, ComposeFilter
    from repro.kernels.graph_topk import (_score_candidates_jnp,
                                          beam_step_scores)
    rng = np.random.default_rng(13)
    q = rng.normal(size=(16, 128)).astype(np.float32)
    cx = rng.normal(size=(16, c, 128)).astype(np.float32)
    cm = np.zeros((16, c, 128), np.float32)      # live rows: 0-padded
    cm[:, :, :3] = rng.uniform(0, 1, size=(16, c, 3))
    cm[:, -2:, :] = 2e30                          # padding rows fail
    f = BoxFilter(lo=jnp.asarray([0.1, 0.1, 0.2]),
                  hi=jnp.asarray([0.9, 0.8, 0.9]))
    if kind == "box_ball":
        f = ComposeFilter(f, BallFilter(center=jnp.asarray([0.5, 0.5]),
                                        radius=jnp.float32(0.3)), "and")
    got_kind, params = encode_filter(f, 3)
    assert got_kind == kind
    d_k, ok_k = beam_step_scores(jnp.asarray(q), jnp.asarray(cx),
                                 jnp.asarray(cm), jnp.asarray(params),
                                 kind=kind)
    d_j, ok_j = _score_candidates_jnp(jnp.asarray(q), jnp.asarray(cx),
                                      jnp.asarray(cm), jnp.asarray(params),
                                      kind=kind, metric="l2")
    assert np.array_equal(np.asarray(ok_k), np.asarray(ok_j))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_j),
                               rtol=1e-5, atol=1e-3)
    assert np.asarray(ok_k).any() and not np.asarray(ok_k).all()
