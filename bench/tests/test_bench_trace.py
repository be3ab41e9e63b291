"""The trace reduction on a small hand-written trace with known answers."""
import os

import jax
import pytest

from bench import kernel_costs, layers, spans, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.textproto")


@pytest.fixture(scope="module")
def summary():
    with open(DATA) as f:
        pd = jax.profiler.ProfileData.from_text_proto(f.read())
    return trace_reduce.summarize(pd)


def test_busy_window_and_kernels(summary):
    # union of device ops clipped to the 10 us window: 0.5 + 1.3 + 0.5
    # + 0.25 us
    assert summary.window_s == pytest.approx(10e-6, abs=1e-15)
    assert summary.busy_s == pytest.approx(2.55e-6, abs=1e-15)
    assert summary.n_devices == 1
    assert summary.kernel_s["fused_topk"] == pytest.approx(1.5e-6, abs=1e-15)
    assert summary.kernel_s["quant_topk"] == pytest.approx(0.25e-6,
                                                           abs=1e-15)
    assert summary.kernel_calls == {"fused_topk": 2, "quant_topk": 1}
    names = [n.split(" =")[0] for n, _ in summary.device_ops]
    assert names == ["%vmap_vmap_jit_filtered_topk_kernel_call___.1",
                     "fusion.1", "copy.2",
                     "%vmap_jit_quant_filtered_topk_kernel_call__.1"]


def test_idle_gaps_by_host_span(summary):
    got = dict(summary.idle_gaps)
    assert got["bench.flush"] == pytest.approx(5.5e-6, abs=1e-15)
    assert got["bench.query_grouped"] == pytest.approx(0.2e-6, abs=1e-15)
    assert got["bench.idle"] == pytest.approx(1.75e-6, abs=1e-15)


def test_shares_from_the_trace(summary):
    ctx = layers.LayerContext(spans=spans.SpanLog(), trace=summary,
                              kernel_min_s={"fused_topk": 0.75e-6})
    assert layers.roofline_pct(ctx, "fused_topk") == pytest.approx(50.0)
    # a kernel with no work counted reads as absent, never as 0
    assert layers.roofline_pct(ctx, "quant_topk") is None
    assert layers.device_idle_pct(ctx) == pytest.approx(74.5)
    empty = layers.LayerContext(spans=spans.SpanLog(),   trace=None, kernel_min_s={})
    assert layers.roofline_pct(empty, "fused_topk") is None
    assert layers.device_idle_pct(empty) is None


def test_unknown_device_kind_raises():
    assert trace_reduce.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace_reduce.peaks_for("TPU v99")


def test_kernel_costs_count_unpadded_work():
    ops, nbytes = kernel_costs.scan_cost(3, 1000, 128, 4, 4)
    assert ops == 2 * 3 * 1000 * 128
    assert nbytes == 1000 * (4 * 128 + 16) + 3 * 4 * 128
    buckets = [{"points": 1000, "spans": [(0.0, 0.4), (0.5, 0.9)]}]
    groups = ((2, "box", 0.1, 0.2), (3, "ball", 0.3, 0.6),
              (4, "box", 0.95, 1.0))
    # the third group's window misses every segment of the bucket
    assert kernel_costs.dispatches(groups, buckets, shared=True) == [
        (3, 1000), (2, 1000)]
    assert kernel_costs.dispatches(groups, buckets, shared=False) == [
        (2, 1000), (3, 1000)]
    assert kernel_costs.min_seconds(2e12, 1.0, 1e12, 1e9) == 2.0
