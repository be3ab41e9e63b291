"""The int8 deployment's cell, ``deep10m-geo4-int8-rerank.closed``: its
stand-ins fail its limits at the cell's size, a rehearsal of the cell
comes out correct with nothing compiled in its window, and the int8
kernel's roofline reader reads the trace."""
import os
import re

import jax
import numpy as np
import pytest

from bench import catalog, check, layers, spans, trace_reduce
from bench.data import STREAM_REQUESTS, STREAM_SAMPLE, make_corpus, rng
from bench.harness import SAMPLE

CELL = "deep10m-geo4-int8-rerank.closed"
DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.textproto")


@pytest.mark.parametrize("stand_in,fails", [
    ("int4", ("miss", "gap")),       # the codes one precision below int8
    ("bf16x3", ("dist_err",)),       # the rerank one precision below fp32
])
def test_stand_ins_fail_the_cells_limits(stand_in, fails):
    """At the cell's size, on the requests a window would send and the
    sample a run compares, on three seeds: each stand-in exceeds one of
    the limits it is there to separate."""
    cell = catalog.cell(CELL)
    cfg = cell.config
    gen, ref = catalog.generator(cell), catalog.reference(cell)
    run = ref.CONTROLS[stand_in]
    for seed in (2718281831, 3141592661, 1732050811):
        corpus = make_corpus(cfg, seed)
        reqs = gen.requests(cell.traffic, corpus, int(cfg["tenants"]), 4096,
                            seed, stream=STREAM_REQUESTS,
                            normalize=bool(cfg["normalize"]))
        idx = rng(seed, STREAM_SAMPLE).choice(len(reqs), SAMPLE,
                                              replace=False)
        oracle = ref.Oracle(corpus)
        numbers = check.compare(run(oracle, reqs, idx, cfg), reqs, idx,
                                oracle)
        over = [n for n in fails if numbers[n] > cfg["limits"][n]]
        assert over, (seed, numbers)


def test_rehearsal_of_the_cell_is_correct_with_no_compile_in_window(
        monkeypatch, capsys):
    import repro.compile_cache
    from bench import harness
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "off")
    result = harness.run_cell(catalog.cell(CELL), seed=2147483659,
                              seconds=1.0, trace=False, t_start=0.0,
                              rehearsal=True)
    assert result is not None and result["correct"] is True, \
        result["checks"]
    assert set(result["checks"]) == {"wrong", "gap", "dist_err", "miss"}
    window = re.findall(r"compiles in window (\d+), cache loads (\d+)",
                        capsys.readouterr().err)
    assert window == [("0", "0")]


def test_quant_topk_roofline_reader():
    read = catalog.reader(catalog.cell(CELL), "quant_topk_roofline")
    empty = layers.LayerContext(spans=spans.SpanLog(), trace=None,
                                kernel_min_s={"quant_topk": 1e-6})
    assert read(empty) is None
    with open(DATA) as f:
        summary = trace_reduce.summarize(
            jax.profiler.ProfileData.from_text_proto(f.read()))
    ctx = layers.LayerContext(spans=spans.SpanLog(), trace=summary,
                              kernel_min_s={"quant_topk": 0.05e-6})
    # 0.05 us of work against the fixture's 0.25 us of int8 kernel time
    assert read(ctx) == pytest.approx(20.0)
    assert np.isfinite(read(ctx))
