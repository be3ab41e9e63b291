"""Whole runs on the CPU at the rehearsal size: each cell reaches its end
and prints no result line off the chip, and a run whose timed path is
broken underneath comes out not correct."""
import dataclasses
import os
import subprocess
import sys

import pytest

from bench import catalog

ROOT = catalog.ROOT
CELLS = [w["name"] for w in catalog.load_benchmark()["workloads"]]

@pytest.mark.parametrize("workload", CELLS)
def test_cpu_rehearsal_reaches_its_end_with_no_result_line(workload,
                                                           tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0",
         "--cpu-rehearsal"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "cpu rehearsal reached its end" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]

def test_run_off_the_chip_fails_with_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert p.stdout.strip() == ""

def _shift_first_two(host_topk):
    def broken(g, d, k):
        og, od = host_topk(g, d, k)
        og = og.copy()
        og[:, [0, 1]] = og[:, [1, 0]]
        return og, od
    return broken

def _kth_skipped(host_topk):
    def broken(g, d, k):
        og, od = host_topk(g, d, k + 1)
        keep = [j for j in range(k + 1) if j != k - 1]
        return og[:, keep], od[:, keep]
    return broken

def _half_batch(query_grouped):
    def broken(groups, **kw):
        h = (len(groups) + 1) // 2
        groups = [dataclasses.replace(g, queries=groups[i - h].queries)
                  if i >= h else g for i, g in enumerate(groups)]
        return query_grouped(groups, **kw)
    return broken

def _unscoped(store):
    return lambda tenant, filt: store._widen(filt)

FAULTS = {
    # an answer altered where it is produced (the host merge)
    "answer_altered": ("sift1m-geo4.closed", "merge"),
    # the host merge keeps the (k+1)-th row in place of the k-th, with its
    # true distance: rows valid, distances right, not the nearest
    "kth_skipped": ("sift1m-geo4.closed", "select"),
    # half of each batch answered, the other half served the answers to
    # the first half's queries (under its own filters and tenants)
    "half_batch": ("sift1m-geo4.closed", "batch"),
    # tenant scoping dropped from every query
    "unscoped": ("sift1m-geo4.closed", "scope"),
}

@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_comes_out_not_correct(fault, monkeypatch):
    import repro.compile_cache
    import repro.distributed.segment_shards as shards
    from bench import deploy, harness
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "off")
    workload, where = FAULTS[fault]
    if where == "merge":
        monkeypatch.setattr(shards, "host_topk",
                            _shift_first_two(shards.host_topk))
    if where == "select":
        monkeypatch.setattr(shards, "host_topk",
                            _kth_skipped(shards.host_topk))
    load = deploy.load

    def load_broken(cfg, corpus, log=print):
        store = load(cfg, corpus, log=log)
        if where == "batch":
            store.manager.query_grouped = _half_batch(
                store.manager.query_grouped)
        if where == "scope":
            store.scoped_filter = _unscoped(store)
        return store
    monkeypatch.setattr(deploy, "load", load_broken)
    cell = catalog.cell(workload)
    result = harness.run_cell(cell, seed=31, seconds=1.0, trace=False,
                              t_start=0.0, rehearsal=True)
    assert result is not None and result["correct"] is False, \
        result["checks"]
    if where == "select":
        miss = result["checks"]["miss"]
        assert miss["value"] > miss["limit"], result["checks"]
