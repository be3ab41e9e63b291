"""Benchmark driver: one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV, writes
experiments/bench_results.json, and distills the streaming sections into
the top-level BENCH_streaming.json perf-trajectory summary.

Section registration is declarative (:data:`SECTIONS`) and *loud*: every
module is imported individually through :func:`load_sections`, so one
module that raises on import no longer silently removes every other
section from the run (the old single grouped ``from . import (...)``
failure mode) — import/entry-point failures are reported per section and
the driver exits non-zero.  ``tests/test_planner.py`` smoke-checks that
every registered module imports and exposes its entry point.
"""
from __future__ import annotations

import importlib
import json
import os
import sys
import time

STREAMING_SUMMARY_PATH = os.path.join(os.path.dirname(__file__), "..",
                                      "BENCH_streaming.json")

# (section name, module under benchmarks/, entry-point attribute) — the
# single source of truth for what the driver runs, in run order.
SECTIONS = (
    ("exp1_search_efficiency", "bench_search", "run"),
    ("exp2_multidim", "bench_multidim", "run"),
    ("exp3_filter_shapes", "bench_filter_shapes", "run"),
    ("exp4_index_cost", "bench_index_cost", "run"),
    ("exp5_dynamic_updates", "bench_updates", "run"),
    ("exp6_merge_count", "bench_merge_count", "run"),
    ("exp7_scalability", "bench_scalability", "run"),
    ("exp8_distributions", "bench_distributions", "run"),
    ("exp9_streaming", "bench_streaming", "run"),
    ("exp10_sharded_mesh", "bench_streaming", "run_sharded"),
    ("exp11_persistence", "bench_persistence", "run"),
    ("exp12_pack_maintenance", "bench_streaming", "run_pack_maintenance"),
    ("exp13_quantized_scan", "bench_quant", "run"),
    ("exp14_observed_stats", "bench_obs", "run"),
    ("exp15_read_path_planner", "bench_planner", "run"),
    ("exp16_tiered_storage", "bench_tiering", "run"),
    ("exp17_resilience", "bench_resilience", "run"),
    ("exp18_serving", "bench_serving", "run"),
    ("a5_aspect_ratio", "bench_aspect_ratio", "run"),
    ("a6_merge_strategy", "bench_merge_strategy", "run"),
    ("kernels", "bench_kernels", "run"),
)


def load_sections():
    """Import every registered module and resolve its entry point.

    Returns ``(loaded, errors)`` where ``loaded`` is ``[(name, fn), ...]``
    in registration order and ``errors`` is ``[(name, exc), ...]`` for
    sections whose module failed to import or lacks the attribute —
    each failure costs only its own section, never the whole run.
    """
    loaded, errors = [], []
    for name, mod_name, attr in SECTIONS:
        try:
            mod = importlib.import_module(f".{mod_name}",
                                          package=__package__)
            loaded.append((name, getattr(mod, attr)))
        except Exception as e:  # noqa: BLE001 — reported + non-zero exit
            errors.append((name, e))
    return loaded, errors


def flush_streaming_summary(results_path: str) -> str:
    """Re-derive ``BENCH_streaming.json`` (median latency + pack bytes per
    streaming experiment) from the merged results file, so the summary
    always reflects every recorded section — including ones not re-run in
    this invocation."""
    from .common import streaming_summary
    with open(results_path) as f:
        results = json.load(f)
    summary = {
        "source": "experiments/bench_results.json",
        "generated_by": "benchmarks/run.py",
        "sections": streaming_summary(results),
    }
    with open(STREAMING_SUMMARY_PATH, "w") as f:
        json.dump(summary, f, indent=1)
        f.write("\n")
    return STREAMING_SUMMARY_PATH


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    from .common import flush_results

    enable_compile_cache()

    sections, errors = load_sections()
    for name, e in errors:
        print(f"# SECTION LOAD FAILED {name}: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
    only = sys.argv[1] if len(sys.argv) > 1 else None
    failed = [name for name, _ in errors if not only or only in name]
    print("name,us_per_call,derived")
    for name, fn in sections:
        if only and only not in name:
            continue
        t0 = time.time()
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — keep the suite going
            print(f"{name},0,ERROR={type(e).__name__}:{e}")
            failed.append(name)
        print(f"# section {name} took {time.time()-t0:.1f}s", flush=True)
    path = flush_results()
    print(f"# results written to {path}")
    print(f"# streaming summary written to {flush_streaming_summary(path)}")
    if failed:
        print(f"# FAILED sections: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
