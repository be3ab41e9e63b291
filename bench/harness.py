"""One benchmark run of one cell: set-up, measured window, check.

``run_cell`` generates the cell's data from the seed, loads it through
the program's public path, warms up the shapes the traffic can reach,
drives the service with the traffic's loop for the window, and then holds
a seeded sample of the answers served in the window against the plain
reference.  It returns the result line's dict and prints its diagnostics
and the compared numbers (last) on standard error.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, Optional

import numpy as np

from . import catalog, check, deploy, kernel_costs, loops, trace_reduce
from .data import (STREAM_REQUESTS, STREAM_SAMPLE, STREAM_WARMUP,
                   make_corpus, rng)
from .layers import LayerContext

SAMPLE = 400          # answers per run held against the reference


T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:8.2f}s] {msg}", file=sys.stderr,
          flush=True)


class GcTimer:
    """Times the interpreter's full (generation 2) collections while
    ``on``."""

    def __init__(self):
        self.on = False
        self.pauses = []
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on or info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, _secs, **_kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **_kw):
        if self.on and name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def rehearsal_config(cfg: dict) -> dict:
    """The configuration cut to the CPU rehearsal's size."""
    out = dict(cfg)
    out.update(cfg["rehearsal"])
    return out


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; inf counts as the largest value."""
    v = sorted(values)
    return float(v[max(math.ceil(q * len(v)), 1) - 1]) if v else math.inf


class Deployment:
    """A cell's data, store, service and request streams."""

    def __init__(self, cell: catalog.Cell, seed: int, rehearsal: bool):
        cfg = rehearsal_config(cell.config) if rehearsal else cell.config
        self.cfg, self.traffic, self.seed = cfg, cell.traffic, seed
        self.gen = catalog.generator(cell)
        self.ref = catalog.reference(cell)
        self.n_tenants = int(cfg["tenants"])
        t0 = time.perf_counter()
        self.corpus = make_corpus(cfg, seed)
        log(f"data: {len(self.corpus.x)} x {self.corpus.x.shape[1]}, "
            f"{time.perf_counter() - t0:.3f}s")
        self.store = deploy.load(cfg, self.corpus, log=log)
        self.svc = deploy.make_service(cfg, self.store)

    def requests(self, count: int, stream: int):
        return self.gen.requests(self.traffic, self.corpus, self.n_tenants,
                                 count, self.seed, stream=stream,
                                 normalize=bool(self.cfg["normalize"]))

    def maker(self, reqs, first_id: int):
        from repro.serving.service import ServeRequest

        def make(rid: int):
            i = (rid - first_id) % len(reqs)
            return ServeRequest(
                req_id=rid, tenant=deploy.tenant_name(int(reqs.tenant[i])),
                query_emb=reqs.q[i],
                filt=self.gen.to_program_filter(reqs.specs[i]), k=reqs.k)
        return make

    def drive(self, rate: Optional[float], seconds: float, reqs,
              first_id: int, seed_offset: int = 0) -> loops.Record:
        """The traffic's loop for ``seconds`` (open at ``rate``)."""
        make = self.maker(reqs, first_id)
        if self.traffic["loop"] == "open":
            due = self.gen.arrival_offsets(rate, seconds,
                                           self.seed + seed_offset)
            return loops.run_open(self.svc, make, due, seconds, first_id)
        return loops.run_closed(self.svc, make, int(self.traffic["clients"]),
                                seconds, first_id)

    def stop_service(self) -> None:
        self.svc.stop()
        for th in threading.enumerate():
            if th.name == "cubegraph-serving.loop":
                th.join(timeout=30.0)


def device_info(chips: int, rehearsal: bool) -> Optional[dict]:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu" and not rehearsal:
        log(f"error: no TPU (JAX sees {info['platform']})")
        return None
    if info["count"] < chips and not rehearsal:
        log(f"error: the cell needs {chips} chips, {info['count']} present")
        return None
    return info


def memory_stat(key: str) -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get(key, 0))


def outcomes(rec: loops.Record):
    """Split the window's requests: (ok ids, degraded, refused, failed,
    unanswered)."""
    from repro.serving.service import ServeResult
    ok, degraded, failed = [], [], []
    for rid, res in rec.result.items():
        if isinstance(res, ServeResult):
            (degraded if res.degraded else ok).append(rid)
        else:
            failed.append(rid)
    unanswered = [rid for rid in rec.due
                  if rid not in rec.result and rid not in rec.refused]
    return ok, degraded, list(rec.refused), failed, unanswered


def served_rows(res) -> tuple:
    """An answer as corpus rows: the documents' ids (one per gid >= 0)."""
    gids = np.asarray(res.gids)
    n = int(np.sum(gids >= 0))
    rows = np.asarray([d.doc_id for d in res.docs], np.int64)
    return rows, np.asarray(res.dists, np.float32)[:len(rows)], n


def judge(dep: Deployment, rec: loops.Record, reqs, first_id: int,
          window_ids, wrong: int):
    """Numbers of a seeded sample of the answers served in the window."""
    from repro.serving.service import ServeResult
    oracle = dep.ref.Oracle(dep.corpus)
    answered = [rid for rid in window_ids
                if isinstance(rec.result.get(rid), ServeResult)]
    gen = rng(dep.seed, STREAM_SAMPLE)
    pick = gen.choice(len(answered), size=min(SAMPLE, len(answered)),
                      replace=False) if answered else []
    answers, idx = {}, []
    for j in pick:
        rid = answered[int(j)]
        rows, dists, n_gids = served_rows(rec.result[rid])
        if n_gids != len(rows):
            wrong += 1
            continue
        i = (rid - first_id) % len(reqs)
        if i in answers:
            continue
        answers[i] = (rows, dists)
        idx.append(i)
    t0 = time.perf_counter()
    numbers = check.compare(answers, reqs, np.asarray(idx, np.int64),
                            oracle, wrong=wrong)
    log(f"check: {len(idx)} answers against the reference, "
        f"{time.perf_counter() - t0:.3f}s")
    return numbers


def kernel_min_seconds(dep: Deployment, spans, buckets, peaks) -> Dict:
    quant = dep.cfg["quantize"] is not None
    key = "quant_topk" if quant else "fused_topk"
    peak_ops = peaks["int8_ops_per_s"] if quant else peaks["bf16_flops_per_s"]
    d, m = int(dep.cfg["dim"]), 3 + 1      # user metadata + tenant column
    total = 0.0
    for sp in spans.of("bench.query_grouped"):
        for q, n in kernel_costs.dispatches(sp.groups, buckets,
                                            shared=not quant):
            ops, nbytes = kernel_costs.scan_cost(q, n, d, m,
                                                 1 if quant else 4)
            total += kernel_costs.min_seconds(ops, nbytes, peak_ops,
                                              peaks["hbm_bytes_per_s"])
    return {key: total}


def run_cell(cell: catalog.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, rehearsal: bool = False) -> Optional[dict]:
    info = device_info(cell.chips, rehearsal)
    if info is None:
        return None
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    counter.on = True
    dep = Deployment(cell, seed, rehearsal)
    cfg, traffic = dep.cfg, dep.traffic
    rate = traffic.get("rate_qps")

    # -- warm-up: every batch shape, then the traffic itself -------------
    t0 = time.perf_counter()
    wreqs = dep.requests(4096, STREAM_WARMUP)
    deploy.warm_shapes(cfg, dep.store, dep.svc, wreqs,
                       dep.gen.to_program_filter, dep.corpus,
                       traffic["warm_groups"],
                       int(traffic["warm_batch"]) if not rehearsal else 0,
                       log=log, counter=counter)
    dep.svc.start()
    warm_s = float(traffic["warmup_seconds"]) * (0.25 if rehearsal else 1.0)
    wrec = dep.drive(rate, warm_s, wreqs, first_id=1 << 40, seed_offset=1)
    log(f"warm-up traffic: {len(wrec.done)} answers in {warm_s:.1f}s "
        f"(+{time.perf_counter() - t0:.3f}s of warm-up in all)")

    buckets, view = deploy.bucket_layout(dep.store)
    n_live = int(dep.store.manager.n_live)
    log(f"pack: {len(view.buckets)} buckets, {n_live} live points, block "
        f"bytes {deploy.block_bytes(view)} "
        f"({deploy.block_bytes(view) / max(n_live, 1):.1f} B/point)")
    del view
    gc.collect()
    gc_timer = GcTimer()
    in_use = memory_stat("bytes_in_use")
    log(f"device bytes in use after warm-up: {in_use}")

    count = int(min(65536, max(1024, (rate or 2000) * seconds * 1.1)))
    reqs = dep.requests(count, STREAM_REQUESTS)
    spans = tmp = None
    if trace:
        import jax
        from . import spans as spans_mod
        spans = spans_mod.install(dep.svc, dep.store)
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tmp, profiler_options=_profile_options())
        spans.on = True
        annot = jax.profiler.TraceAnnotation("bench.window")
        annot.__enter__()
    log(f"set-up: {counter.compiles - counter.cache_hits} compiles, "
        f"{counter.cache_hits} persistent-cache loads")
    counter.compiles = counter.cache_hits = 0
    setup_s = time.perf_counter() - t_start
    gc_timer.on = True
    rec = dep.drive(rate, seconds, reqs, first_id=0)
    counter.on = gc_timer.on = False
    if trace:
        annot.__exit__(None, None, None)
        spans.on = False
        jax.profiler.stop_trace()
    dep.stop_service()
    peak = memory_stat("peak_bytes_in_use")

    # -- what the window did ---------------------------------------------
    if traffic["loop"] == "open":
        window_ids = list(rec.due)
    else:
        window_ids = [rid for rid, t in rec.sent.items() if t < rec.t_end]
    ok, degraded, refused, failed, unanswered = outcomes(rec)
    okset = set(ok)
    late = rec.late_ms()
    log(f"window: {len(window_ids)} requests, {len(ok)} ok, "
        f"{len(degraded)} degraded, {len(refused)} refused, "
        f"{len(failed)} failed, {len(unanswered)} unanswered; generator "
        f"late p50 {percentile(late, 0.5):.3f} ms p99 "
        f"{percentile(late, 0.99):.3f} ms; compiles in window "
        f"{counter.compiles - counter.cache_hits}, cache loads "
        f"{counter.cache_hits}; full collections {len(gc_timer.pauses)}, "
        f"longest {max(gc_timer.pauses, default=0.0) * 1e3:.1f} ms")

    metrics = {}
    if not trace:
        if traffic["loop"] == "open":
            bad = set(failed)
            lat = [(rec.done[r] - rec.due[r]) * 1e3
                   if r in rec.done and r not in rec.refused
                   and r not in bad else math.inf for r in window_ids]
            values = {"p50_ms": percentile(lat, 0.5),
                      "p95_ms": percentile(lat, 0.95)}
        else:
            n_done = sum(1 for r in ok if rec.done[r] < rec.t_end)
            values = {"qps": n_done / seconds}
        values["setup_s"] = setup_s
        values["device_bytes_per_point"] = in_use / max(n_live, 1)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    summary = None
    if trace:
        pd = trace_reduce.load(tmp)
        summary = trace_reduce.summarize(pd)
        shutil.rmtree(tmp, ignore_errors=True)
        peaks = (trace_reduce.peaks_for(info["kind"])
                 if not rehearsal else None)
        ctx = LayerContext(
            spans=spans, trace=summary,
            kernel_min_s=(kernel_min_seconds(dep, spans, buckets, peaks)
                          if peaks else {}))
        for m in cell.per_layer:
            v = catalog.reader(cell, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if summary is not None:
            log(f"trace: window {summary.window_s:.3f}s busy "
                f"{summary.busy_s:.6f}s kernels {summary.kernel_s} calls "
                f"{summary.kernel_calls}")
        sizes = [g[0] for sp in spans.of("bench.query_grouped")
                 for g in sp.groups]
        log(f"grouping: {sum(sizes)} requests in {len(sizes)} groups, "
            f"{sum(n for n in sizes if n > 1)} of them in groups of 2 or "
            f"more")

    # -- correctness -----------------------------------------------------
    in_window = set(window_ids)
    wrong = (sum(1 for r in failed if r in in_window)
             + sum(1 for r in degraded if r in in_window)
             + len([r for r in unanswered if r in in_window]))
    del dep.svc
    numbers = judge(dep, rec, reqs, 0, [r for r in window_ids if r in okset],
                    wrong)
    log("numbers: " + json.dumps(numbers))
    correct, checks = check.verdict(numbers, cell.config["limits"])

    result = {
        "correct": bool(correct),
        "attempted": len(window_ids),
        "failed": len(window_ids) - len([r for r in window_ids
                                         if r in okset]),
        "metrics": metrics,
        "device": dict(info, memory_peak_bytes=peak),
    }
    if trace and summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return result


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run_sweep(cell: catalog.Cell, seed: int, seconds: float, rates,
              rehearsal: bool = False) -> int:
    """One set-up, then for each rate a warm-up of half of ``seconds``
    and a measured step of ``seconds`` of the open loop: the p50 latency
    of the step's first and last quarter (a backlog that grows shows as
    the second well above the first), p95, refusals, the drain time after
    the step and the compiles inside it."""
    if device_info(cell.chips, rehearsal) is None:
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    counter = CompileCounter()
    dep = Deployment(cell, seed, rehearsal)
    deploy.warm_shapes(dep.cfg, dep.store, dep.svc,
                       dep.requests(4096, STREAM_WARMUP),
                       dep.gen.to_program_filter, dep.corpus,
                       dep.traffic["warm_groups"],
                       int(dep.traffic["warm_batch"]) if not rehearsal
                       else 0, log=log)
    dep.svc.start()
    reqs = dep.requests(65536, STREAM_REQUESTS)
    first = 0
    for rate in rates:
        dep.drive(rate, seconds / 2, reqs, first_id=first, seed_offset=7)
        first += int(rate * seconds) + 1000
        counter.on = True
        counter.compiles = counter.cache_hits = 0
        due = dep.gen.arrival_offsets(rate, seconds, seed + first)
        rec = loops.run_open(dep.svc, dep.maker(reqs, first), due, seconds,
                             first)
        counter.on = False
        drain_s = rec.done and max(rec.done.values()) - rec.t_end
        first += len(due)
        ids = sorted(rec.due)
        q = max(len(ids) // 4, 1)

        def lat(r):
            return ((rec.done[r] - rec.due[r]) * 1e3 if r in rec.done
                    else math.inf)
        row = {"rate": rate, "sent": len(ids), "refused": len(rec.refused),
               "p50_first_ms": percentile([lat(r) for r in ids[:q]], 0.5),
               "p50_last_ms": percentile([lat(r) for r in ids[-q:]], 0.5),
               "p50_ms": percentile([lat(r) for r in ids], 0.5),
               "p95_ms": percentile([lat(r) for r in ids], 0.95),
               "drain_s": drain_s,
               "late_p99_ms": percentile(rec.late_ms(), 0.99),
               "compiles": counter.compiles - counter.cache_hits,
               "cache_loads": counter.cache_hits}
        log("sweep " + json.dumps(row))
    dep.stop_service()
    return 0


def run_control(cell: catalog.Cell, seed: int, seconds: float,
                name: str = "", rehearsal: bool = False) -> int:
    """The numbers of a stand-in on the requests a window of ``seconds``
    would send: the reference put in the program's place, one precision
    below the configuration's (its control, the default) or with a fault
    of the reference's ``FAULTS`` planted."""
    if device_info(cell.chips, rehearsal) is None:
        return 2
    cfg = rehearsal_config(cell.config) if rehearsal else cell.config
    gen = catalog.generator(cell)
    ref = catalog.reference(cell)
    name = name or cfg["control"]
    stand_in = {**ref.CONTROLS, **ref.FAULTS}[name]
    corpus = make_corpus(cfg, seed)
    rate = cell.traffic.get("rate_qps") or 2000
    count = int(min(65536, max(1024, rate * seconds * 1.1)))
    reqs = gen.requests(cell.traffic, corpus, int(cfg["tenants"]), count,
                        seed, stream=STREAM_REQUESTS,
                        normalize=bool(cfg["normalize"]))
    idx = rng(seed, STREAM_SAMPLE).choice(count, size=min(SAMPLE, count),
                                          replace=False)
    oracle = ref.Oracle(corpus)
    t0 = time.perf_counter()
    answers = stand_in(oracle, reqs, idx, cfg)
    numbers = check.compare(answers, reqs, idx, oracle)
    log(f"control {name}: {time.perf_counter() - t0:.3f}s")
    log("control " + json.dumps({"seed": seed, "cell": cell.name,
                                 "stand_in": name, **numbers}))
    return 0
