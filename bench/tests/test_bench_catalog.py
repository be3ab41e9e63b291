"""BENCHMARK.json keeps to its contract, and every piece of a cell is
found by name -- a new configuration, traffic mix or per-layer metric
needs new files and entries only."""
import json
import os
import re
import shutil

import pytest

from bench import catalog, layers, spans

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = catalog.load_benchmark()


def test_benchmark_file_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(catalog.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.exists(
            os.path.join(catalog.ROOT, c["file"]))
        data = json.load(open(os.path.join(catalog.ROOT, c["file"])))
        assert data["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key) and not key.endswith(("_dim", "_rank"))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        for n in (w["name"], w["config"], w["traffic"]):
            assert NAME.match(n)
        names.add(w["name"])
        cell = catalog.cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", names)) <= names
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(
            catalog.ROOT, "bench", "layer_metrics", f"{m['name']}.py"))
        for w in m["workloads"]:
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", names)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_piece_of_a_cell_is_found_by_name(workload):
    cell = catalog.cell(workload)
    gen = catalog.generator(cell)
    ref = catalog.reference(cell)
    assert callable(gen.requests) and callable(ref.Oracle)
    assert cell.config["control"] in ref.CONTROLS
    for m in cell.per_layer:
        assert callable(catalog.reader(cell, m["name"]))


def test_new_files_and_entries_are_picked_up_with_no_edit(tmp_path):
    root = tmp_path
    for kind in ("configs", "traffic", "layer_metrics", "generators",
                 "references"):
        shutil.copytree(os.path.join(catalog.ROOT, "bench", kind),
                        root / "bench" / kind)
    bench = json.loads(json.dumps(BENCH))
    cfg = json.load(open(root / "bench/configs/sift1m-geo4.json"))
    cfg.update(name="sift1m-geo8", tenants=8)
    json.dump(cfg, open(root / "bench/configs/sift1m-geo8.json", "w"))
    tr = json.load(open(root / "bench/traffic/closed.json"))
    tr.update(clients=32, generator="geo_filters_copy")
    json.dump(tr, open(root / "bench/traffic/closed-32.json", "w"))
    shutil.copy(root / "bench/generators/geo_filters.py",
                root / "bench/generators/geo_filters_copy.py")
    (root / "bench/layer_metrics/flushes.py").write_text(
        "def read(ctx):\n    return len(ctx.spans.of('bench.flush'))\n")
    bench["configs"].append(dict(bench["configs"][0], name="sift1m-geo8",
                                 file="bench/configs/sift1m-geo8.json"))
    bench["workloads"].append({"name": "sift1m-geo8.closed-32",
                               "config": "sift1m-geo8",
                               "traffic": "closed-32", "chips": 1,
                               "why": "a new cell"})
    bench["per_layer"].append({"name": "flushes", "unit": "count",
                               "better": "lower",
                               "source": "program_span", "layer": "front end",
                               "moves": "qps",
                               "workloads": ["sift1m-geo8.closed-32"]})
    for m in bench["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("sift1m-geo8.closed-32")
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    cell = catalog.cell("sift1m-geo8.closed-32", root=str(root))
    assert cell.config["tenants"] == 8 and cell.traffic["clients"] == 32
    assert "flushes" in [m["name"] for m in cell.per_layer]
    assert catalog.generator(cell).__file__.endswith("geo_filters_copy.py")
    log = spans.SpanLog()
    log.spans.append(spans.Span("bench.flush", 0.0, 1.0))
    ctx = layers.LayerContext(spans=log, trace=None,
                              kernel_min_s={})
    assert catalog.reader(cell, "flushes")(ctx) == 1
