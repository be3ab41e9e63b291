"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout's
root names the cell's configuration, traffic and metrics; each lives in
a file of its own under ``bench/`` --
``configs/<config>.json`` (which names its plain reference,
``references/<reference>.py``), ``traffic/<traffic>.json`` (which names
its generator, ``generators/<generator>.py``) and
``layer_metrics/<metric>.py`` (a reader with ``read(ctx)``).  Adding one
is adding files and an entry; no code here names any of them."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str = ROOT


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind: str, name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", kind, f"{name}.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, name) and m["moves"] in moved]
    return Cell(name=name, config=load_json("configs", w["config"], root),
                traffic=load_json("traffic", w["traffic"], root),
                chips=int(w["chips"]), end_to_end=e2e, per_layer=layer,
                root=root)


def _module(root: str, kind: str, name: str):
    path = os.path.join(root, "bench", kind, f"{name}.py")
    mod_name = f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def generator(c: Cell):
    """The traffic's generator module, ``generators/<generator>.py``."""
    return _module(c.root, "generators", c.traffic["generator"])


def reference(c: Cell):
    """The configuration's plain reference, ``references/<name>.py``."""
    return _module(c.root, "references", c.config["reference"])


def reader(c: Cell, metric: str):
    """The ``read(ctx)`` of ``layer_metrics/<metric>.py``."""
    return _module(c.root, "layer_metrics", metric).read
