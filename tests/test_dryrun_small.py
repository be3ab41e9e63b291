"""Dry-run machinery on a small (2x4) mesh in a subprocess (8 host devices,
so the main test session keeps its single CPU device).

Covers: sharding rules produce valid NamedShardings for every arch family,
lower+compile succeeds for train and decode cells, collective parsing and
memory analysis run — the same code path as the 512-chip production sweep.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
jax.devices()   # lock the 8-device backend BEFORE importing repro.launch.dryrun
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh

from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.launch.dryrun import build_cell, compile_cell
from repro.distributed import hints

mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)

out = {}
for arch in %(archs)s:
    cfg = get_config(arch, smoke=True)
    for kind, shape in (("train", ShapeSpec("t", "train", 32, 8)),
                        ("decode", ShapeSpec("d", "decode", 64, 8))):
        rec = compile_cell(cfg, shape, mesh)
        out[f"{arch}/{kind}"] = {
            "collective_ops": rec["collectives"]["count"],
            "flops": rec["cost"]["flops"],
            "temp": rec["memory"]["temp_bytes"],
        }
print("RESULT " + json.dumps(out))
"""


@pytest.mark.parametrize("archs", [
    ["codeqwen1.5-7b", "qwen2-moe-a2.7b"],
    ["falcon-mamba-7b", "zamba2-2.7b"],
    ["whisper-medium", "internvl2-2b"],
])
def test_dryrun_small_mesh(archs):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"archs": repr(archs)}],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert len(out) == 2 * len(archs)
    for key, rec in out.items():
        assert rec["flops"] > 0, key
        assert rec["temp"] > 0, key
