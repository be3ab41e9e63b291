"""Reduction of a profiler trace to device busy and idle time, kernel
time and the breakdown the result line carries.

Input is a ``jax.profiler.ProfileData`` (``from_file`` on the
``.xplane.pb`` a traced run writes, ``from_text_proto`` in the tests).
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per device operation.  The window is the host span named
``bench.window``.  Busy time is the union of device-op intervals inside
it, averaged over the device planes that ran anything; an idle gap is
attributed to the innermost ``bench.*`` host span covering its midpoint
(``bench.idle`` where none does).
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
HOST_PREFIX = "bench."
# device-op names of the retrieval kernels: the custom call takes the name
# of the jitted function around each pallas_call, behind one "vmap_" per
# batching axis (shards, groups)
KERNELS = {
    "fused_topk": re.compile(r"^%(vmap_)*jit_filtered_topk_kernel_call"),
    "quant_topk": re.compile(r"^%(vmap_)*jit_quant_filtered_topk_kernel_call"),
}
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["kinds"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table['kinds'])}")
    return table["kinds"][device_kind]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    kernel_s: Dict[str, float]
    kernel_calls: Dict[str, int]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    n_devices: int


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _host_spans(pd) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def summarize(pd, top: int = 10) -> Optional[TraceSummary]:
    """The trace's summary, or None when it holds no ``bench.window``."""
    spans = _host_spans(pd)
    win = [(a, b) for name, a, b in spans if name == WINDOW]
    if not win:
        return None
    w0, w1 = win[0]
    inner = [(name, a, b) for name, a, b in spans if name != WINDOW]
    kernel_s = {k: 0.0 for k in KERNELS}
    kernel_calls = {k: 0 for k in KERNELS}
    op_s: Dict[str, float] = {}
    busy_ns = 0.0
    gaps: Dict[str, float] = {}
    n_dev = 0
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        iv = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = max(ev.start_ns, w0)
                b = min(ev.start_ns + ev.duration_ns, w1)
                if b <= a:
                    continue
                iv.append((a, b))
                op_s[ev.name] = op_s.get(ev.name, 0.0) + (b - a) / 1e9
                for key, pat in KERNELS.items():
                    if pat.search(ev.name):
                        kernel_s[key] += (b - a) / 1e9
                        kernel_calls[key] += 1
        if not iv:
            continue
        n_dev += 1
        merged = _union(iv)
        busy_ns += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2.0
            cover = [(sb - sa, name) for name, sa, sb in inner
                     if sa <= mid <= sb]
            who = min(cover)[1] if cover else "bench.idle"
            gaps[who] = gaps.get(who, 0.0) + (b - a) / 1e9
    if n_dev:
        busy_ns /= n_dev
        gaps = {k: v / n_dev for k, v in gaps.items()}
    # an op's name is its HLO text; keep its head (name, shapes, target)
    ops = sorted(((k[:200], v) for k, v in op_s.items()),
                 key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / 1e9,
        kernel_s=kernel_s, kernel_calls=kernel_calls,
        device_ops=[[k, v] for k, v in ops],
        idle_gaps=[[k, v] for k, v in
                   sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        n_devices=n_dev)


def load(path: str):
    """``ProfileData`` of the ``.xplane.pb`` under a trace directory."""
    import glob

    import jax
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return jax.profiler.ProfileData.from_file(max(found,
                                                  key=os.path.getmtime))


def inventory(pd, top: int = 25) -> dict:
    """Planes, lines and the commonest event names: a look at a trace by
    hand before reducing it."""
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            names: Dict[str, int] = {}
            sample = {}
            for ev in line.events:
                names[ev.name] = names.get(ev.name, 0) + 1
                if ev.name not in sample:
                    sample[ev.name] = [(k, str(v)[:120]) for k, v in ev.stats]
            common = sorted(names.items(), key=lambda kv: -kv[1])[:top]
            lines[line.name] = [(n, c, sample[n]) for n, c in common]
        out[plane.name] = lines
    return out
