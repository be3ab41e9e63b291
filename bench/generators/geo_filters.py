"""Geo-temporal filtered retrieval traffic, as the CubeGraph paper
(arXiv 2604.06616) evaluates it, driven open- or closed-loop by
``bench/loops.py``.

Every request draws its own filter: a box over (lon, lat, t) (Exp-1,
Fig. 5) or a radius filter, a ball over (lon, lat) conjoined with an
interval in t (Exp-3, Fig. 7), sized to pass a share ``ratio`` of
[0, 1]^3 with each side jittered by ``side_jitter`` and placed uniformly,
as ``repro.core.workloads`` draws them (``make_box_filter``,
``make_ball_filter``).  No two requests share a filter, so the service's
grouping by filter value merges nothing, and a flush's requests share
only the bucket reads of the grouped dispatch.

Request ``i`` takes kind ``kinds[i % len(kinds)]`` and ratio
``ratios[(i // len(kinds)) % len(ratios)]``: every seed sends the same
mix in the same order, and the seed moves the filters, the tenants and
the queries.  A request picks its tenant uniformly; its query is a point
of the tenant's own corpus plus Gaussian noise of ``query_noise`` times
the corpus's per-component RMS.

Filters are plain dicts (``kind`` "box" with ``lo``/``hi``, or "ball" with
``center``/``radius``/``t``): the reference evaluates them itself, and
:func:`to_program_filter` turns one into the program's filter objects.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from bench.data import STREAM_ARRIVALS, STREAM_REQUESTS, Corpus, rng


def _f32(v) -> list:
    return [float(np.float32(x)) for x in np.atleast_1d(v)]


def _box(gen, ratio: float, jitter: float) -> dict:
    side = ratio ** (1.0 / 3.0) * gen.uniform(1 - jitter, 1 + jitter, 3)
    side = np.clip(side, 1e-4, 0.999)
    lo = gen.uniform(0.0, 1.0 - side)
    return {"kind": "box", "lo": _f32(lo), "hi": _f32(lo + side)}


def _ball(gen, ratio: float, jitter: float) -> dict:
    t_side = ratio ** (1.0 / 3.0) * gen.uniform(1 - jitter, 1 + jitter)
    r = min(float(np.sqrt(ratio / t_side / np.pi)), 0.49)
    center = gen.uniform(r, 1.0 - r, 2)
    t_lo = gen.uniform(0.0, 1.0 - t_side)
    return {"kind": "ball", "center": _f32(center),
            "radius": float(np.float32(r)), "t": _f32([t_lo, t_lo + t_side])}


_MAKE = {"box": _box, "ball": _ball}


def to_program_filter(spec: dict):
    """The program's filter object for one filter dict."""
    from repro.core import BallFilter, BoxFilter, ComposeFilter, \
        IntervalFilter
    if spec["kind"] == "box":
        return BoxFilter(lo=np.float32(spec["lo"]), hi=np.float32(spec["hi"]))
    return ComposeFilter(
        BallFilter(center=np.float32(spec["center"]),
                   radius=np.float32(spec["radius"])),
        IntervalFilter(dim=2, lo=np.float32(spec["t"][0]),
                       hi=np.float32(spec["t"][1])), "and")


@dataclasses.dataclass
class Requests:
    """A seeded request stream: request ``i`` asks tenant ``tenant[i]``
    with filter ``specs[i]`` and query ``q[i]``."""

    tenant: np.ndarray   # [r] int
    specs: List[dict]    # [r] filter dicts
    q: np.ndarray        # [r, d] float32
    k: int

    def __len__(self) -> int:
        return len(self.tenant)


def requests(traffic: dict, corpus: Corpus, n_tenants: int, count: int,
             seed: int, stream: int = STREAM_REQUESTS,
             normalize: bool = False) -> Requests:
    """``count`` requests of the traffic's mix over ``corpus``."""
    gen = rng(seed, stream)
    kinds, ratios = list(traffic["kinds"]), list(traffic["ratios"])
    jitter = float(traffic["side_jitter"])
    specs = [_MAKE[kinds[i % len(kinds)]](
        gen, float(ratios[(i // len(kinds)) % len(ratios)]), jitter)
        for i in range(count)]
    tenant = gen.integers(0, n_tenants, count)
    rows = np.empty(count, np.int64)
    for t in range(n_tenants):
        sel = np.flatnonzero(tenant == t)
        rows[sel] = gen.choice(corpus.rows_of(t), size=len(sel))
    x = corpus.x
    rms = float(np.sqrt(np.mean(x[:4096].astype(np.float64) ** 2)))
    q = x[rows] + np.float32(traffic["query_noise"] * rms) \
        * gen.standard_normal((count, x.shape[1]), dtype=np.float32)
    if normalize:
        q /= np.linalg.norm(q, axis=1, keepdims=True)
    return Requests(tenant=tenant, specs=specs, q=q.astype(np.float32),
                    k=int(traffic["k"]))


def arrival_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of a Poisson stream of
    ``rate`` per second covering ``seconds``.  The gaps are the same
    stratified set of exponential quantiles for every seed, in a seeded
    order, so seeds differ in burst order and not in offered load."""
    m = int(np.ceil(rate * seconds * 1.05)) + 16
    gaps = -np.log1p(-(np.arange(m) + 0.5) / m) / rate
    gaps = rng(seed, STREAM_ARRIVALS).permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]
