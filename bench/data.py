"""Corpus generation for the benchmark's deployments.

The vector generator is a copy of the Gaussian mixture in
``repro.core.workloads.make_dataset`` (32 clusters, within-cluster spread
0.3), kept here so that the data cannot change under a later change to the
program.

The metadata layout -- (lon, lat, t) in [0, 1]^3 in time order, and the
owning tenant of every point -- is drawn from the configuration's fixed
``layout_seed``, not from ``--seed``.  A sealed segment's CubeGraph build
compiles programs whose shapes follow the segment's cube layout, so a
layout drawn per seed compiles new programs in every run's set-up; with a
fixed layout every seed loads the same cube shapes, and ``--seed`` changes
the vectors, the filters, the queries and the arrival order.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# independent random streams drawn from one --seed
STREAM_VECTORS, STREAM_REQUESTS, STREAM_ARRIVALS, STREAM_SAMPLE, \
    STREAM_WARMUP = range(1, 6)


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of ``seed``; any integer seed works."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), int(stream)]))


def gaussian_mixture(n: int, d: int, gen: np.random.Generator
                     ) -> np.ndarray:
    """``[n, d]`` float32: a mixture of 32 Gaussian clusters with
    N(0, 1) centres and within-cluster standard deviation 0.3."""
    n_clusters = min(32, max(2, n // 64))
    centers = gen.standard_normal((n_clusters, d), dtype=np.float32)
    assign = gen.integers(0, n_clusters, size=n)
    x = centers[assign]
    x += np.float32(0.3) * gen.standard_normal((n, d), dtype=np.float32)
    return x


@dataclasses.dataclass
class Corpus:
    """One deployment's data: vectors, metadata and owners, in time
    order (row ``i`` is the ``i``-th point ingested)."""

    x: np.ndarray        # [n, d] float32
    s: np.ndarray        # [n, 3] float64 (lon, lat, t)
    owner: np.ndarray    # [n] int64 tenant index

    @property
    def s32(self) -> np.ndarray:
        """Metadata as the device holds it (float32)."""
        return self.s.astype(np.float32)

    def rows_of(self, tenant: int) -> np.ndarray:
        """Row indices owned by ``tenant``."""
        return np.flatnonzero(self.owner == tenant)


def make_corpus(cfg: dict, seed: int) -> Corpus:
    """The configuration's corpus for ``seed`` (see the module docstring
    for which parts the seed changes)."""
    n, d = int(cfg["n_points"]), int(cfg["dim"])
    lay = np.random.default_rng(int(cfg["layout_seed"]))
    s = lay.uniform(0.0, 1.0, size=(n, 3))
    s = s[np.argsort(s[:, 2], kind="stable")]
    owner = lay.integers(0, int(cfg["tenants"]), n)
    x = gaussian_mixture(n, d, rng(seed, STREAM_VECTORS))
    if cfg["normalize"]:
        x /= np.linalg.norm(x, axis=1, keepdims=True)
    return Corpus(x=x, s=s, owner=owner)
