"""Quickstart: build a CubeGraph index and run hybrid filtered AKNN queries.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import BoxFilter, CubeGraphConfig, CubeGraphIndex
from repro.core.workloads import ground_truth, make_dataset, recall

enable_compile_cache()

# 1. A dataset of (embedding, spatio-temporal metadata) pairs:
#    5k objects, 48-d embeddings, metadata = (lon, lat) in [0,1]^2.
x, s = make_dataset(n=5000, d=48, m=2, seed=0)

# 2. Build the hierarchical-grid stitched-graph index (Alg. 1 + Alg. 2).
index = CubeGraphIndex.build(x, s, CubeGraphConfig(n_layers=4, m_intra=16,
                                                   m_cross=4))
print("index stats:", index.stats())

# 3. A hybrid query: top-10 nearest neighbors inside a spatial box.
queries = x[:8] + 0.02
filt = BoxFilter(lo=np.asarray([0.2, 0.3], np.float32),
                 hi=np.asarray([0.5, 0.6], np.float32))
ids, dists = index.query(queries, filt, k=10, ef=64)
print("result ids[0]:", ids[0])

# 4. Verify against brute force.
gt, _ = ground_truth(x, s, queries, filt, 10)
print(f"recall@10 = {recall(ids, gt):.3f}")

# 5. Every result satisfies the filter:
import jax.numpy as jnp
assert bool(filt.contains(jnp.asarray(s[ids[ids >= 0]])).all())
print("all results inside the filter ✓")
