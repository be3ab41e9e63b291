"""Complex spatio-temporal filters: circles, polygons, compositions, and the
two query strategies (predetermined Alg. 3 vs on-the-fly Alg. 4).

    PYTHONPATH=src python examples/spatial_filters.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import CubeGraphConfig, CubeGraphIndex
from repro.core.workloads import (ground_truth, make_ball_filter,
                                  make_compose_filter, make_dataset,
                                  make_polygon_filter, recall)

enable_compile_cache()

# 3D metadata: (lon, lat, timestamp)
x, s = make_dataset(n=6000, d=32, m=3, seed=1)
index = CubeGraphIndex.build(x, s, CubeGraphConfig(n_layers=4))
queries = x[:8] + 0.02

for name, filt in [
    ("circle+time-window", make_ball_filter(3, 0.08, seed=2)),
    ("polygon-5", make_polygon_filter(3, 0.08, n_vertices=5, seed=3)),
    ("box-minus-circle", make_compose_filter(3, 0.08, seed=4)),
]:
    gt, _ = ground_truth(x, s, queries, filt, 10)
    for mode in ("predetermined", "onthefly"):
        ids, _, st = index.query(queries, filt, k=10, ef=96, mode=mode,
                                 return_stats=True)
        print(f"{name:20s} {mode:14s} layer={st.layer} "
              f"cubes={st.n_active_cubes:3d} recall={recall(ids, gt):.3f} "
              f"search={st.search_ms:.0f}ms")
