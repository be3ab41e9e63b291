"""Pallas TPU kernels for CubeGraph's compute hot-spots.

Each kernel call takes its mode from the backend
(``filtered_topk.interpret_mode``): Mosaic on a TPU, the Pallas
interpreter on the CPU, where ``tests/test_kernels.py`` checks them against
the jnp oracles.  ``tests/test_chip_compile.py`` compiles the main-path
kernels for a described v5e.

- ``distance``      tiled pairwise distance matrix (MXU contraction)
- ``graph_topk``    beam-step kernel + stitched per-bucket traversal
- ``filtered_topk`` fused distance + spatio-temporal predicate + streaming
                    top-k (the paper's Fig. 3 aligned-traversal loop)
- ``quant_topk``    fused *asymmetric-distance* filtered top-k over int8
                    segment codes (scale-folded fp32 query × int8 codes)
- ``ref``           pure-jnp oracles
- ``ops``           jit'd wrappers with padding + filter encoding, plus
                    the dispatch compile-warming registry
"""
from .ops import (PAD_META, dispatch_trace_count, exact_filtered_search,
                  filtered_topk, next_pow2, pairwise_dist, quant_meta_rows,
                  round_up, sharded_filtered_topk,
                  sharded_filtered_topk_grouped,
                  sharded_quant_filtered_topk, warm_sharded_shapes)

__all__ = ["PAD_META", "dispatch_trace_count", "exact_filtered_search",
           "filtered_topk", "next_pow2", "pairwise_dist", "quant_meta_rows",
           "round_up", "sharded_filtered_topk",
           "sharded_filtered_topk_grouped",
           "sharded_quant_filtered_topk", "warm_sharded_shapes"]
