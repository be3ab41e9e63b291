"""Quantized read path: int8 segment codecs + exact fp32 rerank.

Sealed segments are immutable by construction, so per-dimension symmetric
int8 scales can be fit once — at seal or compaction-publish — and never
revisited (``codec``).  The sealed-segment scan then runs over int8 codes
with the scale folded into the fp32 query (asymmetric distance, see
``repro.kernels.quant_topk``), over-fetches a candidate set, and a final
exact fp32 rerank (``rerank``) restores full-precision ordering with the
same deterministic ``(dist, gid)`` tie-break the unquantized merge uses.

- ``codec``   fit / quantize / dequantize + the per-segment ``SegmentQuant``
              payload (codes, scales, dequantized squared norms)
- ``rerank``  exact fp32 top-k over a candidate gid set, gathered into a
              fixed-shape block and scored by ``kernels.ops.rerank_topk``
"""
from .codec import (QUANT_KINDS, SegmentQuant, dequantize, encode_segment,
                    fit_scales, quantize)
from .rerank import rerank_exact

__all__ = ["QUANT_KINDS", "SegmentQuant", "dequantize", "encode_segment",
           "fit_scales", "quantize", "rerank_exact"]
