"""Operations and bytes the retrieval kernels need, from call shapes.

Counted as the algorithm needs them, not as the program pads them: a
scan reads each point's vector once per dispatch (4·d bytes in float32,
d bytes as int8 codes) and its m metadata columns (4·m bytes), and each
query once (4·d bytes); it computes 2·d operations per (query, point)
pair.  Gids are left out (the kernels do not read them; the merge does).
Padding, re-reads of a block by each group of a vmapped dispatch and
the top-k selection then show as a lower share of the roofline.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple


def scan_cost(n_queries: int, n_points: int, d: int, m: int,
              vector_bytes: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of one scan dispatch."""
    ops = 2.0 * n_queries * n_points * d
    nbytes = n_points * (vector_bytes * d + 4.0 * m) + n_queries * 4.0 * d
    return ops, nbytes


def min_seconds(ops: float, nbytes: float, peak_ops: float,
                peak_bw: float) -> float:
    """The least time the chip needs: the larger of the two bounds."""
    return max(ops / peak_ops, nbytes / peak_bw)


def dispatches(groups: Iterable[Tuple[int, str, float, float]],
               buckets: List[Dict], shared: bool
               ) -> List[Tuple[int, int]]:
    """``[(queries, points)]`` of the kernel dispatches one
    ``query_grouped`` call makes.  ``buckets`` lists each sealed bucket's
    live ``points`` and its segments' time ``spans``; a group dispatches a
    bucket when its window overlaps one of them.  With ``shared`` (the
    grouped fp32 path) the groups of one filter kind share one dispatch per
    bucket; otherwise every group dispatches alone."""
    out = []
    for b in buckets:
        active = [(n, kind) for n, kind, t_lo, t_hi in groups
                  if any(t_hi >= lo and t_lo <= hi for lo, hi in b["spans"])]
        if shared:
            for kind in sorted({kind for _, kind in active}):
                out.append((sum(n for n, kk in active if kk == kind),
                            b["points"]))
        else:
            out.extend((n, b["points"]) for n, _ in active)
    return out
