"""Proximity-graph construction for CubeGraph (paper §4.2, Alg. 1 + Alg. 2).

TPU-native adaptation (see DESIGN.md §2): instead of incremental HNSW
insertion (pointer-chasing, data-dependent control flow), each cube's local
graph is built from an *exact* kNN candidate set computed with tiled MXU
matmuls, then pruned with the standard occlusion heuristic (MRNG / HNSW
"select-neighbors-heuristic").  Cross-cube edges (Alg. 2) are exact
top-``M_cross`` neighbors in each face-adjacent cube — a strictly stronger
version of the paper's ``ef_cross`` approximate search, affordable because
brute-force distance blocks run at MXU speed.

All neighbor arrays are dense ``int32`` with ``-1`` padding and are indexed by
**original dataset ids**, so the vector / metadata / norm arrays are stored
once and shared by every layer (paper Fig. 3 memory layout).  Cube-id lookup
structures are *sparse* (sorted nonempty-cube table + searchsorted) so deep
layers in high metadata dimension (g^m cubes) never allocate O(g^m) arrays.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .grid import Layer

__all__ = [
    "CubeMap",
    "LayerGraph",
    "build_layer_graph",
    "topk_over_candidates",
    "occlusion_prune",
    "squared_norms",
]

INF = jnp.float32(np.inf)


def squared_norms(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(jnp.asarray(x, jnp.float32) ** 2, axis=-1)


# ---------------------------------------------------------------------------
# Generic primitive: running top-k over a padded candidate-id matrix.
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("k", "col_chunk", "metric"))
def _topk_over_candidates(qv, qn, cand, x, norms, exclude, k, col_chunk, metric):
    b, s = cand.shape
    pad = (-s) % col_chunk
    cand = jnp.pad(cand, ((0, 0), (0, pad)), constant_values=-1)
    n_chunks = cand.shape[1] // col_chunk
    cand = cand.reshape(b, n_chunks, col_chunk)

    def body(i, state):
        run_ids, run_d = state
        ids = cand[:, i, :]                                   # [b, c]
        safe = jnp.maximum(ids, 0)
        xv = x[safe]                                          # [b, c, d]
        # HIGHEST: a TPU's default f32 matmul is one bf16 pass
        ip = jnp.einsum("bcd,bd->bc", xv, qv,
                        precision=jax.lax.Precision.HIGHEST)
        if metric == "l2":
            d = norms[safe] - 2.0 * ip + qn[:, None]
        else:  # inner product (negated => smaller is better)
            d = -ip
        bad = (ids < 0) | (ids == exclude[:, None])
        d = jnp.where(bad, INF, d)
        all_ids = jnp.concatenate([run_ids, ids], axis=1)
        all_d = jnp.concatenate([run_d, d], axis=1)
        nd, sel = jax.lax.top_k(-all_d, k)
        return jnp.take_along_axis(all_ids, sel, axis=1), -nd

    init = (jnp.full((b, k), -1, jnp.int32), jnp.full((b, k), INF))
    ids, d = jax.lax.fori_loop(0, n_chunks, body, init)
    return jnp.where(d < INF, ids, -1), d


def topk_over_candidates(
    query_vecs: jnp.ndarray,        # [b, d]
    cand_ids: jnp.ndarray,          # [b, s] int32, -1 padded
    x: jnp.ndarray,                 # [n, d] full vector store
    norms: jnp.ndarray,             # [n]
    k: int,
    exclude: Optional[jnp.ndarray] = None,   # [b] ids to mask (e.g. self)
    col_chunk: int = 1024,
    metric: str = "l2",
):
    """Exact top-k by (squared L2 | negated IP) among per-row candidate lists."""
    qv = jnp.asarray(query_vecs, jnp.float32)
    qn = squared_norms(qv)
    if exclude is None:
        exclude = jnp.full((qv.shape[0],), -1, jnp.int32)
    cc = int(min(col_chunk, max(8, cand_ids.shape[1])))
    return _topk_over_candidates(qv, qn, jnp.asarray(cand_ids, jnp.int32),
                                 x, norms, jnp.asarray(exclude, jnp.int32),
                                 int(k), cc, metric)


# ---------------------------------------------------------------------------
# Occlusion pruning (HNSW select-neighbors-heuristic / MRNG rule).
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("m_out", "backfill"))
def _occlusion_prune(cand, cand_d, x, m_out, backfill):
    b, kc = cand.shape
    safe = jnp.maximum(cand, 0)
    cv = x[safe]                                              # [b, kc, d]
    n2 = jnp.sum(cv * cv, axis=-1)
    pd = n2[:, :, None] - 2.0 * jnp.einsum("bid,bjd->bij", cv, cv) + n2[:, None, :]
    valid = cand >= 0

    def body(j, keep):
        # candidate j survives if no already-kept neighbor is closer to it
        # than the query point is: keep_i and d(c_i, c_j) < d(p, c_j) occludes.
        occluded = jnp.any(keep & (pd[:, :, j] < cand_d[:, j][:, None]), axis=1)
        kj = valid[:, j] & ~occluded
        return keep.at[:, j].set(kj)

    keep = jax.lax.fori_loop(0, kc, body, jnp.zeros((b, kc), bool))
    # order: kept (by distance rank) first, then (optionally) pruned backfill.
    rank = jnp.arange(kc)[None, :] + jnp.where(keep, 0, kc if backfill else 10 * kc)
    rank = jnp.where(valid, rank, 100 * kc)
    sel = jnp.argsort(rank, axis=1)[:, :m_out]
    out = jnp.take_along_axis(cand, sel, axis=1)
    ok = jnp.take_along_axis(rank, sel, axis=1) < (10 * kc if backfill else kc)
    return jnp.where(ok, out, -1)


def occlusion_prune(cand_ids, cand_dists, x, m_out: int, backfill: bool = True):
    """Prune a sorted-by-distance candidate list [b, kc] to degree ``m_out``."""
    return _occlusion_prune(jnp.asarray(cand_ids, jnp.int32),
                            jnp.asarray(cand_dists, jnp.float32),
                            x, int(m_out), bool(backfill))


# ---------------------------------------------------------------------------
# Sparse cube bookkeeping (no O(g^m) allocations)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CubeMap:
    """Sorted table of nonempty flat cube ids with searchsorted row lookup."""

    uniq: np.ndarray               # [n_ne] sorted nonempty flat cube ids
    members: np.ndarray            # [n_ne, p_max] int32, -1 padded (orig ids)
    counts: np.ndarray             # [n_ne]
    entry: np.ndarray              # [n_ne, k_entry] entry points (-1 pad)

    def row_of(self, cubes: np.ndarray) -> np.ndarray:
        """Flat cube ids -> member rows; -1 for empty/unknown cubes."""
        cubes = np.asarray(cubes)
        pos = np.searchsorted(self.uniq, cubes)
        pos_c = np.clip(pos, 0, len(self.uniq) - 1)
        ok = (len(self.uniq) > 0) & (self.uniq[pos_c] == cubes) & (cubes >= 0)
        return np.where(ok, pos_c, -1)

    @property
    def n_nonempty(self) -> int:
        return len(self.uniq)


def _fps_entries(v: np.ndarray, ids: np.ndarray, k: int) -> np.ndarray:
    """Greedy farthest-point-sampled entry points, seeded at the medoid.

    Multiple spread-out entries per cube keep the beam search navigable even
    when the intra-cube kNN graph has several vector-space components (the
    role HNSW's upper layers play in the reference implementation)."""
    n = len(ids)
    k = min(k, n)
    c = v.mean(axis=0, keepdims=True)
    first = int(np.argmin(((v - c) ** 2).sum(axis=1)))
    chosen = [first]
    mind = ((v - v[first]) ** 2).sum(axis=1)
    for _ in range(k - 1):
        nxt = int(np.argmax(mind))
        chosen.append(nxt)
        mind = np.minimum(mind, ((v - v[nxt]) ** 2).sum(axis=1))
    out = np.full(k, -1, dtype=np.int64)
    out[: len(chosen)] = ids[chosen]
    return out


def _cube_map(cube_of: np.ndarray, x_np: np.ndarray, k_entry: int = 4) -> CubeMap:
    order = np.argsort(cube_of, kind="stable")
    sorted_cubes = cube_of[order]
    uniq, starts, counts = np.unique(sorted_cubes, return_index=True, return_counts=True)
    p_max = int(counts.max()) if len(counts) else 1
    members = np.full((max(len(uniq), 1), p_max), -1, dtype=np.int32)
    entry = np.full((max(len(uniq), 1), k_entry), -1, dtype=np.int64)
    for row, (st, ct) in enumerate(zip(starts, counts)):
        ids = order[st:st + ct]
        members[row, :ct] = ids
        e = _fps_entries(x_np[ids], ids, k_entry)
        entry[row, : len(e)] = e
    return CubeMap(uniq=uniq, members=members, counts=counts, entry=entry)


def _face_adjacent_flat(coords: np.ndarray, g: int) -> np.ndarray:
    """[n, m] integer coords -> [n, 2m] flat ids of face-adjacent cubes (-1 OOB).

    Direction order: [dim0-, dim0+, dim1-, dim1+, ...] (matches Fig. 3 blocks).
    """
    n, m = coords.shape
    out = np.full((n, 2 * m), -1, dtype=np.int64)
    weights = g ** np.arange(m - 1, -1, -1)
    base = coords @ weights
    for d in range(m):
        for j, delta in enumerate((-1, +1)):
            nd = coords[:, d] + delta
            ok = (nd >= 0) & (nd < g)
            out[:, 2 * d + j] = np.where(ok, base + delta * weights[d], -1)
    return out


# ---------------------------------------------------------------------------
# Layer graph container + construction
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class LayerGraph:
    """One grid layer's stitched-graph data (all ids = original dataset ids)."""

    level: int
    layer: Layer
    cube_of: np.ndarray            # [n] flat cube id per point
    cubes: CubeMap
    nbrs: jnp.ndarray              # [n, m_intra] intra-cube edges
    xnbrs: jnp.ndarray             # [n, 2m * m_cross] cross-cube edges

    @property
    def all_nbrs(self) -> jnp.ndarray:
        return jnp.concatenate([self.nbrs, self.xnbrs], axis=1)

    def entry_of_cubes(self, cube_ids: np.ndarray) -> np.ndarray:
        """[c] cube ids -> [c, k_entry] entry points (-1 for empty cubes)."""
        rows = self.cubes.row_of(cube_ids)
        e = self.cubes.entry[np.maximum(rows, 0)].copy()
        e[rows < 0] = -1
        return e


def build_layer_graph(
    x: jnp.ndarray,                # [n, d] fp32
    s: np.ndarray,                 # [n, m] metadata (host)
    norms: jnp.ndarray,            # [n]
    layer: Layer,
    m_intra: int = 16,
    m_cross: int = 4,
    point_chunk: int = 2048,
    col_chunk: int = 2048,
    metric: str = "l2",
    k_entry: int = 4,
    n_random: int = 8,
    seed: int = 0,
) -> LayerGraph:
    """Alg. 1 (per-cube local graphs) + Alg. 2 (cross-cube edges) for one layer.

    ``n_random`` random same-cube candidates are appended to each point's
    exact-kNN pool before occlusion pruning; the surviving ones provide the
    long-range edges that incremental HNSW insertion produces implicitly
    (without them a kNN graph over well-separated vector clusters is
    disconnected and un-navigable)."""
    n = x.shape[0]
    m = s.shape[1]
    x_np = np.asarray(x)
    coords = layer.coords_of(s)
    cube_of = layer.flat_of(coords)
    cubes = _cube_map(cube_of, x_np, k_entry=k_entry)
    members = jnp.asarray(cubes.members)
    rng = np.random.default_rng(seed + 7919 * max(layer.level, 0))

    adj_flat = _face_adjacent_flat(coords, layer.g)         # [n, 2m]
    adj_rows = cubes.row_of(adj_flat)                        # [n, 2m] member rows
    own_rows = cubes.row_of(cube_of)                         # [n]

    ids_all = np.arange(n, dtype=np.int32)
    k_cand = int(min(2 * m_intra, max(2, cubes.members.shape[1] - 1)))
    nbrs_out = np.full((n, m_intra), -1, dtype=np.int32)
    xnbrs_out = np.full((n, 2 * m, m_cross), -1, dtype=np.int32)

    counts_of_row = cubes.counts

    for lo in range(0, n, point_chunk):
        sel = ids_all[lo:lo + point_chunk]
        qv = x[sel]
        rows_sel = own_rows[sel]
        cand = members[jnp.asarray(rows_sel)]                # [c, p_max]
        knn_ids, knn_d = topk_over_candidates(
            qv, cand, x, norms, k_cand, exclude=jnp.asarray(sel),
            col_chunk=col_chunk, metric=metric)
        if n_random > 0:
            # random same-cube candidates -> long-range edge pool
            cnt = counts_of_row[rows_sel][:, None]           # [c, 1]
            pos = rng.integers(0, np.maximum(cnt, 1), size=(len(sel), n_random))
            rand_ids = cubes.members[rows_sel[:, None], pos].astype(np.int32)
            rand_ids = np.where(rand_ids == sel[:, None], -1, rand_ids)
            rj = jnp.asarray(rand_ids)
            safe = jnp.maximum(rj, 0)
            xv = x[safe]
            if metric == "l2":
                qn = jnp.sum(qv * qv, axis=-1)
                rd = norms[safe] - 2.0 * jnp.einsum("bcd,bd->bc", xv, qv) + qn[:, None]
            else:
                rd = -jnp.einsum("bcd,bd->bc", xv, qv)
            rd = jnp.where(rj < 0, INF, rd)
            all_ids = jnp.concatenate([knn_ids, rj], axis=1)
            all_d = jnp.concatenate([knn_d, rd], axis=1)
            order = jnp.argsort(all_d, axis=1)
            knn_ids = jnp.take_along_axis(all_ids, order, axis=1)
            knn_d = jnp.take_along_axis(all_d, order, axis=1)
        pruned = occlusion_prune(knn_ids, knn_d, x, m_intra)
        nbrs_out[sel] = np.asarray(pruned)

        # Alg. 2: exact top-m_cross into each face-adjacent cube
        for direction in range(2 * m):
            rows = adj_rows[sel, direction]
            if np.all(rows < 0):
                continue
            cand_dir = cubes.members[np.maximum(rows, 0)].copy()
            cand_dir[rows < 0] = -1
            xids, _ = topk_over_candidates(
                qv, jnp.asarray(cand_dir), x, norms, m_cross,
                col_chunk=col_chunk, metric=metric)
            xnbrs_out[sel, direction] = np.asarray(xids)

    return LayerGraph(
        level=layer.level,
        layer=layer,
        cube_of=cube_of,
        cubes=cubes,
        nbrs=jnp.asarray(nbrs_out),
        xnbrs=jnp.asarray(xnbrs_out.reshape(n, 2 * m * m_cross)),
    )
