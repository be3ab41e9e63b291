"""Plain reference of filtered k-nearest-neighbour retrieval.

A float64 numpy brute force over one tenant's points under a numpy twin of
the kernel's filter predicate (both copied from ``chip_smoke.py``).  It
imports nothing of the program and reads nothing the program made: it
sees the corpus and the filter dicts the benchmark generated.

The controls are this reference put in the program's place one precision
below what a configuration states:

* ``bf16x3``: squared L2 distances whose dot products take three bf16
  passes with float32 accumulation (what ``Precision.HIGH`` computes on a
  TPU), for a configuration stated at float32 ``highest``;
* ``int4``: symmetric per-dimension int4 codes fit per sealed segment,
  asymmetric float32 distances, ``rerank_multiple * k`` candidates
  reranked exactly, for a configuration stated at int8 codes with an
  exact rerank.

``FAULTS`` are this reference put in the program's place with one fault
planted, for the numbers that no control separates:

* ``kth_skipped``: the exact answer with its k-th row replaced by the
  (k+1)-th, each row with its true distance -- valid rows, right
  distances, but not the nearest.

All are computed explicitly in numpy on the host, so they give the same
answers on any machine.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def filter_mask(spec: dict, s32: np.ndarray) -> np.ndarray:
    """Rows of float32 metadata ``s32 [n, 3]`` that pass ``spec``, as the
    kernel's float32 predicate decides."""
    if spec["kind"] == "box":
        lo = np.float32(spec["lo"])
        hi = np.float32(spec["hi"])
        return np.all((s32 >= lo) & (s32 <= hi), axis=1)
    if spec["kind"] == "ball":
        c = np.float32(spec["center"])
        d2 = np.sum((s32[:, :2] - c) ** 2, axis=1, dtype=np.float32)
        r = np.float32(spec["radius"])
        t = s32[:, 2]
        return ((d2 <= r * r) & (t >= np.float32(spec["t"][0]))
                & (t <= np.float32(spec["t"][1])))
    raise ValueError(f"unknown filter kind {spec['kind']!r}")


class Oracle:
    """Exact answers over a corpus: float64 distances, ties broken by
    row."""

    def __init__(self, corpus):
        self.corpus = corpus
        self.s32 = corpus.s32

    def candidates(self, tenant: int, spec: dict) -> np.ndarray:
        """Rows of ``tenant`` that pass the filter ``spec``."""
        return np.flatnonzero((self.corpus.owner == tenant)
                              & filter_mask(spec, self.s32))

    def dist64(self, q: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """float64 squared L2 distances of ``q`` to ``rows``."""
        xv = self.corpus.x[rows].astype(np.float64)
        return np.sum((xv - q.astype(np.float64)) ** 2, axis=1)

    def topk(self, tenant: int, spec: dict, q: np.ndarray, k: int
             ) -> Tuple[np.ndarray, np.ndarray]:
        """``(rows [<=k], dists [<=k])``, nearest first."""
        rows = self.candidates(tenant, spec)
        d = self.dist64(q, rows)
        top = np.lexsort((rows, d))[:k]
        return rows[top], d[top]


def _bf16(a: np.ndarray) -> np.ndarray:
    """``a`` rounded to bfloat16, held as float32."""
    import ml_dtypes
    return a.astype(ml_dtypes.bfloat16).astype(np.float32)


def _bf16x3_sq_l2(q: np.ndarray, xv: np.ndarray) -> np.ndarray:
    """``[b, d] x [n, d]`` squared L2 whose dot products take three bf16
    passes (hi*hi + hi*lo + lo*hi) with float32 accumulation: products of
    bf16 values are exact in float32, so this is ``Precision.HIGH`` on any
    device."""
    qh, xh = _bf16(q), _bf16(xv)
    ql, xl = _bf16(q - qh), _bf16(xv - xh)
    ip = qh @ xh.T + qh @ xl.T + ql @ xh.T
    return (np.sum(q * q, axis=1)[:, None] - 2.0 * ip
            + np.sum(xv * xv, axis=1)[None, :])


def control_bf16x3(oracle: Oracle, reqs, idx: np.ndarray, cfg: dict
                   ) -> Dict[int, Tuple[np.ndarray, np.ndarray]]:
    """Control answers ``{request: (rows, float32 dists)}`` for ``idx``."""
    out = {}
    for i in idx:
        i = int(i)
        rows = oracle.candidates(int(reqs.tenant[i]), reqs.specs[i])
        d = _bf16x3_sq_l2(reqs.q[i:i + 1], oracle.corpus.x[rows])[0]
        top = np.lexsort((rows, d))[:reqs.k]
        out[i] = (rows[top], d[top].astype(np.float32))
    return out


def control_int4(oracle: Oracle, reqs, idx: np.ndarray, cfg: dict
                 ) -> Dict[int, Tuple[np.ndarray, np.ndarray]]:
    """Control answers with int4 codes fit per sealed segment."""
    x = oracle.corpus.x
    seg = int(cfg["seal_max_points"])
    scales = np.empty_like(x)
    for lo in range(0, len(x), seg):
        blk = x[lo:lo + seg]
        scales[lo:lo + seg] = np.maximum(np.abs(blk).max(axis=0) / 7.0,
                                         1e-12)
    deq = (np.clip(np.rint(x / scales), -7, 7) * scales).astype(np.float32)
    k = reqs.k
    fetch = k * int(cfg["rerank_multiple"])
    out = {}
    for i in idx:
        rows = oracle.candidates(int(reqs.tenant[i]), reqs.specs[int(i)])
        q = reqs.q[i]
        dv = deq[rows]
        approx = (np.sum(q * q) - 2.0 * dv @ q
                  + np.sum(dv * dv, axis=1)).astype(np.float32)
        cand = rows[np.lexsort((rows, approx))[:fetch]]
        d = oracle.dist64(q, cand)
        top = np.lexsort((cand, d))[:k]
        out[int(i)] = (cand[top], d[top].astype(np.float32))
    return out


def fault_kth_skipped(oracle: Oracle, reqs, idx: np.ndarray, cfg: dict
                      ) -> Dict[int, Tuple[np.ndarray, np.ndarray]]:
    """The exact answers with the k-th row replaced by the (k+1)-th."""
    k = reqs.k
    out = {}
    for i in idx:
        i = int(i)
        rows, d = oracle.topk(int(reqs.tenant[i]), reqs.specs[i], reqs.q[i],
                              k + 1)
        keep = [j for j in range(len(rows)) if j != k - 1][:k]
        out[i] = (rows[keep], d[keep].astype(np.float32))
    return out


CONTROLS = {"bf16x3": control_bf16x3, "int4": control_int4}
FAULTS = {"kth_skipped": fault_kth_skipped}
