"""The comparison that decides ``correct``.

Each answer served in the window is a list of corpus rows (read from the
documents ``materialize`` returned) with float32 distances.  A seeded
sample of them is held against the plain reference, and each number below
against the configuration's limit:

* ``wrong``: answers that are plainly wrong -- a row of another tenant or
  outside the filter, a repeated row, fewer rows than the reference has
  (up to k), documents that do not match the gids, or a request due in the
  window that failed or never came back.  Exact: the limit is 0.
* ``gap``: the widest gap, over positions j, by which the float64 distance
  of the j-th row served lies above the reference's j-th, as a share of
  the reference's k-th distance.
* ``dist_err``: the widest gap between a served distance and the float64
  distance of the row it names, as a share of the reference's k-th
  distance.
* ``miss``: 1 minus the mean recall@k against the reference.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

NUMBERS = ("wrong", "gap", "dist_err", "miss")


def compare(answers: Dict[int, Tuple[np.ndarray, np.ndarray]], reqs,
            idx, oracle, wrong: int = 0) -> Dict[str, float]:
    """Numbers of ``answers`` (request -> (rows, dists)) for requests
    ``idx``; ``wrong`` carries faults found before the comparison."""
    k = reqs.k
    gap = dist_err = 0.0
    recalls = []
    for i in idx:
        i = int(i)
        t, spec = int(reqs.tenant[i]), reqs.specs[i]
        ref_rows, ref_d = oracle.topk(t, spec, reqs.q[i], k)
        rows, dists = answers[i]
        rows = np.asarray(rows, np.int64)
        cand = oracle.candidates(t, spec)
        bad = (len(rows) < len(ref_rows)
               or len(np.unique(rows)) != len(rows)
               or not np.all(np.isin(rows, cand))
               or len(dists) < len(rows))
        if bad:
            wrong += 1
            recalls.append(0.0)
            continue
        if not len(ref_rows):
            recalls.append(1.0)
            continue
        scale = max(float(ref_d[-1]), 1e-12)
        d64 = oracle.dist64(reqs.q[i], rows)
        j = min(len(rows), len(ref_rows))
        gap = max(gap, float(np.max(d64[:j] - ref_d[:j])) / scale)
        err = np.abs(np.asarray(dists[:len(rows)], np.float64) - d64)
        dist_err = max(dist_err, float(np.max(err)) / scale)
        recalls.append(len(np.intersect1d(rows, ref_rows)) / len(ref_rows))
    return {"wrong": float(wrong), "gap": gap, "dist_err": dist_err,
            "miss": 1.0 - float(np.mean(recalls)) if recalls else 0.0}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, dict]]:
    """``(correct, {name: {"value", "limit"}})`` over the numbers the
    configuration gives a limit: correct when each is finite and at most
    its limit.  A configuration compares the numbers that it gives a
    limit, each set between the program's readings and those of its
    control or of a planted fault (see ``PERF.md``)."""
    checks = {name: {"value": numbers[name], "limit": limits[name]}
              for name in NUMBERS if name in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
