"""Benchmark-side spans around the program's calls, for the traced run.

``install`` replaces three bound methods by instance attributes that
time each call and wrap it in a ``jax.profiler.TraceAnnotation``, so the
spans sit on the profiler's clock beside the device trace:
``bench.flush`` (``CubeGraphService.flush``), ``bench.query_grouped``
(``store.manager.query_grouped``) and ``bench.materialize``
(``store.materialize``).  Each ``query_grouped`` span also records its
groups -- row count, filter kind and time window -- from which
``kernel_costs`` counts the operations and bytes the kernels need.  No
program file changes, and untraced runs install nothing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Tuple


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    groups: Tuple[Tuple[int, str, float, float], ...] = ()

    @property
    def ms(self) -> float:
        return (self.end - self.start) * 1e3


def _kind(filt) -> str:
    """"ball" when the (scoped) filter holds a ball, else "box"."""
    from repro.core import BallFilter, ComposeFilter
    if isinstance(filt, BallFilter):
        return "ball"
    if isinstance(filt, ComposeFilter):
        return "ball" if "ball" in (_kind(filt.a), _kind(filt.b)) else "box"
    return "box"


def _window(filt) -> Tuple[float, float]:
    lo, hi = filt.bounding_box()
    return (float(lo[2]), float(hi[2])) if len(lo) > 2 else \
        (float("-inf"), float("inf"))


class SpanLog:
    """The spans recorded while ``on`` is set."""

    def __init__(self):
        self.spans: List[Span] = []
        self.on = False

    def wrap(self, obj, attr: str, name: str, describe=None) -> None:
        import jax
        inner = getattr(obj, attr)

        def wrapped(*args, **kw):
            if not self.on:
                return inner(*args, **kw)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = inner(*args, **kw)
            sp = Span(name, t0, time.perf_counter())
            if describe is not None:
                sp.groups = describe(*args, **kw)
            self.spans.append(sp)
            return out
        setattr(obj, attr, wrapped)

    def of(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


def install(svc, store) -> SpanLog:
    """Wrap the service's and the store's calls (see the module
    docstring); the returned log records while ``log.on`` is True."""
    log = SpanLog()

    def groups(gqs, *a, **kw):
        import numpy as np
        return tuple((int(np.atleast_2d(g.queries).shape[0]),
                      _kind(g.filt)) + _window(g.filt) for g in gqs)
    log.wrap(svc, "flush", "bench.flush")
    log.wrap(store.manager, "query_grouped", "bench.query_grouped", groups)
    log.wrap(store, "materialize", "bench.materialize")
    return log
