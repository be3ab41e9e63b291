"""Structured tracing: nested host spans on the device trace's clock.

A :class:`QueryTrace` is a tree of :class:`Span` context managers opened
along the query path (delta scan, per-bucket dispatch, rerank, merge)
and, for a served flush, along ``CubeGraphService.flush``.  Three rules
make the numbers honest under JAX's async dispatch:

* a span times the host: it closes when the Python work inside it
  returns, and device work it enqueued may still be running.  Device
  time comes from the device trace, on the same clock;
* every span wraps ``jax.profiler.TraceAnnotation`` named
  ``cubegraph.<span name>`` on the thread that does the work, so a
  captured profile holds the program's spans beside the device's
  operations and a trace reduction tells them from JAX's own host events
  (``Span.name`` and :meth:`QueryTrace.to_dict` keep the bare names);
* no span adds a ``block_until_ready`` or a host copy that the untraced
  path does not make.  Where the path itself waits (:func:`block_ready`
  on the solo path, ``device_wait`` on the grouped path), the span
  around the wait times it.

Compiles are counted in the program: one process-wide
``jax.monitoring`` listener counts backend compiles and
persistent-cache loads (:func:`compile_counts`) and adds each to the
innermost open span of the compiling thread, as attributes ``compiles``
and ``cache_loads``; a finished trace's root carries the totals of its
tree.

The disabled path is a set of shared singletons (:data:`NULL_TRACE` /
its no-op span): opening a span on a disabled trace allocates nothing
and touches no clocks, which is what keeps tracing opt-in
(``SegmentManager.query(..., return_trace=True)``, a
``CubeGraphService`` given a :class:`TraceLog`) rather than a standing
tax.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, Iterator, List, Optional

import jax

__all__ = ["ANNOTATION_PREFIX", "NULL_TRACE", "QueryTrace", "Span",
           "TraceLog", "block_ready", "compile_counts"]

ANNOTATION_PREFIX = "cubegraph."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_hits"
COUNT_KEYS = ("compiles", "cache_loads")

_LOCAL = threading.local()        # per thread: open spans, pending load
_COUNTS = dict.fromkeys(COUNT_KEYS, 0)
_COUNTS_LOCK = threading.Lock()


def _open_spans() -> List["Span"]:
    spans = getattr(_LOCAL, "spans", None)
    if spans is None:
        spans = _LOCAL.spans = []
    return spans


def _note(key: str) -> None:
    with _COUNTS_LOCK:
        _COUNTS[key] += 1
    spans = getattr(_LOCAL, "spans", None)
    if spans:
        attrs = spans[-1].attrs
        attrs[key] = attrs.get(key, 0) + 1


def _on_event(name: str, **_kw) -> None:
    if name == CACHE_LOAD_EVENT:
        # JAX times a persistent-cache load as a backend compile too: the
        # duration event that follows on this thread is this load
        _LOCAL.loading = True
        _note("cache_loads")


def _on_duration(name: str, _secs: float, **_kw) -> None:
    if name == COMPILE_EVENT:
        if getattr(_LOCAL, "loading", False):
            _LOCAL.loading = False
        else:
            _note("compiles")


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_counts() -> Dict[str, int]:
    """Process-wide ``{"compiles", "cache_loads"}`` since import: XLA
    backend compiles, and executables loaded from the persistent
    compilation cache instead."""
    with _COUNTS_LOCK:
        return dict(_COUNTS)


def block_ready(value):
    """``jax.block_until_ready`` that tolerates numpy/None pytrees.

    Returns ``value`` unchanged once its device work has finished.  Only
    call it where the path needs the result on the host anyway: a span
    around it then times the wait, and tracing adds no sync.
    """
    if value is None:
        return value
    return jax.block_until_ready(value)


class Span:
    """One timed node of a trace tree (use via ``QueryTrace.span``)."""

    __slots__ = ("name", "attrs", "children", "_t0", "duration_ms",
                 "_annotation")

    def __init__(self, name: str, attrs: Optional[dict] = None):
        self.name = name
        self.attrs = attrs or {}
        self.children: List[Span] = []
        self._t0 = 0.0
        self.duration_ms = 0.0
        self._annotation = None

    def annotate(self, **attrs) -> None:
        """Attach key/value attributes (bucket cap, candidate counts...)."""
        self.attrs.update(attrs)

    def start(self) -> "Span":
        """Open the ``cubegraph.<name>`` profiler annotation, make this the
        thread's innermost open span, and start the wall clock."""
        self._annotation = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + self.name)
        self._annotation.__enter__()
        _open_spans().append(self)
        self._t0 = time.perf_counter()
        return self

    def stop(self) -> None:
        """Stop the wall clock and close the profiler annotation (on the
        thread that started the span)."""
        self.duration_ms = (time.perf_counter() - self._t0) * 1e3
        spans = _open_spans()
        if self in spans:
            spans.remove(self)
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None

    def walk(self) -> Iterator["Span"]:
        """This span and every span below it, depth first."""
        yield self
        for c in self.children:
            yield from c.walk()

    def to_dict(self) -> dict:
        """JSON-safe ``{name, ms, attrs?, spans?}`` subtree."""
        out = {"name": self.name, "ms": round(self.duration_ms, 4)}
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        if self.children:
            out["spans"] = [c.to_dict() for c in self.children]
        return out


class _SpanCtx:
    """Context manager that pushes/pops one span on its trace's stack."""

    __slots__ = ("_trace", "_span")

    def __init__(self, trace: "QueryTrace", span: Span):
        self._trace = trace
        self._span = span

    def __enter__(self) -> Span:
        self._trace._stack.append(self._span)
        return self._span.start()

    def __exit__(self, exc_type, exc, tb):
        self._span.stop()
        self._trace._stack.pop()
        return False


class QueryTrace:
    """Span tree for one query; the root span times the whole call.

    Created by ``SegmentManager.query(..., return_trace=True)``, by a
    ``CubeGraphService`` flush with a :class:`TraceLog` set (or
    directly) and threaded through ``streaming.query.query_segments*``
    and ``distributed.segment_shards.pack_search*``.  :meth:`finish`
    stops the root; :meth:`to_dict` exports the tree.  A trace belongs to
    the thread that created it.
    """

    enabled = True

    def __init__(self, name: str = "query"):
        self.root = Span(name)
        self._stack: List[Span] = [self.root]
        self.root.start()

    def span(self, name: str, **attrs) -> _SpanCtx:
        """Open a child span of the innermost active span."""
        sp = Span(name, attrs)
        self._stack[-1].children.append(sp)
        return _SpanCtx(self, sp)

    def finish(self) -> "QueryTrace":
        """Stop the root span and give it the tree's ``compiles`` and
        ``cache_loads`` totals (idempotent)."""
        root = self.root
        if root._annotation is not None:
            root.stop()
            totals = {key: sum(sp.attrs.get(key, 0) for sp in root.walk())
                      for key in COUNT_KEYS}
            root.attrs.update(totals)
        return self

    @property
    def total_ms(self) -> float:
        """Root span duration (finish first)."""
        return self.root.duration_ms

    def to_dict(self) -> dict:
        """JSON-safe span tree (root node)."""
        return self.root.to_dict()


class TraceLog:
    """Bounded sink of finished traces: keeps the newest ``maxlen``.

    ``CubeGraphService(trace_log=...)`` adds one ``serve.flush`` trace
    per flush; set the attribute to ``None`` to stop tracing.
    """

    def __init__(self, maxlen: int = 4096):
        self._traces: deque = deque(maxlen=int(maxlen))
        self.added = 0

    def add(self, trace: QueryTrace) -> None:
        """Keep one finished trace (dropping the oldest when full)."""
        self._traces.append(trace)
        self.added += 1

    @property
    def dropped(self) -> int:
        """Traces added but no longer kept."""
        return self.added - len(self._traces)

    def traces(self) -> List[QueryTrace]:
        """The kept traces, oldest first."""
        return list(self._traces)

    def __len__(self) -> int:
        return len(self._traces)


class _NullSpan:
    """Shared no-op span for the disabled trace."""

    __slots__ = ()
    name = "null"
    attrs: dict = {}
    children: list = []
    duration_ms = 0.0

    def annotate(self, **attrs) -> None:
        """No-op."""

    def to_dict(self) -> dict:
        """Empty subtree."""
        return {}


class _NullSpanCtx:
    """Shared no-op span context: no clocks, no allocations."""

    __slots__ = ()

    def __enter__(self) -> _NullSpan:
        return _NULL_SPAN

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()
_NULL_CTX = _NullSpanCtx()


class _NullTrace:
    """Shared disabled tracer (the default for every query)."""

    __slots__ = ()
    enabled = False

    def span(self, name: str, **attrs) -> _NullSpanCtx:
        """Return the shared no-op span context."""
        return _NULL_CTX

    def finish(self) -> "_NullTrace":
        """No-op."""
        return self

    @property
    def total_ms(self) -> float:
        """Always zero."""
        return 0.0

    def to_dict(self) -> dict:
        """Empty tree."""
        return {}


NULL_TRACE = _NullTrace()
