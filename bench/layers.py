"""What the per-layer readers read: ``LayerContext`` holds the traced
run's spans, trace summary and kernel lower bounds, and
the helpers below turn them into one number or None (nothing to read)."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from .spans import SpanLog
from .trace_reduce import TraceSummary


@dataclasses.dataclass
class LayerContext:
    spans: SpanLog
    trace: Optional[TraceSummary]
    kernel_min_s: Dict[str, float]        # least time of each kernel's work


def mean_span_ms(ctx: LayerContext, name: str) -> Optional[float]:
    spans = ctx.spans.of(name)
    return sum(s.ms for s in spans) / len(spans) if spans else None


def flush_self_ms(ctx: LayerContext) -> Optional[float]:
    """Mean of each flush's span less the query_grouped spans inside it."""
    flushes = ctx.spans.of("bench.flush")
    if not flushes:
        return None
    inner = ctx.spans.of("bench.query_grouped")
    total = 0.0
    for f in flushes:
        total += f.ms - sum(q.ms for q in inner
                            if q.start >= f.start and q.end <= f.end)
    return total / len(flushes)


def roofline_pct(ctx: LayerContext, kernel: str) -> Optional[float]:
    """Share of the kernel's device time that its work needs at least."""
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s.get(kernel, 0.0)
    need = ctx.kernel_min_s.get(kernel, 0.0)
    if t <= 0.0 or need <= 0.0:
        return None
    return 100.0 * need / t


def device_idle_pct(ctx: LayerContext) -> Optional[float]:
    tr = ctx.trace
    if tr is None or tr.n_devices == 0 or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
