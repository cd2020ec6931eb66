"""The program's own spans in a traced window, for the per-layer metrics
that read them.

The engine, the server and the control plane emit ``engine.*``,
``server.*`` and ``plane.*`` spans (``jax.profiler.TraceAnnotation``), so
they land on the host plane of the trace beside the harness's
``server.step`` and ``engine.step``. A reader keeps the spans that lie
inside the window the harness reduces: from the first traced
``server.step``'s start to the last one's end. A program without these
spans gives the readers nothing to read, and they return ``None``.
"""
from __future__ import annotations

from typing import List, Optional

import xtrace


def window_spans(ctx) -> Optional[List[xtrace.Span]]:
    """The trace's host spans inside the window, or ``None`` without a
    trace or a traced server step."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    steps = [s for s in tr.spans if s[0] == "server.step"]
    if not steps:
        return None
    w0, w1 = min(s[1] for s in steps), max(s[2] for s in steps)
    return [s for s in tr.spans if s[1] >= w0 and s[2] <= w1]


def named(spans: List[xtrace.Span], *names: str) -> List[xtrace.Span]:
    return [s for s in spans if s[0] in names]


def total_ns(spans: List[xtrace.Span]) -> float:
    return sum(e - s for _, s, e, _ in spans)


def stat_sum(spans: List[xtrace.Span], key: str) -> int:
    return sum(int(s[3].get(key, 0)) for s in spans)
