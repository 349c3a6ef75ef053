"""Per-request views of the program's spans: the spans of one `plan()`
request, and a span's self time."""
from __future__ import annotations

import numpy as np

from perfbench.harness.devtrace import merge
from perfbench.harness.spans import mean, select


def per_request(ctx, name: str, reduce, dag: bool = False) -> float | None:
    """Mean over the window's requests of `reduce(spans)`, the spans called
    `name` inside the request's `plan()` call (with `dag`, inside its DAG
    build); None where no request has such a span."""
    found = []
    for r in ctx.loop.requests:
        t0, t1 = (r["t0"], r["t_plan"]) if dag else (r["t_plan"], r["t1"])
        found.append(select(ctx.spans, name, t0, t1))
    if not any(found):
        return None
    return mean(reduce(s) for s in found)


def self_time(span, spans) -> float:
    """`span`'s duration less the union of the intervals of the spans
    nested in it (deeper, on the same thread, inside its interval)."""
    end = span.t0 + span.dur
    inner = [(s.t0, s.t0 + s.dur) for s in spans
             if s.depth > span.depth and s.thread == span.thread
             and s.t0 >= span.t0 and s.t0 + s.dur <= end]
    if not inner:
        return span.dur
    iv = merge(np.array(inner, dtype=float))
    return span.dur - float((iv[:, 1] - iv[:, 0]).sum())
