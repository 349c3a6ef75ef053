"""Reading the program's spans (`repro.obs` records: name, start `t0` and
duration `dur` on the host's monotonic clock, attrs)."""
from __future__ import annotations


def select(spans, name: str, t0: float = float("-inf"),
           t1: float = float("inf"), **attrs) -> list:
    """Spans called `name` with the given attrs that lie in [t0, t1]."""
    return [s for s in spans if s.name == name and s.t0 >= t0
            and s.t0 + s.dur <= t1
            and all(s.attrs.get(k) == v for k, v in attrs.items())]


def total(spans) -> float:
    return sum(s.dur for s in spans)


def mean(values) -> float | None:
    values = list(values)
    return sum(values) / len(values) if values else None
