"""Per-layer metrics read from what the program counts."""

from __future__ import annotations


def value(ctx, *, key: str, scale: float = 1.0):
    v = ctx.run.counters.get(key)
    return None if v is None else scale * v


def ratio(ctx, *, num: str, den: str, per: str | None = None,
          scale: float = 1.0):
    """`scale * num / den`, over `per` too where given: occupancy is
    tokens / steps / slots."""
    c = ctx.run.counters
    n, d = c.get(num), c.get(den)
    p = 1 if per is None else c.get(per)
    if n is None or not d or not p:
        return None
    return scale * n / d / p
