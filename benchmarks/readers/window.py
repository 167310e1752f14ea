"""Per-layer metrics read from what the benchmark itself took on the
host's clock over the window: a statistic that runs too unsteadily to
hold a bound end to end, reported beside the metrics it moves."""

from __future__ import annotations


def taken(ctx, *, key: str):
    v = ctx.run.end_to_end.get(key)
    return None if v is None else float(v)
