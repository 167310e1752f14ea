"""Per-layer metrics read from the device trace of a traced run. Each
returns nothing where the trace has no such program."""

from __future__ import annotations


def program_ms_per_count(ctx, *, program: str, count: str):
    """Device time of every execution of `program` in the traced window
    over a count the program kept over the same window: a decode step's
    time is `_step`'s device time over the decode steps the batcher
    counted (one dispatch runs 1 to 4 of them)."""
    trace, n = ctx.run.trace, ctx.run.counters.get(count)
    total = None if trace is None else trace.program_total_s(program)
    if total is None or not n:
        return None
    return 1e3 * total / n


def program_median_ms(ctx, *, program: str):
    trace = ctx.run.trace
    med = None if trace is None else trace.program_median_s(program)
    return None if med is None else 1e3 * med
