"""Per-layer metrics read from the spans the program itself writes into
the profiler's trace (`benchmarks/spans.py`): the scheduler's host
time, the requests' waits, the device's idle time the host owes, the
trainer's host gap. Each takes its span names from its data file.

Each returns nothing off the chip (`peaks` is None there): a CPU run
puts no time under a metric's name. Nothing, too, where the profile
holds no such span, as a program older than the spans leaves it.
"""

from __future__ import annotations

import statistics

from benchmarks import harness, spans


def profile(ctx):
    """The traced run's profile; nothing off the chip."""
    if ctx.peaks is None:
        return None
    return spans.load(harness.trace_dir(ctx.cell))


def span_less_ms(ctx, *, span: str, minus: list[str]):
    """Mean over the spans called `span` on the worker's line of their
    duration less the time in the spans called one of `minus`."""
    prof = profile(ctx)
    if prof is None:
        return None
    left = spans.span_less(spans.worker_line(prof.lines, span), span, minus)
    return 1e3 * statistics.mean(left) if left else None


def gap_ms(ctx, *, span: str):
    """Mean time from one span called `span` to the next."""
    prof = profile(ctx)
    if prof is None:
        return None
    gaps = spans.gaps_between(spans.worker_line(prof.lines, span), span)
    return 1e3 * statistics.mean(gaps) if gaps else None


def _stats(prof, span: str, stat: str) -> list[float]:
    return [v for line in prof.lines
            for v in spans.stat_values(line, span, stat)]


def stat_mean(ctx, *, span: str, stat: str, scale: float = 1.0):
    """`scale` x the mean of `stat` over the spans called `span`."""
    prof = profile(ctx)
    values = [] if prof is None else _stats(prof, span, stat)
    return scale * statistics.mean(values) if values else None


def stat_share(ctx, *, span: str, part: str, whole: list[str]):
    """100 x the sum of `part` over the sum of the stats `whole`, over
    the spans called `span`."""
    prof = profile(ctx)
    if prof is None:
        return None
    total = sum(sum(_stats(prof, span, s)) for s in whole)
    return 100.0 * sum(_stats(prof, span, part)) / total if total else None


def idle_share(ctx, *, anchor: str, excused: list[str]):
    """100 x the device's idle seconds that do not fall under a span
    called one of `excused` (idle for want of requests), over the
    window from the first program's start to the last one's end: the
    idle time the host owes. The worker's line is the one that holds
    `anchor`."""
    prof = profile(ctx)
    if prof is None or not prof.window_s():
        return None
    segments = spans.worker_segments(prof.lines, anchor)
    if not segments:
        return None
    by_span = spans.idle_by_span(
        spans.idle_intervals(prof.ops, prof.programs), segments)
    owed = sum(s for name, s in by_span.items() if name not in excused)
    return 100.0 * owed / prof.window_s()
