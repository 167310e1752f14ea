"""Per-layer metrics of a model that keeps a recurrent state per slot
beside the paged pool. The bytes and operations come from functions of
the cell's model class (`benchmarks/models/`), named by the metric's
data file; the time from the device trace; the peak from `peaks.json`.

Each returns nothing off the chip (`peaks` is None there), where the
model class has no such function (every class older than the metric),
and where the program ran nothing under that scope or wrote no such
span, as a program older than them does not.
"""

from __future__ import annotations

from benchmarks.readers import devtrace, flops
from benchmarks.readers.spans import profile, stat_mean


def _need(ctx, name: str):
    """What `ctx.model.<name>(config, counters)` counts for one decode
    step of the window; nothing without the function or the steps."""
    count = getattr(ctx.model, name, None)
    if count is None or not ctx.run.counters.get("decode_steps"):
        return None
    return count(ctx.cell.config, ctx.run.counters)


def _share_of_bandwidth(ctx, need, ms):
    if not need or ms is None or ctx.peaks is None:
        return None
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (ms * 1e-3)


def program_bw_share(ctx, *, program: str, count: str, bytes: str):
    """The bytes a whole decode step has to move, as the model class
    counts them from the slots that decode, over the device's memory
    bandwidth, as a share of `program`'s device time a step."""
    ms = devtrace.program_ms_per_count(ctx, program=program, count=count)
    return _share_of_bandwidth(ctx, _need(ctx, bytes), ms)


def scope_ms_per_count(ctx, *, program: str, scope: str, count: str):
    """Own device time under a model's own `scope` (one that
    `benchmarks/spans.py`'s list does not hold) in `program`, over a
    count the program kept over the traced window: `readers/flops.py`'s
    `scope_ms` is by execution, and an execution of the decode program
    runs 1 to 4 steps."""
    ms = flops.scope_ms(ctx, program=program, scope=scope)
    n = ctx.run.counters.get(count)
    if ms is None or not n:
        return None
    return ms * profile(ctx).executions(program) / n


def scope_bw_share(ctx, *, program: str, scope: str, count: str,
                   bytes: str):
    """A kernel's roofline share, bound by bandwidth: the least bytes
    its work needs a decode step, whatever implements it, over the
    device's memory bandwidth, as a share of the time under `scope` a
    step."""
    ms = scope_ms_per_count(ctx, program=program, scope=scope, count=count)
    return _share_of_bandwidth(ctx, _need(ctx, bytes), ms)


def scope_flops_share_per_token(ctx, *, program: str, scope: str,
                                flops_per_token: str, span: str,
                                stat: str):
    """100 x the operations `ctx.model.<flops_per_token>(config)`
    counts a token x the mean of `stat` over the spans called `span`
    (a prefill slice's valid tokens), over the chip's bf16 peak, over
    the device's own time under `scope` in one execution of `program`:
    a share of a peak, bound by compute."""
    per_token = getattr(ctx.model, flops_per_token, None)
    ms = flops.scope_ms(ctx, program=program, scope=scope)
    if per_token is None or ms is None:
        return None
    tokens = stat_mean(ctx, span=span, stat=stat)
    if not tokens:
        return None
    need = per_token(ctx.cell.config) * tokens
    return 100.0 * need / ctx.peaks["bf16_flops_per_s"] / (ms * 1e-3)
