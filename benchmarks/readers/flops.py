"""Per-layer metrics that set the operations a piece of the step needs
against the device's peak over the time the device spent on it, and the
counters a `Trainer` keeps in its registry. The operations come from a
function of the cell's model class (`benchmarks/models/`), named by the
metric's data file; a model class that has no such function (every
class older than the metric) reports nothing.
"""

from __future__ import annotations

from benchmarks import spans
from benchmarks.readers.spans import profile


def scope_ms(ctx, *, program: str, scope: str, kernels: tuple = ()):
    """Own device time under `scope` in one execution of `program`, for
    a scope that `benchmarks/spans.py`'s own list does not hold (a
    model's own: `kda`, `moe_experts`): every operation with `scope`
    among its `op_name`'s path components counts, whatever wraps it.
    `kernels` names operations that belong to the scope's work though
    the compiler leaves their `op_name` empty: XLA rewrites a grouped
    product into its own `ragged-dot` kernel and drops the scope it
    was traced under; they count where the operation's name holds one
    of them and no other scope claims it."""
    prof = profile(ctx)
    if prof is None:
        return None
    runs = prof.executions(program)
    secs = spans.scope_seconds(
        prof.ops, prof.programs, prof.op_names, scopes=(scope,)
    ).get(program, {}).get(scope, 0.0)
    if kernels:
        named = [op for op in prof.ops
                 if any(k in op[0].split(" = ")[0] for k in kernels)]
        secs += spans.scope_seconds(
            named, prof.programs, prof.op_names, scopes=(scope,)
        ).get(program, {}).get(spans.UNSCOPED, 0.0)
    return 1e3 * secs / runs if secs and runs else None


def scope_flops_share(ctx, *, program: str, scope: str, flops: str,
                      kernels: tuple = ()):
    """100 x the operations `ctx.model.<flops>(config, counters)` counts
    for one execution of `program`, over the chip's bf16 peak, over the
    device's own time under `scope` in that execution: a share of a
    peak, bound by compute."""
    count = getattr(ctx.model, flops, None)
    ms = scope_ms(ctx, program=program, scope=scope, kernels=kernels)
    if count is None or ms is None:
        return None
    need = count(ctx.cell.config, ctx.run.counters)
    if not need:
        return None
    return 100.0 * need / ctx.peaks["bf16_flops_per_s"] / (ms * 1e-3)


def trainer_gauge(ctx, *, name: str):
    """What the cell's `Trainer` holds in its registry under `name`,
    as the model class that built it hands it over."""
    read = getattr(ctx.model, "trainer_gauge", None)
    return None if read is None else read(name)
