"""Per-layer metrics read from the device's own time under a scope the
program names (`jax.named_scope`; `benchmarks/spans.py` finds it in an
operation's `op_name`), inside one of its step programs. Each takes
the program and the scope from its data file.

Each returns nothing off the chip (`peaks` is None there), and where
the program ran no operation under that scope, as a program older
than the scopes does.
"""

from __future__ import annotations

from benchmarks.readers.spans import profile


def _scope_s(ctx, program: str, scope: str):
    """-> (own seconds under `scope` in `program`, its executions)."""
    prof = profile(ctx)
    if prof is None:
        return None, 0
    secs = prof.by_scope.get(program, {}).get(scope)
    return secs, prof.executions(program)


def ms_per_count(ctx, *, program: str, scope: str, count: str):
    """Own device time under `scope` over a count the program kept over
    the traced window (the decode steps: one execution runs 1 to 4)."""
    secs, _ = _scope_s(ctx, program, scope)
    n = ctx.run.counters.get(count)
    return 1e3 * secs / n if secs and n else None


def ms_per_execution(ctx, *, program: str, scope: str):
    secs, runs = _scope_s(ctx, program, scope)
    return 1e3 * secs / runs if secs and runs else None


def kv_bw_share(ctx, *, program: str, scope: str, count: str):
    """The kernel's roofline share, bound by bandwidth: the K and V
    bytes of the decoding slots' contexts — the least the work needs,
    whatever implements it — over the device's memory bandwidth, as a
    share of the time under `scope` a step."""
    ms = ms_per_count(ctx, program=program, scope=scope, count=count)
    c = ctx.run.counters
    if ms is None or not c.get("decode_steps"):
        return None
    slots_decoding = c["decode_tokens"] / c["decode_steps"]
    need = (slots_decoding * c["mean_context_tokens"]
            * ctx.model.kv_token_bytes(ctx.cell.config))
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
