"""Shares of the device's published peaks. The operations and bytes
come from the configuration file's own numbers (`benchmarks/models/`),
the time from the trace or the host clock, the peak from `peaks.json`.
Nothing is reported off the chip (`peaks` is None there)."""

from __future__ import annotations

from benchmarks.readers import devtrace


def decode_bw_share(ctx, *, program: str, count: str):
    """The bytes a decode step has to read — every weight once, and the
    K and V of the contexts of the slots that decode in it — over the
    device's memory bandwidth, as a share of the step's device time."""
    step_ms = devtrace.program_ms_per_count(ctx, program=program, count=count)
    c = ctx.run.counters
    if step_ms is None or ctx.peaks is None or not c.get("decode_steps"):
        return None
    slots_decoding = c["decode_tokens"] / c["decode_steps"]
    need = ctx.model.decode_bytes_per_step(
        ctx.cell.config, slots_decoding * c["mean_context_tokens"])
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (step_ms * 1e-3)


def train_mfu(ctx):
    """Model FLOPs per token (recomputation not counted) x the tokens a
    chip trains in one median step, over the chip's bf16 peak. From the
    median step and not the window's rate: a traced window also holds
    the profiler's own stop."""
    c = ctx.run.counters
    if ctx.peaks is None or not c.get("step_median_ms"):
        return None
    flops = ctx.model.train_flops_per_token(ctx.cell.config, c["seq_len"])
    tokens_per_s = c["tokens_per_step_per_chip"] / (c["step_median_ms"] * 1e-3)
    return 100.0 * flops * tokens_per_s / ctx.peaks["bf16_flops_per_s"]
