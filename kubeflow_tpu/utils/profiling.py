"""Profiling: XLA profiler traces, first-compile latency, step timing.

The reference has no tracing/profiling at all (SURVEY.md §5 "Tracing /
profiling — absent"); its observability is metrics + logs. The TPU
replacement is the XLA profiler (TensorBoard profile plugin reads the
trace directory) plus the platform's north-star latency metric
(BASELINE.md): **pod-to-first-XLA-compile seconds** — how long a user
waits between pod start and a first compiled step.

Pod start time comes from `KFTPU_POD_START_TIME` (epoch seconds,
injected by the TPU webhook alongside the topology env); fallback is
process start.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Callable

import jax

_PROCESS_START = time.time()
POD_START_ENV = "KFTPU_POD_START_TIME"


def pod_start_time() -> float:
    raw = os.environ.get(POD_START_ENV, "")
    try:
        return float(raw)
    except ValueError:
        return _PROCESS_START


def device_stamp() -> dict:
    """The device as JAX reports it. Every result a run prints carries
    it, so a number can never be read under the wrong device's name."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def time_to_first_compile(
    fn: Callable[..., Any], *args: Any, **kwargs: Any
) -> tuple[float, Any]:
    """Run `jit(fn)(*args)` once and return (seconds since pod start at
    completion of the first compile+execute, result). The BASELINE
    "pod-to-first-XLA-compile" measurement."""
    out = jax.jit(fn)(*args, **kwargs)
    jax.block_until_ready(out)
    return time.time() - pod_start_time(), out


@contextlib.contextmanager
def trace(logdir: str, tracer: Any = None):
    """XLA profiler trace → `logdir` (open with TensorBoard's profile
    plugin). Wraps steps of interest:

        with profiling.trace("/tmp/profile"):
            state, loss = trainer.step(state, batch, targets)

    Pass an `obs.Tracer` to also drop an `xla.profile` span into the
    app-level trace ring, marking WHICH wall-clock window the heavy XLA
    trace covers — /debug/traces shows the window, TensorBoard's
    profile plugin shows what happened inside it.
    """
    ctx = (tracer.span("xla.profile", logdir=logdir)
           if tracer is not None else contextlib.nullcontext())
    with ctx:
        jax.profiler.start_trace(logdir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()


class StepTimer:
    """Blocking step timer with percentile summary — a thin adapter
    over `obs.profiling.PhaseProfiler` (ISSUE 8): every recorded step
    is a `name` phase on the profiler, so training processes get the
    same step-anatomy aggregation (totals, rolling percentiles,
    counter tracks) the serving batcher has, and `summary()` uses the
    same quantile interpolation as `obs.metrics.Histogram.quantile`
    (`sample_quantile` — the old naive index pick disagreed with the
    histogram-side p95 asserted by the tenants loadtest).

    `with timer.step(): ...` — the exit blocks on `ready` (pass the
    step's output) so async dispatch doesn't fake a fast step.

    Optional obs bridge: give it a `tracer` and/or `histogram` and each
    timed step also becomes a span (named `name`) and a histogram
    observation — the summary here stays process-local, the histogram
    is what /metrics scrapes. Pass a shared `profiler` (the Trainer
    passes its own) to aggregate into an existing step anatomy.
    """

    def __init__(self, tracer: Any = None, histogram: Any = None,
                 name: str = "train.step", profiler: Any = None):
        from kubeflow_tpu.obs.profiling import PhaseProfiler

        self.durations: list[float] = []
        self.tracer = tracer
        self.histogram = histogram
        self.name = name
        self.profiler = (profiler if profiler is not None
                         else PhaseProfiler(phases=(name,)))

    @contextlib.contextmanager
    def step(self, ready: Any = None, **attrs: Any):
        ctx = (self.tracer.span(self.name, **attrs)
               if self.tracer is not None else contextlib.nullcontext())
        with ctx:
            t0 = time.perf_counter()
            yield
            if ready is not None:
                jax.block_until_ready(ready)
            self.record(time.perf_counter() - t0)

    def record(self, seconds: float) -> None:
        self.durations.append(seconds)
        self.profiler.record(self.name, seconds)
        if self.histogram is not None:
            self.histogram.observe(seconds)

    def summary(self) -> dict[str, float]:
        from kubeflow_tpu.obs.metrics import sample_quantile

        if not self.durations:
            return {}
        xs = sorted(self.durations)
        return {
            "count": len(xs),
            "mean_s": sum(xs) / len(xs),
            "p50_s": sample_quantile(xs, 0.50),
            "p90_s": sample_quantile(xs, 0.90),
            "p99_s": sample_quantile(xs, 0.99),
            "max_s": xs[-1],
        }
