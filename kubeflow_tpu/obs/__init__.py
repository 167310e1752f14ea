"""Unified observability layer: histograms + in-process span tracing.

One import point for the three layers (control plane, serving, train):

    from kubeflow_tpu import obs
    with obs.DEFAULT_TRACER.span("reconcile", kind="Notebook"):
        ...
    obs.get_or_create_histogram(reg, "x_seconds", "...").observe(dt)

`Histogram` registers into the EXISTING controlplane Registry (or any
object with register()/get()); `Tracer` is standalone. The module-level
defaults exist for components with no natural registry/tracer owner
(the Trainer); apps that serve `/metrics` and `/debug/traces` should
own their instances and pass them down (Cluster does).

Import discipline: this package must not import controlplane at module
scope — controlplane.metrics imports `obs.metrics` for its own
histograms, and an eager reverse import would cycle. `default_registry`
imports lazily instead.
"""

from __future__ import annotations

from kubeflow_tpu.obs.cachestats import (
    DEFER_CAUSES,
    EVICTION_CAUSES,
    PEER_FETCH_OUTCOMES,
    PREFILL_SOURCES,
    REUSE_BUCKETS,
    UNATTRIBUTED,
    CacheLedger,
    canonical_prefix,
    prefix_hash,
)
from kubeflow_tpu.obs.cardinality import OVERFLOW_LABEL, LabelGuard
from kubeflow_tpu.obs.compiles import (
    STARTUP_PHASES,
    STARTUP_SPANS,
    CompileLedger,
    bind_startup_gauge,
    startup_span,
)
from kubeflow_tpu.obs.decisions import (
    OUTCOMES as DECISION_OUTCOMES,
    VERDICTS as DECISION_VERDICTS,
    DecisionLedger,
)
from kubeflow_tpu.obs.exposition import (
    ExpositionError,
    parse_exposition,
    render_families,
)
from kubeflow_tpu.obs.federation import federate, merge_families
from kubeflow_tpu.obs.metrics import (
    LATENCY_BUCKETS,
    SIZE_BUCKETS,
    TOKEN_BUCKETS,
    Histogram,
    format_float,
    get_or_create_histogram,
    sample_quantile,
)
from kubeflow_tpu.obs.profiling import (
    SERVING_PHASES,
    TRAIN_PHASES,
    WATCHED_SERVING_FNS,
    WATCHED_TRAIN_FNS,
    CompileWatch,
    PhaseProfiler,
    merge_counter_tracks,
)
from kubeflow_tpu.obs.slo import (
    Slo,
    SloBudgetGauge,
    SloEngine,
    get_or_create_slo_engine,
    register_budget_gauge,
)
from kubeflow_tpu.obs.timeline import RequestTimeline, TimelineStore
from kubeflow_tpu.obs.tracing import (
    Span,
    Tracer,
    merge_chrome_traces,
    traces_response_payload,
)

# obs.endpoints (the shared aiohttp /metrics + /debug/traces handlers)
# is deliberately NOT imported here: importing `obs` must not pull
# aiohttp into HTTP-free processes (the Trainer).

__all__ = [
    "DEFER_CAUSES",
    "EVICTION_CAUSES",
    "LATENCY_BUCKETS",
    "PEER_FETCH_OUTCOMES",
    "PREFILL_SOURCES",
    "REUSE_BUCKETS",
    "SIZE_BUCKETS",
    "STARTUP_PHASES",
    "STARTUP_SPANS",
    "TOKEN_BUCKETS",
    "SERVING_PHASES",
    "TRAIN_PHASES",
    "UNATTRIBUTED",
    "WATCHED_SERVING_FNS",
    "WATCHED_TRAIN_FNS",
    "CacheLedger",
    "CompileLedger",
    "CompileWatch",
    "DECISION_OUTCOMES",
    "DECISION_VERDICTS",
    "DecisionLedger",
    "ExpositionError",
    "Histogram",
    "LabelGuard",
    "OVERFLOW_LABEL",
    "PhaseProfiler",
    "RequestTimeline",
    "Slo",
    "SloBudgetGauge",
    "SloEngine",
    "Span",
    "TimelineStore",
    "Tracer",
    "DEFAULT_TRACER",
    "bind_startup_gauge",
    "canonical_prefix",
    "compile_ledger",
    "default_registry",
    "federate",
    "format_float",
    "get_or_create_histogram",
    "get_or_create_slo_engine",
    "merge_chrome_traces",
    "merge_counter_tracks",
    "merge_families",
    "parse_exposition",
    "prefix_hash",
    "register_budget_gauge",
    "render_families",
    "sample_quantile",
    "startup_span",
    "traces_response_payload",
]

# Process-wide default tracer: components without an injected tracer
# (Trainer, ad-hoc scripts) share it, so one /debug/traces view can
# correlate them.
DEFAULT_TRACER = Tracer()



def compile_ledger() -> CompileLedger:
    """The process's one compile ledger (`obs/compiles.py`): it holds
    what JAX built and the start-up spans once
    `compile_cache.enable()` has installed it, nothing before."""
    from kubeflow_tpu.obs import compiles

    return compiles.LEDGER


_default_registry = None


def default_registry():
    """Lazy process-wide Registry (see import discipline above)."""
    global _default_registry
    if _default_registry is None:
        from kubeflow_tpu.controlplane.metrics import Registry

        _default_registry = Registry()
    return _default_registry
