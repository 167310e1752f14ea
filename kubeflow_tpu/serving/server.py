"""Model-serving REST server (the TF-Serving-proxy replacement).

The reference exposed model inference as an HTTP service behind the same
Service/VirtualService machinery as notebooks
(`/root/reference/docs_dev/tf_serving.md:1-60`; prediction smoke test in
`/root/reference/testing/test_tf_serving.py:40-57`). TPU-native version:
an aiohttp app wrapping `InferenceEngine`, serving
  POST /v1/models/{name}:generate   {"tokens": [[...]], "max_new": N}
  POST /v1/models/{name}:generate   {"text": "...", ...} (byte tokenizer)
  GET  /v1/models                    model card listing
  GET  /healthz /readyz              gateway probes

Text in/out uses a dependency-free byte-level tokenizer (offset by
`BYTE_OFFSET` to keep specials 0..byte_offset-1 free) so the server
round-trips strings without downloaded vocabularies; real deployments
pass token IDs from their own tokenizer.
"""

from __future__ import annotations

import asyncio
import logging
import math
import secrets
import time
from typing import Any

import jax.numpy as jnp
import numpy as np
from aiohttp import web

from kubeflow_tpu import obs as obs_lib
from kubeflow_tpu.obs import endpoints as obs_endpoints
from kubeflow_tpu.serving.continuous import (
    PREFILL_CHUNK_TOKENS,
    ContinuousBatcher,
    MigratedAway,
    Overloaded,
    bucket_pow2,
)
from kubeflow_tpu.serving.engine import InferenceEngine
from kubeflow_tpu.serving import migration
from kubeflow_tpu.serving.speculative import SpeculativeEngine
from kubeflow_tpu.tenancy import (
    PRIORITIES,
    THROTTLE_REASONS,
    TenancyConfig,
    Throttled,
)

BYTE_OFFSET = 3  # 0=pad, 1=bos, 2=eos
BOS, EOS = 1, 2


def byte_encode(text: str) -> list[int]:
    return [BOS] + [b + BYTE_OFFSET for b in text.encode("utf-8")]


def byte_decode(tokens: list[int], on_dropped=None) -> str:
    # Ids outside the byte range are dropped, not crashed on. Specials
    # below the offset (pad/bos/eos) are expected in generated rows and
    # stay silent; vocab-TAIL ids (the model's vocab is larger than
    # 256+offset, so a sampled tail id means tokenizer/model drift) are
    # the ones worth surfacing — silent drops there hide drift, and
    # debugging a prefix-cache mismatch starts from the token stream.
    # Callers pass `on_dropped(count)` to count tail drops; the serving
    # app feeds `serving_tokenizer_dropped_tokens_total`.
    kept = [t - BYTE_OFFSET for t in tokens
            if BYTE_OFFSET <= t < BYTE_OFFSET + 256]
    if on_dropped is not None:
        tail = sum(1 for t in tokens if t >= BYTE_OFFSET + 256)
        if tail:
            on_dropped(tail)
    return bytes(kept).decode("utf-8", errors="replace")


ENGINES_KEY: web.AppKey = web.AppKey("engines", dict)
GPU_LOCK_KEY: web.AppKey = web.AppKey("gpu_lock", asyncio.Lock)
TOKENIZER_KEY: web.AppKey = web.AppKey("tokenizer", object)
BATCHERS_KEY: web.AppKey = web.AppKey("batchers", dict)
SPEC_KEY: web.AppKey = web.AppKey("speculative", dict)
OBS_KEY: web.AppKey = web.AppKey("obs", object)
DRAIN_KEY: web.AppKey = web.AppKey("drain_state", dict)
FLEET_REG_KEY: web.AppKey = web.AppKey("fleet_registration", dict)
TENANCY_KEY: web.AppKey = web.AppKey("tenancy", object)  # TenancyConfig|None
POOL_KEY: web.AppKey = web.AppKey("pool_role", str)  # disagg role
# Live-rollout plane (ISSUE 18): the version this replica advertises in
# fleet heartbeats, the injected weight-reloader callable (None → Orbax
# checkpoint restore), and the chaos-defect dict the loadtest's bad-
# version arm plants via /v1/reload to force an SLO burn.
MODEL_VERSION_KEY: web.AppKey = web.AppKey("model_version", str)
RELOADER_KEY: web.AppKey = web.AppKey("weight_reloader", object)
DEFECT_KEY: web.AppKey = web.AppKey("reload_defect", dict)

# Disaggregation roles (mirrors fleet.registry.POOLS — the serving
# side must stay importable without the fleet package and vice versa)
POOL_ROLES = ("mixed", "prefill", "decode")


# Replica SLO defaults (ISSUE 6). TTFT thresholds are per priority
# class — interactive traffic is the one the burn-rate gauge exists to
# defend; batch gets slack. Overridable per deployment via
# `create_serving_app(slo_ttft_s=...)` (the loadtest tunes interactive
# to the hardware it runs on).
SLO_TTFT_THRESHOLDS_S = {
    "interactive": 0.5,
    "standard": 2.0,
    "batch": 10.0,
}
SLO_ITL_THRESHOLD_S = 0.25
SLO_LATENCY_OBJECTIVE = 0.95   # 95% of requests under threshold
SLO_ERROR_OBJECTIVE = 0.99     # 99% of requests without a 5xx
# Speculative decoding pays for itself only while the draft keeps
# guessing right: every verified draft token is a good/bad event, and
# the burn rate pages when the accepted fraction drops below this
# objective (a stale or mismatched draft silently BURNS throughput —
# each rejected token is a wasted verify slot). Overridable per
# deployment via `create_serving_app(slo_spec_acceptance=...)`.
SLO_SPEC_ACCEPTANCE_OBJECTIVE = 0.5


class ServingObs:
    """Per-app observability bundle: metric registry + span tracer +
    the serving hot-path histograms (ISSUE 1). `/metrics` renders the
    registry, `/debug/traces` exports the tracer's ring; every request
    carries its trace id back in `X-Trace-Id`."""

    def __init__(self, registry=None, tracer=None, *, slo_ttft_s=None,
                 slo_spec_acceptance: float | None = None):
        # controlplane.metrics is pure Python (no jax/store state is
        # touched here) — the ONE Registry implementation serves all
        # three layers rather than a drifted serving copy.
        from kubeflow_tpu.controlplane.metrics import (
            Counter,
            Gauge,
            Registry,
        )

        self.registry = registry if registry is not None else Registry()
        self.tracer = tracer if tracer is not None else obs_lib.Tracer()
        self.request_latency = obs_lib.get_or_create_histogram(
            self.registry, "serving_request_duration_seconds",
            "Serving HTTP request latency by route/method")
        self.ttft = obs_lib.get_or_create_histogram(
            self.registry, "serving_time_to_first_token_seconds",
            "Request arrival to first generated token, per model "
            "(streaming: first token on the wire; one-shot: full "
            "generation, an upper bound)")
        self.batch_size = obs_lib.get_or_create_histogram(
            self.registry, "serving_batch_size",
            "Requests co-scheduled per engine invocation",
            buckets=obs_lib.SIZE_BUCKETS)
        # Paged-KV / radix-prefix-cache instrumentation (continuous
        # batcher only; the gauge is refreshed by a render-time
        # collector so /metrics always reports the live pool).
        self.prefix_hits = Counter(
            "serving_prefix_cache_hits_total",
            "Admissions that reused cached KV cells (radix prefix "
            "cache or a registered prefix)", self.registry)
        self.prefix_misses = Counter(
            "serving_prefix_cache_misses_total",
            "Admissions that prefilled their whole prompt (no cached "
            "prefix matched)", self.registry)
        self.kv_blocks = Gauge(
            "serving_kv_blocks_in_use",
            "KV pool blocks held by active requests plus the radix "
            "prefix cache, per model", self.registry)
        self.kv_pool_cell_lanes = Gauge(
            "serving_kv_pool_cell_lanes",
            "Minor dimension of the KV pool: the head size where a "
            "cell lies a head a row (heads of whole 128-lane tiles), "
            "n_kv x head size where a cell's heads lie side by side "
            "in one row (smaller heads), per model", self.registry)
        self.ssm_state_bytes = Gauge(
            "serving_ssm_state_bytes",
            "Recurrent-state bytes of the slots that hold a request "
            "(a model with Mamba layers keeps a state per slot beside "
            "the paged pool; 0 for every other model), per model",
            self.registry)
        self.ssm_state_resets = Counter(
            "serving_ssm_state_resets_total",
            "Slots adopted with their recurrent state zeroed: one per "
            "admission, a preempted request's replay included",
            self.registry)
        self.prefill_tokens = obs_lib.get_or_create_histogram(
            self.registry, "serving_prefill_tokens",
            "Per-admission prompt tokens by source: computed (suffix "
            "actually prefilled), reused (served from device-resident "
            "cached KV), restored (host spill tier, host->device "
            "copy), peer_fetched (imported from a peer replica via "
            "the X-KV-Peer heat hint)",
            buckets=obs_lib.TOKEN_BUCKETS)
        self.dropped_tokens = Counter(
            "serving_tokenizer_dropped_tokens_total",
            "Generated token ids outside the byte-decoder's range "
            "(vocab tail / specials) dropped from text responses — "
            "nonzero means tokenizer/model drift", self.registry)
        # One-shot info gauge (value is always 1; the information is
        # the label): which paged-attention impl decode resolved to —
        # xla gather or the fused pallas kernel. Set once per model at
        # app creation; joins cleanly against the per-model latency
        # series.
        self.attention_impl = Gauge(
            "serving_attention_impl",
            "Resolved paged-attention impl per model (info gauge: "
            "value 1, impl in the label)", self.registry)
        # Multi-tenant QoS series (continuous batcher with a tenancy
        # config only — tenant-blind deployments register the families
        # but emit no samples). Counters sync from the ledger's
        # cumulative stats at scrape time; the gauge reads live depth.
        self.tenant_queue_depth = Gauge(
            "serving_tenant_queue_depth",
            "Requests waiting in a tenant's admission sub-queue",
            self.registry)
        self.tenant_tokens = Counter(
            "serving_tenant_tokens_total",
            "Tokens generated per tenant and model", self.registry)
        self.tenant_throttled = Counter(
            "serving_tenant_throttled_total",
            "Admissions shed or deferred per tenant by reason: rate "
            "(request bucket empty, HTTP 429) or kv_quota (concurrent "
            "KV-block share spent, request waits)", self.registry)
        self.tenant_preemptions = Counter(
            "serving_tenant_preemptions_total",
            "Batch-class decodes evicted mid-generation to free a slot "
            "for interactive work, per tenant", self.registry)
        # Live KV-block migration (ISSUE 7): instant drain exports
        # in-flight sequences to peers; /v1/migrate/in imports them.
        # Failures always roll back (zero leaked blocks) and count
        # here by direction.
        self.migration_out = Counter(
            "serving_migration_out_total",
            "In-flight sequences exported to a peer replica on "
            "instant drain, per model", self.registry)
        self.migration_in = Counter(
            "serving_migration_in_total",
            "Migrated sequences imported into the local KV pool "
            "(cache-warm; the router re-dispatch resumes them), per "
            "model", self.registry)
        self.migration_failed = Counter(
            "serving_migration_failed_total",
            "Migration transfers that failed and rolled back, per "
            "model and direction (in: import rejected or wedged, "
            "out: no peer accepted the record)", self.registry)
        self.migration_blocks = Counter(
            "serving_migration_blocks_total",
            "KV pool blocks moved by live migration, per model and "
            "direction", self.registry)
        # Token-timeline companions (ISSUE 6): the continuous batcher's
        # on_itl/on_queue_wait hooks feed these, so the fleet view gets
        # the same numbers the per-request timeline endpoint shows.
        self.itl = obs_lib.get_or_create_histogram(
            self.registry, "serving_itl_seconds",
            "Inter-token latency: gap between consecutive decode "
            "tokens of one request, per model (gaps spanning a "
            "preempt/resume hole are excluded — those measure "
            "scheduling, see serving_queue_wait_seconds)")
        self.queue_wait = obs_lib.get_or_create_histogram(
            self.registry, "serving_queue_wait_seconds",
            "Enqueue to first admission into the decode batch, per "
            "model (scheduling delay; excludes prefill)")
        # Step-anatomy profiling plane (ISSUE 8): the continuous
        # batcher's PhaseProfiler decomposes every worker iteration
        # into named phases; these families carry the decomposition.
        # Phase/fn labels are CLOSED SETS (obs.profiling guards them),
        # zero-seeded per model at app creation.
        self.step_phase_seconds = obs_lib.get_or_create_histogram(
            self.registry, "serving_step_phase_seconds",
            "Wall time per worker-loop phase (admit, prefill, decode, "
            "sample, detokenize, preempt, resume, host_gap, idle), "
            "per model — phases record exclusive time, so summing "
            "them reconstructs loop wall time")
        self.step_tokens = obs_lib.get_or_create_histogram(
            self.registry, "serving_step_tokens",
            "Tokens attributed per phase and model (prefill: suffix "
            "tokens computed per grouped prefill; decode: tokens "
            "emitted per chunk)", buckets=obs_lib.TOKEN_BUCKETS)
        self.goodput = Gauge(
            "serving_goodput_ratio",
            "Decode device-time share of total non-idle step time, "
            "per model (the Podracer-style goodput ledger; 1.0 means "
            "every non-idle second decoded tokens)", self.registry)
        self.bubble = Gauge(
            "serving_bubble_fraction",
            "host_gap share of total non-idle step time, per model — "
            "the bubble dispatch-ahead exists to hide", self.registry)
        self.kv_high_water = Gauge(
            "serving_kv_blocks_high_water",
            "High-water mark of KV pool blocks in use since startup, "
            "per model (capacity headroom for the pool sizing knob)",
            self.registry)
        self.recompiles = Counter(
            "serving_recompiles_total",
            "Entries of a watched jitted callable's own dispatch cache "
            "past its first (JAX traced it for a new signature: a "
            "retrace) — nonzero RATE in steady state means the "
            "compile-shape bucketing leaked", self.registry)
        obs_lib.bind_startup_gauge(self.registry, "serving_startup_seconds")
        # KV-cache observatory (ISSUE 13): the block lifecycle ledger
        # (obs.cachestats.CacheLedger, attached to each batcher's
        # BlockPool) books every block death to a CAUSE; the cause set
        # is closed and zero-seeded per model, and the conservation
        # invariant — causes sum to total frees, `unattributed` == 0 —
        # is what `ci/obs_check cache` asserts from a live scrape.
        self.kv_evictions = Counter(
            "serving_kv_evictions_total",
            "KV pool blocks freed, by cause: lru (radix eviction), "
            "pressure (preemption), refdrop (normal retirement), "
            "divergence (duplicate content), migration (exported or "
            "rolled back). `unattributed` is a free site that forgot "
            "to book a cause — always zero, or it's a bug",
            self.registry)
        self.kv_admission_defers = Counter(
            "serving_kv_admission_defers_total",
            "Admissions pushed back for lack of KV blocks, by cause: "
            "kv_quota (tenant share spent) vs pool_exhausted (pool "
            "empty even after LRU eviction)", self.registry)
        # Fleet cache tier (ISSUE 19): host-RAM spill demotions and
        # restores are content movement, not deaths — they get their
        # own counters so the tier's traffic is visible next to the
        # eviction causes, plus a render-time occupancy gauge. Peer
        # block fetches (the router's X-KV-Peer hint) count by
        # OUTCOME (closed set: ok/miss/failed); any non-ok falls back
        # to plain prefill, so `failed` burning is a perf smell, not
        # a correctness one.
        self.kv_spill_demotions = Counter(
            "serving_kv_spill_demotions_total",
            "KV blocks demoted from the device pool into the host-RAM "
            "spill tier on eviction, per model (deaths booked to "
            "cause=spill in serving_kv_evictions_total)", self.registry)
        self.kv_spill_restores = Counter(
            "serving_kv_spill_restores_total",
            "Spilled KV blocks promoted back into the device pool on "
            "a prefix re-hit (host->device copy instead of prefill "
            "recompute), per model", self.registry)
        self.kv_spill_bytes = Gauge(
            "serving_kv_spill_bytes",
            "Host RAM currently holding spilled KV block contents, "
            "per model (bounded by --kv-spill-bytes)", self.registry)
        self.peer_fetch = Counter(
            "fleet_peer_fetch_total",
            "Replica-side KV block fetches from a peer named by the "
            "router's X-KV-Peer heat hint, by outcome: ok (blocks "
            "imported, prefill seeded), miss (peer no longer caches "
            "the prefix), failed (transport/geometry error — request "
            "fell back to plain prefill)", self.registry)
        self.kv_reuse_distance = obs_lib.get_or_create_histogram(
            self.registry, "serving_kv_reuse_distance_admissions",
            "Admissions between consecutive touches of the same cached "
            "KV block, per model — the working-set curve; mass beyond "
            "the pool's block count predicts misses an LRU pool of "
            "that size must take", buckets=obs_lib.REUSE_BUCKETS)
        self.kv_block_age = obs_lib.get_or_create_histogram(
            self.registry, "serving_kv_block_age_admissions",
            "Block age at death in admissions, per model — young "
            "deaths under pressure/lru mean the pool churns before "
            "reuse can pay off", buckets=obs_lib.REUSE_BUCKETS)
        # SLO burn rates (obs.slo): the engine IS the gauge metric —
        # registering it zero-seeds every slo x window series. TTFT
        # objectives are per priority class; error-rate likewise;
        # ITL is fleet-wide (a preempted batch decode and a healthy
        # interactive one share the decode loop).
        ttft_thr = dict(SLO_TTFT_THRESHOLDS_S)
        ttft_thr.update(slo_ttft_s or {})
        slos = [obs_lib.Slo(
                    f"serving_ttft_{cls}", SLO_LATENCY_OBJECTIVE,
                    threshold_s=ttft_thr[cls],
                    description=f"p95 TTFT for {cls} traffic under "
                                f"{ttft_thr[cls]:g} s")
                for cls in PRIORITIES]
        slos.append(obs_lib.Slo(
            "serving_itl", SLO_LATENCY_OBJECTIVE,
            threshold_s=SLO_ITL_THRESHOLD_S,
            description=f"p95 inter-token latency under "
                        f"{SLO_ITL_THRESHOLD_S:g} s"))
        slos.extend(obs_lib.Slo(
                        f"serving_errors_{cls}", SLO_ERROR_OBJECTIVE,
                        description=f"99% of {cls} requests answered "
                                    "without a 5xx")
                    for cls in PRIORITIES)
        spec_obj = SLO_SPEC_ACCEPTANCE_OBJECTIVE \
            if slo_spec_acceptance is None else float(slo_spec_acceptance)
        slos.append(obs_lib.Slo(
            "serving_spec_acceptance", spec_obj,
            description=f"{spec_obj:.0%} of verified draft tokens "
                        "accepted (below this the draft burns more "
                        "verify slots than it saves)"))
        # shared-registry rule: one burn-rate engine per registry (a
        # process hosting several apps feeds the first one)
        self.slo = obs_lib.get_or_create_slo_engine(self.registry, slos)
        # X-Tenant is a raw client header: anywhere it becomes a label
        # or span attribute it passes this guard, so a scanner minting
        # fresh values cannot mint unbounded timeseries.
        self.tenant_guard = obs_lib.LabelGuard()


_OBS_T0 = "obs_request_start"
_OBS_TTFT_DONE = "obs_ttft_recorded"


def _priority_class(request: web.Request) -> str:
    """Resolve the request's tenant priority class for SLO accounting.
    Tenant-blind deployments are all `standard` — the SLO families
    still zero-seed for every class, so dashboards don't change shape
    when tenancy is switched on."""
    tenancy = request.app.get(TENANCY_KEY)
    if tenancy is None:
        return "standard"
    return tenancy.resolve(request.headers.get("X-Tenant", "")).priority


def _observe_first_token(request: web.Request, model: str) -> None:
    """Record time-to-first-token ONCE per request (stream paths call
    on the first emitted token; the one-shot path after generate)."""
    sobs = request.app.get(OBS_KEY)
    t0 = request.get(_OBS_T0)
    if sobs is None or t0 is None or request.get(_OBS_TTFT_DONE):
        return
    request[_OBS_TTFT_DONE] = True
    dt = time.perf_counter() - t0
    labels = {"model": model}
    tenant_hdr = request.headers.get("X-Tenant")
    if tenant_hdr:
        # guarded: the label echoes a client-chosen value
        labels["tenant"] = sobs.tenant_guard.admit(tenant_hdr)
    sobs.ttft.observe(dt, **labels)
    sobs.slo.observe(f"serving_ttft_{_priority_class(request)}", dt)


@web.middleware
async def _obs_middleware(request: web.Request, handler):
    """Root span + latency histogram + X-Trace-Id for every serving
    response. Routes label by PATTERN (`/v1/models/{name}:generate`),
    never raw path — label cardinality must not scale with model names
    scanners probe for."""
    sobs: ServingObs = request.app[OBS_KEY]
    resource = getattr(request.match_info.route, "resource", None)
    route = getattr(resource, "canonical", None) or "unmatched"
    request[_OBS_T0] = time.perf_counter()
    status = 500
    # Cross-process propagation (ISSUE 6): a request routed through
    # the fleet router carries its trace context in headers; adopt it
    # so this replica's segment commits under the ROUTER's trace id
    # (span_from_remote validates the ids — an arbitrary client header
    # can't corrupt the ring).
    remote_tid = request.headers.get("X-Trace-Id", "")
    remote_psid = request.headers.get("X-Parent-Span", "")
    if remote_tid and remote_psid:
        span_cm = sobs.tracer.span_from_remote(
            "http.request", remote_tid, remote_psid,
            method=request.method, route=route)
    else:
        span_cm = sobs.tracer.span("http.request",
                                   method=request.method, route=route)
    with span_cm as span:
        tenant_hdr = request.headers.get("X-Tenant")
        if tenant_hdr:
            # guarded: the attribute echoes a client-chosen value
            span.attrs["tenant"] = sobs.tenant_guard.admit(tenant_hdr)
        try:
            resp = await handler(request)
            status = resp.status
            span.attrs["status"] = status
            if not resp.prepared:  # stream paths set it pre-prepare
                resp.headers.setdefault("X-Trace-Id", span.trace_id)
            return resp
        except web.HTTPException as exc:
            status = exc.status
            span.attrs["status"] = status
            exc.headers.setdefault("X-Trace-Id", span.trace_id)
            raise
        finally:
            sobs.request_latency.observe(
                time.perf_counter() - request[_OBS_T0],
                route=route, method=request.method)
            if route.startswith("/v1/models/"):
                # availability SLO counts model-inference traffic
                # only — probe/debug endpoints would dilute the budget
                sobs.slo.record(
                    f"serving_errors_{_priority_class(request)}",
                    status < 500)


class Batcher:
    """Dynamic request batching for one engine: concurrent generate
    requests collected within a small window run as ONE padded batch.

    Decode reads every weight once per step regardless of batch size, so
    co-scheduling N requests costs ~one request's bandwidth — the
    classic serving-throughput lever. Variable prompt lengths ride the
    engine's left-padded prompt_mask path; requests are grouped by
    sampling knobs (one SamplingParams per compiled batch) and run to
    the group's max max_new (each caller trims to its own ask).
    """

    def __init__(self, engine: InferenceEngine, gpu_lock: asyncio.Lock,
                 *, window_ms: float = 5.0, max_batch: int = 8):
        self.engine = engine
        self.gpu_lock = gpu_lock
        self.window_s = window_ms / 1000.0
        self.max_batch = max_batch
        self.calls = 0            # engine invocations (observability)
        self.requests = 0         # successfully batched requests
        self.on_batch = None      # hook(batch_size) per successful group
        self._queue: asyncio.Queue = asyncio.Queue()
        self._worker: asyncio.Task | None = None
        self._inflight: list = []  # dequeued but unresolved (see close)
        self._closed = False
        self._draining = False

    def in_flight(self) -> int:
        """Admitted-but-unfinished work (queued + dequeued-unresolved)."""
        return self._queue.qsize() + len(self._inflight)

    def begin_drain(self) -> None:
        """Stop admission; queued work still runs. Sticky until close()
        or end_drain()."""
        self._draining = True

    def end_drain(self) -> None:
        """Re-open admission after a completed drain (the /v1/reload
        drain-swap-resume cycle; a drain is only terminal with close)."""
        self._draining = False

    async def drain(self, timeout: float | None = None) -> bool:
        """Stop admission and wait for admitted work to resolve. Same
        contract as ContinuousBatcher.drain (False on timeout / dead
        worker with work left)."""
        self._draining = True
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.in_flight():
            if self._worker is None or self._worker.done():
                return False
            if deadline is not None and time.monotonic() > deadline:
                return False
            await asyncio.sleep(0.01)
        return True

    async def submit(self, tokens: list[int], max_new: int,
                     sampling: tuple) -> list[int]:
        if self._closed:
            raise RuntimeError("batcher is shut down")
        if self._draining:
            raise RuntimeError("batcher is draining")
        if self._worker is None or self._worker.done():
            self._worker = asyncio.get_event_loop().create_task(
                self._run())
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        await self._queue.put((tokens, max_new, sampling, fut))
        return await fut

    async def _run(self):
        while True:
            first = await self._queue.get()
            # Everything dequeued is tracked until its future resolves:
            # cancellation mid-window or mid-run must not strand callers
            # (close() fails whatever is left here).
            self._inflight = [first]
            await asyncio.sleep(self.window_s)  # let siblings arrive
            batch = [first]
            while (len(batch) < self.max_batch
                   and not self._queue.empty()):
                batch.append(self._queue.get_nowait())
            self._inflight = batch
            # Sampling knobs are per-row vectors (SamplingParams), so
            # requests with DIFFERENT temperature/top_k/top_p share one
            # batch; split only when padded prompt + max_new would
            # exceed the cache bucket (each request alone fits; their
            # COMBINATION might not).
            cap = self.engine.ec.max_len
            sub: list = []
            for item in batch:
                trial = sub + [item]
                need = (max(len(t) for t, _, _, _ in trial)
                        + max(mn for _, mn, _, _ in trial))
                if sub and need > cap:
                    await self._run_group(sub)
                    sub = [item]
                else:
                    sub = trial
            if sub:
                await self._run_group(sub)
            self._inflight = []

    # Round up to a power of two (>= 16), capped: bounded compile
    # shapes instead of one compile per novel (longest, max_new).
    # One definition (continuous.bucket_pow2) serves both batchers.
    _bucket = staticmethod(bucket_pow2)

    async def _run_group(self, items: list) -> None:
        cap = self.engine.ec.max_len
        longest = max(len(t) for t, _, _, _ in items)
        max_new = max(mn for _, mn, _, _ in items)
        # Bucket both dims so mixed traffic reuses a handful of
        # compiled shapes; extra prompt columns are masked pads, extra
        # new tokens are trimmed per request. Fall back to exact sizes
        # when the buckets would not fit the cache.
        max_new_b = self._bucket(max_new, cap - longest)
        longest_b = self._bucket(longest, cap - max_new_b)
        if longest_b < longest or max_new_b < max_new:
            longest_b, max_new_b = longest, max_new
        rows = 1
        while rows < len(items):
            rows *= 2  # batch dim buckets too (dummy rows, outputs dropped)
        arr = np.zeros((rows, longest_b), np.int32)
        mask = np.zeros((rows, longest_b), bool)
        mask[:, -1] = True  # dummy rows need one real token
        ec = self.engine.ec
        # filler rows get forced-greedy knobs (temp 0, no filters): a
        # sampled EngineConfig default on a dummy row would drag an
        # all-greedy batch into the sampled branch's per-step argsorts
        temp = np.zeros(rows, np.float32)
        top_k = np.zeros(rows, np.int64)
        top_p = np.ones(rows, np.float32)
        for i, (toks, _, sampling, _) in enumerate(items):
            mask[i, :] = False
            arr[i, longest_b - len(toks):] = toks
            mask[i, longest_b - len(toks):] = True
            s = dict(sampling)
            temp[i] = s.get("temperature", ec.temperature)
            top_k[i] = s.get("top_k", ec.top_k)
            top_p[i] = s.get("top_p", ec.top_p)
        max_new = max_new_b

        def run():
            return np.asarray(self.engine.generate(
                jnp.asarray(arr), max_new=max_new,
                prompt_mask=jnp.asarray(mask),
                temperature=temp, top_k=top_k, top_p=top_p))

        try:
            async with self.gpu_lock:
                out = await asyncio.get_event_loop().run_in_executor(
                    None, run)
            self.calls += 1
            self.requests += len(items)  # mean batch = requests/calls
            if self.on_batch is not None:
                self.on_batch(len(items))
            for i, (_, mn, _, fut) in enumerate(items):
                if not fut.done():
                    fut.set_result(out[i, :mn].tolist())
        except Exception as e:  # noqa: BLE001 — fail the waiting requests
            for _, _, _, fut in items:
                if not fut.done():
                    fut.set_exception(e)

    async def close(self) -> None:
        """Cancel the worker and fail everything unresolved — queued
        AND already dequeued (the worker holds items across the window
        sleep and the engine call; CancelledError bypasses _run_group's
        except, so those futures must be failed here)."""
        self._closed = True   # late submit() raises instead of hanging
        if self._worker is not None:
            self._worker.cancel()
            try:
                await self._worker
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        pending = list(self._inflight)
        self._inflight = []
        while not self._queue.empty():
            pending.append(self._queue.get_nowait())
        for _, _, _, fut in pending:
            if not fut.done():
                fut.set_exception(RuntimeError("server shutting down"))


def create_serving_app(engines: dict[str, InferenceEngine],
                       *, tokenizer=None, batch_window_ms: float = 0.0,
                       max_batch: int = 8, continuous: bool = False,
                       warmup: bool = False,
                       prefill_chunk_tokens: int = PREFILL_CHUNK_TOKENS,
                       prefixes: dict[str, list[int]] | None = None,
                       max_pending: int | None = None,
                       pipeline_depth: int | None = None,
                       kv_block_size: int = 64,
                       kv_pool_blocks: int | None = None,
                       kv_spill_bytes: int | None = None,
                       paged_attention_impl: str = "auto",
                       drafts: dict[str, InferenceEngine] | None = None,
                       spec_decode: bool = False,
                       spec_gamma: int = 4,
                       registry=None, tracer=None,
                       drain_grace_s: float = 30.0,
                       tenancy: TenancyConfig | None = None,
                       slo_ttft_s: dict[str, float] | None = None,
                       slo_spec_acceptance: float | None = None,
                       pool: str = "mixed",
                       model_version: str = "",
                       reloader=None,
                       ) -> web.Application:
    """`tokenizer` (data.bpe.Tokenizer or anything with encode/decode)
    serves the "text" request mode; without one, the zero-training
    byte-level fallback applies. `batch_window_ms > 0` enables dynamic
    request batching: concurrent single-prompt requests within the
    window run as one padded batch per sampling group.
    `continuous=True` upgrades batching to slot-based continuous
    batching (serving/continuous.py): requests join/leave a persistent
    `max_batch`-slot decode batch at token boundaries — no window, no
    waiting for a group's longest member. `warmup=True` (continuous
    only) compiles the bounded serving shape set in on_startup, so
    readiness implies no first-arrival compile stalls — startup takes
    correspondingly longer. `drafts` maps model names to draft
    engines; a request with "speculative": true then decodes through
    SpeculativeEngine (latency lever; batch 1). `spec_decode=True`
    (continuous only) instead folds each model's draft into its
    continuous batcher: EVERY request decodes speculatively on the
    paged KV cache, `spec_gamma` draft tokens verified per round in
    one fused batched pass — token-identical to plain decode, and it
    composes with radix caching, preemption and migration. Requires a
    draft for every served model. `prefill_chunk_tokens` (continuous
    only) is the budget of an admission prefill slice: prompts are fed
    that many tokens at a time, interleaved with decode chunks, so no
    decode stall is longer than the budget while a long prompt
    prefills. `kv_block_size` /
    `kv_pool_blocks` (continuous only) shape the paged KV cache: pow2
    tokens per block and total pool blocks per model (default: the
    dense equivalent, every slot can reach max_len — shrink the pool
    to cap KV HBM, admission then accounts by blocks free and defers
    requests the pool can't cover). `kv_spill_bytes` (continuous only)
    adds a bounded host-RAM spill tier under each model's pool: radix
    eviction demotes block contents to host numpy instead of
    discarding, and a returning prefix restores them with a
    host->device copy instead of recomputing prefill — size it from
    the reuse-distance histogram's mass beyond the pool (see
    docs/operator-guide.md). `paged_attention_impl`
    (continuous only) selects decode's attention path: "xla" (gather
    through the block table), "pallas" (fused kernel walking the table
    in-kernel; TPU only), or "auto" (pallas on TPU, xla
    elsewhere) — the resolved choice is exported as the
    `serving_attention_impl` info gauge. `registry`/`tracer`
    share an external metric registry / span tracer; by default the app
    owns fresh ones, exposed at `/metrics` and `/debug/traces`.
    `drain_grace_s` bounds how long shutdown (and POST /drain via
    cleanup) waits for in-flight generations before closing.
    `tenancy` (continuous only) is a `tenancy.TenancyConfig`: requests
    carry their tenant in the `X-Tenant` header (unknown/absent →
    `default`), admission becomes priority + weighted fair-share with
    per-tenant rate limits, KV-block shares, and batch-class
    preemption, and `/metrics` grows zero-seeded `serving_tenant_*`
    series. Without it the server is tenant-blind: FIFO admission,
    identical to before. `slo_ttft_s` overrides the per-priority-class
    TTFT SLO thresholds (`SLO_TTFT_THRESHOLDS_S`) feeding the
    `slo_burn_rate` gauges — e.g. `{"interactive": 0.2}`.
    `pool` declares the replica's disaggregation role (ISSUE 12):
    "mixed" (default) serves both phases exactly as before;
    "prefill"/"decode" (continuous only) advertise the role in fleet
    heartbeats so the pool-aware router sends prompts to the prefill
    pool and hands the filled KV blocks to decode replicas over
    `/v1/migrate/in`. The role changes ROUTING, not capability —
    either specialized replica can still serve a full generation, so
    pool imbalance degrades to symmetric behavior instead of 503s.
    `model_version` names the weights this replica boots with; it rides
    in fleet heartbeats (the rollout plane's confirmation signal) and
    is updated by `POST /v1/reload`. `reloader` is an optional
    `fn(name, engine, source) -> params` callable /v1/reload uses to
    materialize new weights (tests and the loadtest inject seed-based
    reloaders); without one, reload restores `source["checkpoint"]`
    via Orbax."""
    if pool not in POOL_ROLES:
        raise ValueError(
            f"pool must be one of {POOL_ROLES}, got {pool!r}")
    if pool != "mixed" and not continuous:
        raise ValueError(
            f"pool={pool!r} requires continuous=True (the handoff "
            "path ships paged KV blocks)")
    app = web.Application(middlewares=[_obs_middleware])
    app[POOL_KEY] = pool
    app[DRAIN_KEY] = {"draining": False, "grace_s": float(drain_grace_s)}
    app[MODEL_VERSION_KEY] = str(model_version or "")
    app[RELOADER_KEY] = reloader
    app[DEFECT_KEY] = {}
    sobs = ServingObs(registry=registry, tracer=tracer,
                      slo_ttft_s=slo_ttft_s,
                      slo_spec_acceptance=slo_spec_acceptance)
    app[OBS_KEY] = sobs
    app[ENGINES_KEY] = engines
    unknown = set(drafts or {}) - set(engines)
    if unknown:
        raise ValueError(f"drafts registered for unknown models "
                         f"{sorted(unknown)}")
    app[SPEC_KEY] = {name: SpeculativeEngine(engines[name], draft)
                     for name, draft in (drafts or {}).items()}
    tok_vocab = getattr(tokenizer, "vocab_size", None)
    if tok_vocab is not None:
        # Fail at startup, not per request: a tokenizer whose ids exceed
        # a model's vocab would 400 every text request with a confusing
        # "token ids must be in range" error.
        for name, eng in engines.items():
            if tok_vocab > eng.cfg.vocab_size:
                raise ValueError(
                    f"tokenizer vocab {tok_vocab} exceeds model "
                    f"{name!r} vocab {eng.cfg.vocab_size}")
    app[TOKENIZER_KEY] = tokenizer
    # One inference at a time per process: the device is the bottleneck,
    # and interleaved generate calls would just thrash compile caches.
    lock = asyncio.Lock()
    app[GPU_LOCK_KEY] = lock
    if not continuous and (warmup or prefixes
                           or prefill_chunk_tokens != PREFILL_CHUNK_TOKENS
                           or spec_decode
                           or max_pending is not None
                           or pipeline_depth is not None
                           or kv_block_size != 64
                           or kv_pool_blocks is not None
                           or kv_spill_bytes is not None
                           or paged_attention_impl != "auto"
                           or tenancy is not None):
        # these knobs only exist on the continuous batcher; silently
        # ignoring them would ship a server missing configuration the
        # caller explicitly asked for (max_pending especially: the
        # caller believes overload sheds at that depth; tenancy
        # especially: the caller believes quotas are enforced)
        raise ValueError(
            "warmup/prefill_chunk_tokens/prefixes/"
            "max_pending/pipeline_depth/kv_block_size/kv_pool_blocks/"
            "kv_spill_bytes/paged_attention_impl/spec_decode/tenancy "
            "require continuous=True")
    if spec_decode:
        missing = set(engines) - set(drafts or {})
        if missing:
            # silently decoding some models speculatively and others
            # not would make the latency story per-model surprising
            raise ValueError(
                f"spec_decode=True requires a draft for every served "
                f"model; missing {sorted(missing)}")
    app[TENANCY_KEY] = tenancy
    if continuous:
        # prefixes: named system prompts; a request opts in with
        # {"prefix": name} and the radix cache keeps their KV.
        app[BATCHERS_KEY] = {
            name: ContinuousBatcher(
                eng, lock, max_slots=max_batch,
                prefill_chunk_tokens=prefill_chunk_tokens,
                prefixes=prefixes,
                max_pending=256 if max_pending is None else max_pending,
                pipeline_depth=pipeline_depth,
                kv_block_size=kv_block_size,
                kv_pool_blocks=kv_pool_blocks,
                kv_spill_bytes=kv_spill_bytes,
                paged_attention_impl=paged_attention_impl,
                draft=(drafts or {}).get(name) if spec_decode else None,
                spec_gamma=spec_gamma,
                tenancy=tenancy)
            for name, eng in engines.items()}
        if warmup:
            async def _warm(app_):
                loop = asyncio.get_event_loop()
                for b in app_[BATCHERS_KEY].values():
                    await loop.run_in_executor(None, b.warmup)

            app.on_startup.append(_warm)
    else:
        app[BATCHERS_KEY] = (
            {name: Batcher(eng, lock, window_ms=batch_window_ms,
                           max_batch=max_batch)
             for name, eng in engines.items()}
            if batch_window_ms > 0 else {})
    for model_name, b in app[BATCHERS_KEY].items():
        if isinstance(b, Batcher):
            # coalescing evidence as a histogram, not just the
            # calls/requests counters list_models reports
            b.on_batch = (lambda n, _m=model_name:
                          sobs.batch_size.observe(n, model=_m))
        elif isinstance(b, ContinuousBatcher):
            def on_prefix(computed, reused, hit, tenant="",
                          restored=0, _m=model_name):
                fam = sobs.prefix_hits if hit else sobs.prefix_misses
                # the unlabeled (model-only) totals stay exactly what
                # they always were — the bench gate reads them; the
                # tenant-labelled series rides in the same family,
                # guard-capped (ISSUE 13)
                fam.inc(model=_m)
                fam.inc(model=_m, tenant=sobs.tenant_guard.admit(tenant))
                sobs.prefill_tokens.observe(
                    computed, model=_m, source="computed")
                # restored cells are radix hits whose content came off
                # the host spill tier — split them out of `reused` so
                # the two sources partition the cached cells exactly
                restored = max(0, min(int(restored), int(reused)))
                if reused - restored:
                    sobs.prefill_tokens.observe(
                        reused - restored, model=_m, source="reused")
                if restored:
                    sobs.prefill_tokens.observe(
                        restored, model=_m, source="restored")

            b.on_prefix = on_prefix

            # token-timeline companions: the batcher hands back every
            # decode gap and first-admission wait (ISSUE 6)
            def on_itl(gap, _m=model_name):
                sobs.itl.observe(gap, model=_m)
                sobs.slo.observe("serving_itl", gap)

            def on_queue_wait(wait, _m=model_name):
                sobs.queue_wait.observe(wait, model=_m)

            # every verified draft token is one good/bad event against
            # the spec-acceptance SLO (rejected = budget burned); the
            # series zero-seeds with the engine whether or not
            # spec_decode is on, so the dashboard shape is stable
            def on_spec_round(proposed, accepted):
                accepted = min(int(accepted), int(proposed))
                for _ in range(accepted):
                    sobs.slo.record("serving_spec_acceptance", True)
                for _ in range(int(proposed) - accepted):
                    sobs.slo.record("serving_spec_acceptance", False)

            b.on_itl = on_itl
            b.on_queue_wait = on_queue_wait
            b.on_spec_round = on_spec_round
            # seed zero samples so the exposition carries the series
            # (and a 0 reading) before the first admission
            sobs.prefix_hits.inc(0, model=model_name)
            sobs.prefix_misses.inc(0, model=model_name)
            _t0 = sobs.tenant_guard.admit("")  # tenant-blind bucket
            sobs.prefix_hits.inc(0, model=model_name, tenant=_t0)
            sobs.prefix_misses.inc(0, model=model_name, tenant=_t0)
            sobs.migration_out.inc(0, model=model_name)
            sobs.migration_in.inc(0, model=model_name)
            for _d in ("in", "out"):
                sobs.migration_failed.inc(
                    0, model=model_name, direction=_d)
                sobs.migration_blocks.inc(
                    0, model=model_name, direction=_d)
            # which attention impl decode resolved to, as an info
            # gauge; the tracer hook makes each decode chunk a
            # `decode.attention` span carrying the same label
            sobs.attention_impl.set(
                1, model=model_name, impl=b.cengine.attention_impl)
            b.tracer = sobs.tracer
            # Step-anatomy plane (ISSUE 8): zero-seed the full closed
            # phase/fn label sets so dashboards see every series from
            # the first scrape, then bind the profiler and
            # compile-watch hooks (same swallowed-exception contract
            # as on_prefix — see PhaseProfiler)
            for _p in obs_lib.SERVING_PHASES:
                sobs.step_phase_seconds.seed(model=model_name, phase=_p)
                sobs.step_tokens.seed(model=model_name, phase=_p)
            sobs.goodput.set(0.0, model=model_name)
            sobs.bubble.set(0.0, model=model_name)
            sobs.kv_high_water.set(0, model=model_name)
            for _fn in obs_lib.WATCHED_SERVING_FNS:
                sobs.recompiles.inc(0, model=model_name, fn=_fn)
            # cache observatory: zero-seed the CLOSED cause sets (incl.
            # `unattributed`, whose permanent zero is the conservation
            # contract) and the reuse/age histograms, then bind the
            # lifecycle ledger's hooks
            for _c in (*obs_lib.EVICTION_CAUSES, obs_lib.UNATTRIBUTED):
                sobs.kv_evictions.inc(0, model=model_name, cause=_c)
            for _c in obs_lib.DEFER_CAUSES:
                sobs.kv_admission_defers.inc(
                    0, model=model_name, cause=_c)
            sobs.kv_reuse_distance.seed(model=model_name)
            sobs.kv_block_age.seed(model=model_name)
            # fleet cache tier (ISSUE 19): zero-seed the closed
            # prefill-source and peer-fetch-outcome sets plus the
            # spill traffic counters, so the tier's absence reads as
            # explicit zeros rather than missing series
            for _s in obs_lib.PREFILL_SOURCES:
                sobs.prefill_tokens.seed(model=model_name, source=_s)
            for _o in obs_lib.PEER_FETCH_OUTCOMES:
                sobs.peer_fetch.inc(0, model=model_name, outcome=_o)
            sobs.kv_spill_demotions.inc(0, model=model_name)
            sobs.kv_spill_restores.inc(0, model=model_name)
            sobs.kv_spill_bytes.set(0, model=model_name)

            def on_free(cause, n, _m=model_name):
                sobs.kv_evictions.inc(n, model=_m, cause=cause)

            def on_reuse(dist, _m=model_name):
                sobs.kv_reuse_distance.observe(dist, model=_m)

            def on_age(age, _m=model_name):
                sobs.kv_block_age.observe(age, model=_m)

            def on_defer(cause, _m=model_name):
                sobs.kv_admission_defers.inc(model=_m, cause=cause)

            def on_spill(event, n, _m=model_name):
                # demote/restore are content movement between tiers;
                # "drop" (budget pushed an entry out of host RAM) has
                # no counter of its own — it shows up as the spilled
                # gauge falling without a restore
                if event == "demote":
                    sobs.kv_spill_demotions.inc(n, model=_m)
                elif event == "restore":
                    sobs.kv_spill_restores.inc(n, model=_m)

            b.cache_ledger.on_free = on_free
            b.cache_ledger.on_reuse = on_reuse
            b.cache_ledger.on_age = on_age
            b.cache_ledger.on_defer = on_defer
            b.cache_ledger.on_spill = on_spill

            def on_phase(phase, seconds, tokens, _m=model_name):
                # seconds is None for token-only attributions
                if seconds is not None:
                    sobs.step_phase_seconds.observe(
                        seconds, model=_m, phase=phase)
                if tokens:
                    sobs.step_tokens.observe(
                        tokens, model=_m, phase=phase)

            b.profiler.on_phase = on_phase
            b.compile_watch.tracer = sobs.tracer

            def on_recompile(fn, program, _m=model_name):
                sobs.recompiles.inc(model=_m, fn=fn)

            b.compile_watch.on_recompile = on_recompile
    if continuous:
        def collect_kv_blocks():
            # gauge refreshed at render: /metrics reads the LIVE pool,
            # not the pool as of the last admission/retirement
            for _m, _b in app[BATCHERS_KEY].items():
                if isinstance(_b, ContinuousBatcher):
                    sobs.kv_blocks.set(_b.kv_blocks_in_use(), model=_m)
                    sobs.kv_pool_cell_lanes.set(
                        _b.cengine.kv_cell[1], model=_m)
                    # the watch re-reads its functions' caches, so
                    # serving_recompiles_total is current in the scrape
                    _b.compile_watch.counts()
                    sobs.ssm_state_bytes.set(_b.ssm_state_bytes(), model=_m)
                    # a counter can only inc: the delta since last scrape
                    sobs.ssm_state_resets.inc(
                        _b.state_resets
                        - sobs.ssm_state_resets.value(model=_m), model=_m)
                    tier = _b._spill_tier
                    sobs.kv_spill_bytes.set(
                        tier.spilled_bytes if tier is not None else 0,
                        model=_m)

        sobs.registry.register_collector(collect_kv_blocks)

        def collect_goodput():
            # the goodput ledger is derived state: recompute at render
            # from the profiler's phase totals + high-water marks
            for _m, _b in app[BATCHERS_KEY].items():
                if isinstance(_b, ContinuousBatcher):
                    g = _b.profiler.goodput()
                    sobs.goodput.set(g["goodput_ratio"], model=_m)
                    sobs.bubble.set(g["bubble_fraction"], model=_m)
                    sobs.kv_high_water.set(
                        g["kv_blocks_high_water"], model=_m)

        sobs.registry.register_collector(collect_goodput)
    if tenancy is not None:
        # zero-seed the full per-tenant series set so dashboards see
        # every configured tenant (at 0) from the first scrape, and
        # pre-admit configured names into the label guard
        for _t in tenancy.names():
            sobs.tenant_guard.admit(_t)
            for _m in app[BATCHERS_KEY]:
                sobs.tenant_queue_depth.set(0, model=_m, tenant=_t)
                sobs.tenant_tokens.inc(0, model=_m, tenant=_t)
                sobs.tenant_preemptions.inc(0, model=_m, tenant=_t)
                sobs.prefix_hits.inc(0, model=_m, tenant=_t)
                sobs.prefix_misses.inc(0, model=_m, tenant=_t)
                for _r in THROTTLE_REASONS:
                    sobs.tenant_throttled.inc(
                        0, model=_m, tenant=_t, reason=_r)

        def _sync_counter(counter, total, **labels):
            # the ledger keeps cumulative totals; a counter can only
            # inc, so apply the delta since the last scrape
            cur = counter.value(**labels)
            if total > cur:
                counter.inc(total - cur, **labels)

        def collect_tenants():
            for _m, _b in app[BATCHERS_KEY].items():
                if not isinstance(_b, ContinuousBatcher):
                    continue
                for _t, s in _b.tenant_stats().items():
                    _t = sobs.tenant_guard.admit(_t)
                    sobs.tenant_queue_depth.set(
                        s.get("queued", 0), model=_m, tenant=_t)
                    _sync_counter(sobs.tenant_tokens, s["tokens"],
                                  model=_m, tenant=_t)
                    _sync_counter(sobs.tenant_preemptions,
                                  s["preempted"], model=_m, tenant=_t)
                    for _r, n in s["throttled"].items():
                        _sync_counter(sobs.tenant_throttled, n,
                                      model=_m, tenant=_t, reason=_r)

        sobs.registry.register_collector(collect_tenants)

    async def _close_batchers(app_):
        # ISSUE 3 bugfix: shutdown used to close() straight away, which
        # failed every in-flight generation with "server shutting down".
        # Drain first — stop admission, let admitted work decode to
        # completion within the grace window — THEN close (which only
        # has stragglers to fail, usually none).
        app_[DRAIN_KEY]["draining"] = True
        grace = app_[DRAIN_KEY]["grace_s"]
        for b in app_[BATCHERS_KEY].values():
            b.begin_drain()
        for b in app_[BATCHERS_KEY].values():
            if not await b.drain(timeout=grace):
                logging.getLogger(__name__).warning(
                    "shutdown drain timed out with %d request(s) "
                    "in flight; closing anyway", b.in_flight())
        for b in app_[BATCHERS_KEY].values():
            await b.close()

    app.on_cleanup.append(_close_batchers)

    async def request_timeline(request):
        # the TimelineStore keeps live AND finished requests (bounded,
        # oldest evicted): an operator pastes the X-Request-Id from a
        # slow response and reads where its time went
        rid = request.match_info["id"]
        for b in request.app[BATCHERS_KEY].values():
            if isinstance(b, ContinuousBatcher):
                tl = b.timelines.get(rid)
                if tl is not None:
                    return web.json_response(tl.to_dict())
        return web.json_response(
            {"error": f"no timeline for request {rid!r} (timelines "
                      "exist for continuous-batching requests only, "
                      "and the store is bounded)"},
            status=404)

    async def request_timelines_index(request):
        # enumeration surface for the scenario recorder: every id the
        # bounded stores still hold, oldest first per batcher
        ids: list[str] = []
        for b in request.app[BATCHERS_KEY].values():
            if isinstance(b, ContinuousBatcher):
                ids.extend(b.timelines.ids())
        return web.json_response({"requests": ids})

    async def debug_traces(request):
        # the shared traces handler plus this app's counter tracks
        # (ISSUE 8): phase budgets and pool fill ride the SAME Chrome
        # trace as the spans, namespaced per model
        try:
            payload = obs_lib.traces_response_payload(
                sobs.tracer, request.rel_url.query)
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e)) from None
        for _m, _b in request.app[BATCHERS_KEY].items():
            if isinstance(_b, ContinuousBatcher):
                obs_lib.merge_counter_tracks(
                    payload, _b.profiler.counter_events(prefix=_m))
                obs_lib.merge_counter_tracks(
                    payload,
                    _b.cache_ledger.counter_events(prefix=_m))
        return web.json_response(payload)

    async def debug_profile(request):
        # rolling step anatomy: per-phase p50/p95 + totals, the
        # goodput ledger, and per-fn retrace counts — the JSON the
        # "reading a step anatomy" walkthrough (docs/observability.md)
        # narrates
        models = {}
        for _m, _b in request.app[BATCHERS_KEY].items():
            if isinstance(_b, ContinuousBatcher):
                snap = _b.profiler.snapshot()
                snap["recompiles"] = _b.compile_watch.counts()
                snap["cache"] = _b.cache_anatomy()
                models[_m] = snap
        # the process's compile ledger: every program JAX built, the
        # costliest first, and where the start went
        return web.json_response(
            {"models": models, **obs_lib.compile_ledger().snapshot()})

    async def spec_toggle(request: web.Request):
        """POST /v1/spec {"enabled": bool} — runtime kill switch for
        speculative decoding on every model this replica serves (the
        fleet controller's disable_draft actuator fires this when the
        spec-acceptance SLO burns: a draft model that stops earning
        its keep costs a verify round per window for nothing). GET
        returns the current per-model state."""
        if request.method == "GET":
            return web.json_response({"models": _spec_state(request.app)})
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": "invalid JSON"},
                                     status=400)
        enabled = body.get("enabled") if isinstance(body, dict) else None
        if not isinstance(enabled, bool):
            return web.json_response(
                {"error": "body needs a boolean 'enabled'"}, status=400)
        for b in request.app[BATCHERS_KEY].values():
            if isinstance(b, ContinuousBatcher) \
                    and b.cengine.draft is not None:
                b.spec_enabled = enabled
        return web.json_response({"enabled": enabled,
                                  "models": _spec_state(request.app)})

    app.router.add_get("/healthz", healthz)
    app.router.add_get("/readyz", _ok)
    app.router.add_get("/v1/spec", spec_toggle)
    app.router.add_post("/v1/spec", spec_toggle)
    app.router.add_get("/metrics",
                       obs_endpoints.metrics_handler(sobs.registry))
    app.router.add_get("/debug/traces", debug_traces)
    app.router.add_get("/debug/profile", debug_profile)
    app.router.add_post("/drain", drain_endpoint)
    app.router.add_post("/v1/migrate/in", migrate_in)
    app.router.add_post("/v1/blocks/export", blocks_export)
    app.router.add_post("/v1/reload", reload_weights)
    app.router.add_get("/v1/models", list_models)
    app.router.add_get("/v1/requests/timelines",
                       request_timelines_index)
    app.router.add_get("/v1/requests/{id}/timeline", request_timeline)
    app.router.add_post("/v1/models/{name}:generate", generate)
    app.router.add_post("/v1/models/{name}:prefill", prefill_handoff)
    app.router.add_post("/v1/models/{name}:score", score)
    return app


async def _ok(request: web.Request):
    return web.json_response({"status": "ok"})


def _spec_state(app: web.Application) -> dict:
    """Per-model speculative-decoding state for /v1/spec."""
    out = {}
    for name, b in app[BATCHERS_KEY].items():
        has_draft = (isinstance(b, ContinuousBatcher)
                     and b.cengine.draft is not None)
        out[name] = {"draft": has_draft,
                     "spec_enabled": bool(has_draft and b.spec_enabled)}
    return out


def _in_flight(app: web.Application) -> int:
    return sum(b.in_flight() for b in app[BATCHERS_KEY].values())


def fleet_stats(app: web.Application) -> dict:
    """Routing/autoscale stats in the fleet heartbeat's vocabulary
    (summed over models — the fleet registry tracks replicas, not
    model shards). max_slots for the window batcher is its max_batch
    (the analog: requests co-scheduled per device call). `pool` is
    this replica's disaggregation role and `phase_seconds` folds the
    PhaseProfiler's cumulative totals into the two coarse phases the
    pool autoscaler splits on (prefill slices vs decode + speculative
    draft/verify)."""
    queue_depth = active = max_slots = 0
    kv_free = kv_total = 0
    phase_prefill = phase_decode = 0.0
    cache_digest: list = []
    for b in app[BATCHERS_KEY].values():
        if isinstance(b, ContinuousBatcher):
            queue_depth += len(b._pending)
            active += len(b._active)
            max_slots += len(b._free) + len(b._active)
            kv_free += b.cengine.pool.num_free
            kv_total += b.cengine.num_blocks
            cache_digest.extend(b._radix.heat_digest(16))
            totals = b.profiler.totals()
            phase_prefill += totals.get("prefill_chunk", 0.0)
            phase_decode += (totals.get("decode", 0.0)
                             + totals.get("draft", 0.0)
                             + totals.get("verify", 0.0))
        else:
            queue_depth += b._queue.qsize()
            active += len(b._inflight)
            max_slots += b.max_batch
    return {
        "queue_depth": queue_depth, "active_slots": active,
        "max_slots": max_slots, "kv_blocks_free": kv_free,
        "kv_blocks_total": kv_total,
        "draining": app[DRAIN_KEY]["draining"],
        "pool": app.get(POOL_KEY, "mixed"),
        # the rollout plane's confirmation signal: the RolloutManager
        # watches this label flip after a /v1/reload before promoting
        "version": app.get(MODEL_VERSION_KEY, ""),
        "phase_seconds": {"prefill": round(phase_prefill, 6),
                          "decode": round(phase_decode, 6)},
        # top-K hashed prefix heat (ISSUE 13): the router merges these
        # into the fleet heat map and scores counterfactual remote hits
        "cache_digest": cache_digest,
    }


async def healthz(request: web.Request):
    """Readiness with substance (the fleet router's health probe, and
    a gateway's): 200 only when the server admits work — not draining,
    engines loaded, admission queue below its shed depth. /readyz
    stays the bare liveness 200."""
    app = request.app
    if app[DRAIN_KEY]["draining"]:
        return web.json_response(
            {"status": "draining", "in_flight": _in_flight(app)},
            status=503)
    models = {}
    overloaded = False
    for name, b in app[BATCHERS_KEY].items():
        if isinstance(b, ContinuousBatcher):
            pending = len(b._pending)
            models[name] = {
                "pending": pending,
                "active_slots": len(b._active),
                "kv_blocks_free": b.cengine.pool.num_free,
                "kv_blocks_total": b.cengine.num_blocks,
            }
            overloaded = overloaded or pending >= b.max_pending
        else:
            models[name] = {"pending": b._queue.qsize(),
                            "active_slots": len(b._inflight)}
    if overloaded:
        return web.json_response(
            {"status": "overloaded", "models": models}, status=503)
    return web.json_response({"status": "ok", "models": models})


async def drain_endpoint(request: web.Request):
    """Stop admission NOW. Bodyless (legacy): in-flight generations
    keep decoding to completion and the response reports what is still
    in flight — the wait-out drain. With `{"migrate": true, "peers":
    [url, ...]}` (the router's instant-drain path): every active +
    pending sequence is EXPORTED (serving.migration wire records) and
    pushed round-robin to the peers' `/v1/migrate/in`, so the replica
    can exit in seconds instead of waiting out its longest generation.
    Sequences whose transfer fails everywhere still resume via the
    router's checkpoint failover (heartbeats carried their tokens-so-
    far) — migration only saves the peer the re-prefill. Standalone-
    usable either way: an operator can drain one server with one
    POST."""
    app = request.app
    app[DRAIN_KEY]["draining"] = True
    for b in app[BATCHERS_KEY].values():
        b.begin_drain()
    try:
        body = await request.json()
    except Exception:  # noqa: BLE001 — bodyless legacy drain
        body = {}
    if not (isinstance(body, dict) and body.get("migrate")):
        return web.json_response(
            {"draining": True, "in_flight": _in_flight(app)})
    import aiohttp

    peers = [str(p).rstrip("/") for p in body.get("peers", []) if p]
    sobs: ServingObs = app[OBS_KEY]
    t0 = time.monotonic()
    migrated = failed = 0
    async with aiohttp.ClientSession() as session:
        for name, b in app[BATCHERS_KEY].items():
            if not isinstance(b, ContinuousBatcher):
                continue
            with sobs.tracer.span("migrate.out", model=name):
                records = await b.export_sequences()
            for i, record in enumerate(records):
                ok = False
                for j in range(len(peers)):
                    peer = peers[(i + j) % len(peers)]
                    try:
                        async with session.post(
                                f"{peer}/v1/migrate/in",
                                json={"model": name, "record": record},
                                timeout=aiohttp.ClientTimeout(
                                    total=30)) as r:
                            if r.status == 200:
                                ok = True
                                break
                    except (aiohttp.ClientError, asyncio.TimeoutError,
                            OSError):
                        continue
                if ok:
                    migrated += 1
                    sobs.migration_out.inc(model=name)
                    kv = record.get("kv")
                    if kv:
                        sobs.migration_blocks.inc(
                            kv["n_full"], model=name, direction="out")
                else:
                    failed += 1
                    sobs.migration_failed.inc(model=name,
                                              direction="out")
    return web.json_response({
        "draining": True, "in_flight": _in_flight(app),
        "migrated": migrated, "failed": failed,
        "migrate_s": round(time.monotonic() - t0, 3)})


async def migrate_in(request: web.Request):
    """Import one migrated sequence (body: `{"model": name, "record":
    <serving.migration wire record>}`): validate geometry, allocate
    local blocks, scatter the KV payload, and index the prefix in the
    radix cache under the record's tenant namespace. The sequence is
    NOT enqueued here — the router re-dispatches the generation
    (replay prompt + remaining budget), which radix-hits the imported
    prefix and resumes token-identically under greedy sampling. Any
    failure — including a wedged transfer (`"wedge": true`, the chaos
    harness's mid-transfer fault) — rolls back completely: the
    destination pool frees every partially-imported block."""
    app = request.app
    try:
        body: dict[str, Any] = await request.json()
    except Exception:
        return web.json_response({"error": "invalid JSON"}, status=400)
    name = body.get("model", "")
    batcher = app[BATCHERS_KEY].get(name)
    if name not in app[ENGINES_KEY]:
        return web.json_response(
            {"error": f"no model {name!r}"}, status=404)
    if not isinstance(batcher, ContinuousBatcher):
        return web.json_response(
            {"error": "migration import requires continuous batching"},
            status=400)
    record = body.get("record")
    wedge = bool(body.get("wedge", False))
    sobs: ServingObs = app[OBS_KEY]
    try:
        with sobs.tracer.span("migrate.in", model=name, wedge=wedge):
            blocks = await batcher.import_sequence(record, wedge=wedge)
    except ValueError as e:
        sobs.migration_failed.inc(model=name, direction="in")
        return web.json_response({"error": str(e)}, status=400)
    except Exception as e:  # noqa: BLE001 — rolled back inside
        sobs.migration_failed.inc(model=name, direction="in")
        return web.json_response(
            {"error": f"{type(e).__name__}: {e}"}, status=500)
    sobs.migration_in.inc(model=name)
    if blocks:
        sobs.migration_blocks.inc(blocks, model=name, direction="in")
    rid = (str(record.get("request_id", ""))
           if isinstance(record, dict) else "")
    return web.json_response(
        {"imported": True, "blocks": blocks, "request_id": rid})


async def blocks_export(request: web.Request):
    """POST /v1/blocks/export — peer side of the fleet cache tier's
    pull path (ISSUE 19). Body: `migration.prefix_fetch_request`
    (`model`/`tokens`/`ns` plus the 16-hex first-block prefix hash the
    router's heat hint advertised). Exports this replica's cached
    full-block KV prefix of `tokens` as a migration wire record —
    exactly the `/v1/migrate/in` format with `out=[]`, so the
    requester imports it through `import_sequence` with geometry
    validation unchanged. 404 when the prefix is no longer cached
    (heat digests lag evictions); the requester books that as
    `outcome=miss` and prefills normally — this endpoint can make a
    remote hit cheap, never a local miss wrong."""
    app = request.app
    try:
        body: dict[str, Any] = await request.json()
    except Exception:
        return web.json_response({"error": "invalid JSON"}, status=400)
    name = body.get("model", "") if isinstance(body, dict) else ""
    if name not in app[ENGINES_KEY]:
        return web.json_response(
            {"error": f"no model {name!r}"}, status=404)
    batcher = app[BATCHERS_KEY].get(name)
    if not isinstance(batcher, ContinuousBatcher):
        return web.json_response(
            {"error": "block export requires continuous batching"},
            status=400)
    try:
        _model, tokens, ns = migration.validate_fetch_request(
            body, block_size=batcher.cengine.block_size)
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    sobs: ServingObs = app[OBS_KEY]
    rid = request.headers.get("X-Request-Id") or secrets.token_hex(8)
    with sobs.tracer.span("blocks.export", model=name):
        record = await batcher.export_prefix(tokens, ns=ns,
                                             request_id=rid)
    if record is None:
        return web.json_response(
            {"error": "prefix not cached"}, status=404)
    blocks = int(record["kv"]["n_full"]) if record.get("kv") else 0
    if blocks:
        sobs.migration_blocks.inc(blocks, model=name, direction="out")
    return web.json_response({"record": record, "blocks": blocks})


async def _peer_fetch_blocks(app, name: str, batcher, tokens,
                             peer: str) -> None:
    """Requester side of the fleet cache tier's pull path: the router
    said `peer`'s heat digest carries this prompt's first-block prefix
    (`X-KV-Peer`), so pull the cached blocks over
    `/v1/blocks/export` + `import_sequence` BEFORE admission — the
    prefill then radix-hits the imported prefix. Best-effort with the
    PR 12 degradation discipline: any failure (dead peer, geometry
    mismatch, stale digest, import race) books its outcome and falls
    through to plain prefill, token-identically. Only the shared
    namespace participates — heat hints join on un-namespaced prefix
    hashes, and tenant-isolated trees never leave their replica."""
    sobs: ServingObs = app[OBS_KEY]
    bs = batcher.cengine.block_size
    if len(tokens) < bs + 1:
        # no full block that planning could reuse (the planner always
        # leaves >= 1 token to prefill)
        return
    nodes, _partial, _plen = batcher._radix.match(tokens)
    if nodes:
        return  # locally cached already — the hint is stale
    try:
        req = migration.prefix_fetch_request(
            name, tokens, block_size=bs)
    except ValueError:
        return
    import aiohttp

    try:
        async with aiohttp.ClientSession() as session:
            async with session.post(
                    f"{peer.rstrip('/')}/v1/blocks/export", json=req,
                    timeout=aiohttp.ClientTimeout(total=30)) as r:
                if r.status == 404:
                    sobs.peer_fetch.inc(model=name, outcome="miss")
                    return
                if r.status != 200:
                    sobs.peer_fetch.inc(model=name, outcome="failed")
                    return
                payload = await r.json()
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
        sobs.peer_fetch.inc(model=name, outcome="failed")
        return
    record = (payload.get("record")
              if isinstance(payload, dict) else None)
    if record is None:
        sobs.peer_fetch.inc(model=name, outcome="miss")
        return
    try:
        with sobs.tracer.span("peer.fetch", model=name):
            blocks = await batcher.import_sequence(record)
    except Exception:  # noqa: BLE001 — import rolled back inside
        sobs.peer_fetch.inc(model=name, outcome="failed")
        return
    sobs.peer_fetch.inc(model=name, outcome="ok")
    if blocks:
        # booked at import time: these cells reach the prefill as a
        # radix hit, so they ALSO appear under source=reused at
        # admission — peer_fetched measures transfer traffic, the
        # admission sources measure what seeded each prefill
        sobs.prefill_tokens.observe(blocks * bs, model=name,
                                    source="peer_fetched")


# Mirrors fleet.rollout.valid_version — the serving side must stay
# importable without the fleet package (same pact as POOL_ROLES).
_VERSION_CHARS = frozenset(
    "abcdefghijklmnopqrstuvwxyz"
    "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789._-")


def _valid_version(v: Any) -> bool:
    return (isinstance(v, str) and 0 < len(v) <= 64
            and all(c in _VERSION_CHARS for c in v))


def _params_mismatch(old, new) -> str:
    """Structural compatibility check before a weight swap: same
    treedef, same leaf shapes and dtypes. The compiled decode/prefill
    functions are shape-specialized on the param tree — swapping in a
    differently-shaped tree would either retrace everything or crash
    mid-decode, so a mismatch rejects the reload with the old weights
    still live. Returns "" when compatible, else the reason."""
    import jax

    old_leaves, old_def = jax.tree.flatten(old)
    new_leaves, new_def = jax.tree.flatten(new)
    if old_def != new_def:
        return ("parameter tree structure differs from the live "
                "model's")
    for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
        o_shape = getattr(o, "shape", None)
        n_shape = getattr(n, "shape", None)
        o_dtype = getattr(o, "dtype", None)
        n_dtype = getattr(n, "dtype", None)
        if o_shape != n_shape or o_dtype != n_dtype:
            return (f"leaf {i}: incoming {n_shape}/{n_dtype} vs live "
                    f"{o_shape}/{o_dtype}")
    return ""


def _default_reloader(name: str, engine: InferenceEngine,
                      source: dict):
    """Materialize replacement params from a version's source spec —
    the same Orbax partial-restore path `python -m kubeflow_tpu.serving
    --checkpoint` boots from (params subtree only; pulling the Adam
    moments through disk to throw away would double the IO). Runs in
    an executor thread: restore is blocking IO. Deployments with other
    weight sources (seed-init tests, the loadtest) inject their own
    `reloader=` instead."""
    ckpt_dir = source.get("checkpoint", "")
    if not ckpt_dir:
        raise ValueError(
            "reload source needs a 'checkpoint' directory (no "
            "custom reloader is installed on this replica)")
    import jax
    import orbax.checkpoint as ocp

    from kubeflow_tpu.train.checkpoint import STATE_ITEM

    mgr = ocp.CheckpointManager(ckpt_dir, item_names=(STATE_ITEM,))
    try:
        step = source.get("step")
        if not isinstance(step, int):
            step = mgr.latest_step()
        if step is None:
            raise ValueError(f"no committed checkpoint under "
                             f"{ckpt_dir!r}")
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            engine.params)
        restored = mgr.restore(step, args=ocp.args.Composite(**{
            STATE_ITEM: ocp.args.PyTreeRestore(
                {"params": abstract}, partial_restore=True),
        }))
    finally:
        mgr.close()
    return restored[STATE_ITEM]["params"]


def _resume_admission(app: web.Application, draining: bool) -> None:
    """Undo a reload's drain: re-open every batcher and restore the
    door flag (a replica that was ALREADY draining when the reload
    arrived stays draining)."""
    for b in app[BATCHERS_KEY].values():
        b.end_drain()
    app[DRAIN_KEY]["draining"] = draining


async def reload_weights(request: web.Request):
    """POST /v1/reload — drain-then-swap live weight reload (the
    rollout plane's replica-side primitive, ISSUE 18). Body:

        {"version": "step-12",           # required, [A-Za-z0-9._-]{1,64}
         "model": "llama-tiny",          # optional when one model served
         "source": {"checkpoint": dir,   # what to load — consumed by the
                    "step": 12},         #   installed reloader
         "defect": {"ttft_delay_s": 2}}  # optional chaos (bad-version arm)

    Choreography: stop admission (drain door + every batcher), wait out
    in-flight generations (grace-bounded — the ROUTER migrates KV off
    the replica via /drain BEFORE calling this, so the wait is normally
    zero), materialize the new params in an executor under the gpu
    lock, verify tree/shape/dtype compatibility, swap `engine.params`,
    invalidate the radix prefix cache (cached KV describes the old
    weights), re-open admission, adopt the version label, and force a
    fleet re-registration so the router sees the flip without waiting a
    heartbeat period. Every failure path resumes admission with the OLD
    weights — a failed reload must leave a serving replica, not a
    drained one. A reload also RESETS any planted defect: rolling back
    to the prior version heals the chaos arm by construction."""
    app = request.app
    try:
        body: dict[str, Any] = await request.json()
    except Exception:
        return web.json_response({"error": "invalid JSON"}, status=400)
    if not isinstance(body, dict):
        return web.json_response({"error": "body must be an object"},
                                 status=400)
    version = body.get("version", "")
    if not _valid_version(version):
        return web.json_response(
            {"error": "version must be 1..64 chars of [A-Za-z0-9._-]"},
            status=400)
    engines = app[ENGINES_KEY]
    name = body.get("model", "")
    if not name and len(engines) == 1:
        name = next(iter(engines))
    if name not in engines:
        return web.json_response(
            {"error": f"no model {name!r} (serving "
                      f"{sorted(engines)})"}, status=404)
    source = body.get("source")
    if source is not None and not isinstance(source, dict):
        return web.json_response({"error": "source must be an object"},
                                 status=400)
    defect = body.get("defect")
    if defect is not None:
        delay = defect.get("ttft_delay_s", 0.0) \
            if isinstance(defect, dict) else None
        if not isinstance(delay, (int, float)) \
                or isinstance(delay, bool) or not 0 <= delay <= 30:
            return web.json_response(
                {"error": "defect.ttft_delay_s must be a number in "
                          "[0, 30]"}, status=400)
    engine = engines[name]
    sobs: ServingObs = app[OBS_KEY]
    was_draining = app[DRAIN_KEY]["draining"]
    app[DRAIN_KEY]["draining"] = True
    grace = app[DRAIN_KEY]["grace_s"]
    batchers = app[BATCHERS_KEY]
    for b in batchers.values():
        b.begin_drain()
    for b in batchers.values():
        if not await b.drain(timeout=grace):
            _resume_admission(app, was_draining)
            return web.json_response(
                {"error": f"drain timed out with {b.in_flight()} "
                          "request(s) in flight; weights unchanged"},
                status=409)
    reloader = app[RELOADER_KEY] or _default_reloader
    t0 = time.monotonic()
    try:
        with sobs.tracer.span("weights.reload", model=name,
                              version=version):
            async with app[GPU_LOCK_KEY]:
                params = await asyncio.get_event_loop() \
                    .run_in_executor(
                        None, reloader, name, engine,
                        dict(source or {}))
            why = _params_mismatch(engine.params, params)
            if why:
                raise ValueError(f"incompatible weights: {why}")
            engine.params = params
            b = batchers.get(name)
            if isinstance(b, ContinuousBatcher):
                # in_flight()==0 here (drained above): safe to drop
                # every cached block — they hold the OLD model's KV
                b.flush_cache()
    except ValueError as e:
        _resume_admission(app, was_draining)
        return web.json_response({"error": str(e)}, status=400)
    except Exception as e:  # noqa: BLE001 — old weights stay live
        _resume_admission(app, was_draining)
        return web.json_response(
            {"error": f"{type(e).__name__}: {e}"}, status=500)
    _resume_admission(app, False)
    app[MODEL_VERSION_KEY] = version
    app[DEFECT_KEY].clear()
    if isinstance(defect, dict):
        app[DEFECT_KEY].update(defect)
    # push the new version label to the fleet registry NOW — the
    # RolloutManager's confirm step watches for it, and a heartbeat
    # period of staleness would just slow every rollout phase down
    reg_state = app.get(FLEET_REG_KEY)
    register_fn = (reg_state or {}).get("register_fn")
    if register_fn is not None:
        try:
            await register_fn()
        except Exception:  # noqa: BLE001 — the beat loop will retry
            pass
    return web.json_response({
        "reloaded": True, "model": name, "version": version,
        "reload_s": round(time.monotonic() - t0, 3)})


async def prefill_handoff(request: web.Request):
    """POST /v1/models/{name}:prefill — the prefill half of a
    disaggregated handoff (ISSUE 12). Body: the usual `tokens`/`text`
    prompt plus an optional `"peer"` URL (the decode replica the
    pool-aware router picked). The replica prefills the prompt through
    its normal admission path (chunked prefill + the fused
    prefill/append kernel fill paged KV blocks, which the radix cache
    indexes), exports the full-block prefix as a migration wire
    record with `out=[]`, and pushes it to the peer's
    `/v1/migrate/in`. The response reports whether the handoff landed;
    the ROUTER then dispatches the real generation to the decode pool,
    where the imported prefix radix-hits and only the partial tail
    block prefills. Best-effort by design: any failure here just
    costs the decode replica one ordinary prefill — correctness never
    depends on this endpoint."""
    app = request.app
    if app[DRAIN_KEY]["draining"]:
        return web.json_response(
            {"error": "server is draining"}, status=503,
            headers={"Retry-After": "5"})
    name = request.match_info["name"]
    engine = app[ENGINES_KEY].get(name)
    if engine is None:
        return web.json_response(
            {"error": f"no model {name!r}"}, status=404)
    batcher = app[BATCHERS_KEY].get(name)
    if not isinstance(batcher, ContinuousBatcher):
        return web.json_response(
            {"error": "prefill handoff requires continuous batching"},
            status=400)
    try:
        body: dict[str, Any] = await request.json()
    except Exception:
        return web.json_response({"error": "invalid JSON"}, status=400)
    parsed = _parse_token_lists(body, app[TOKENIZER_KEY], min_len=1)
    if isinstance(parsed, web.Response):
        return parsed
    token_lists, _text_mode = parsed
    if len(token_lists) != 1:
        return web.json_response(
            {"error": "prefill handoff is single-prompt"}, status=400)
    toks = [int(t) for t in token_lists[0]]
    vocab = engine.cfg.vocab_size
    if min(toks) < 0 or max(toks) >= vocab:
        return web.json_response(
            {"error": f"token ids must be in [0, {vocab})"}, status=400)
    if len(toks) + 1 > engine.ec.max_len:
        return web.json_response(
            {"error": f"prompt {len(toks)} + 1 exceeds model max_len "
                      f"{engine.ec.max_len}"}, status=400)
    peer = body.get("peer", "")
    if not isinstance(peer, str):
        return web.json_response(
            {"error": "peer must be a URL string"}, status=400)
    rid = request.headers.get("X-Request-Id") or secrets.token_hex(8)
    sampling: dict[str, Any] = {"request_id": rid}
    tenant_hdr = request.headers.get("X-Tenant", "")
    if tenant_hdr:
        sampling["tenant"] = tenant_hdr
    sobs: ServingObs = app[OBS_KEY]
    t0 = time.monotonic()
    try:
        # max_new=1: the cheapest submission that runs the full prefill
        # path and leaves the prompt's blocks indexed in the radix tree
        # (at admission). The single decode token is discarded — the
        # decode replica owns the generation.
        with sobs.tracer.span("prefill.handoff", model=name):
            await batcher.submit(toks, 1,
                                 tuple(sorted(sampling.items())))
    except Throttled as e:
        return web.json_response(
            {"error": str(e)}, status=429,
            headers={"Retry-After": _retry_after_s(batcher, e)})
    except Overloaded as e:
        return web.json_response(
            {"error": f"server overloaded: {e}"}, status=429,
            headers={"Retry-After": _retry_after_s(batcher, e)})
    except MigratedAway as e:
        return web.json_response(
            {"error": str(e), "migrated": True}, status=503,
            headers={"Retry-After": "0"})
    record = await batcher.export_prefix(toks, request_id=rid)
    blocks = nbytes = 0
    if record is not None and record.get("kv"):
        blocks = int(record["kv"]["n_full"])
        nbytes = len(record["kv"]["k"]) + len(record["kv"]["v"])
    handoff = False
    if record is not None and peer:
        import aiohttp

        try:
            async with aiohttp.ClientSession() as session:
                async with session.post(
                        f"{peer.rstrip('/')}/v1/migrate/in",
                        json={"model": name, "record": record},
                        timeout=aiohttp.ClientTimeout(total=30)) as r:
                    handoff = r.status == 200
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            handoff = False
        if handoff:
            sobs.migration_out.inc(model=name)
            if blocks:
                sobs.migration_blocks.inc(
                    blocks, model=name, direction="out")
        else:
            sobs.migration_failed.inc(model=name, direction="out")
    return web.json_response({
        "prefilled": True, "handoff": handoff, "blocks": blocks,
        "bytes": nbytes if handoff else 0,
        "handoff_s": round(time.monotonic() - t0, 6),
        "request_id": rid})


def sequence_checkpoints(app: web.Application) -> list[dict]:
    """Lightweight resume records (tokens only, no KV) for every
    admitted request across models — the crash-failover feed
    `enable_fleet_registration` attaches to each heartbeat. When the
    registry sweeper declares this replica dead, the router replays
    them on a healthy peer from exactly where the stream stopped."""
    out = []
    for name, b in app[BATCHERS_KEY].items():
        if isinstance(b, ContinuousBatcher):
            for ck in b.checkpoints():
                out.append({"model": name, **ck})
    return out


async def list_models(request: web.Request):
    out = []
    for name, eng in request.app[ENGINES_KEY].items():
        entry = {
            "name": name,
            "family": eng.family.name,
            "max_len": eng.ec.max_len,
            "vocab_size": eng.cfg.vocab_size,
            "hidden_size": eng.cfg.hidden_size,
            "num_layers": eng.cfg.num_layers,
        }
        if eng.adapter_pack is not None:
            entry["adapters"] = sorted(eng.adapter_pack.names)
        batcher = request.app[BATCHERS_KEY].get(name)
        if batcher is not None:
            # coalescing evidence: for the window Batcher, mean
            # effective batch = batched_requests / batcher_calls
            # (counted at group SUCCESS, so failures can't inflate it;
            # pinned by tests/test_serving.py). For the continuous
            # batcher, calls = decode steps and the analog is
            # occupancy = tokens emitted per step.
            entry["batcher_calls"] = batcher.calls
            entry["batched_requests"] = batcher.requests
            if isinstance(batcher, ContinuousBatcher):
                entry["batcher_mode"] = "continuous"
                entry["occupancy"] = round(batcher.occupancy(), 3)
                entry["pending"] = len(batcher._pending)
                entry["active_slots"] = len(batcher._active)
                entry["pipeline_depth"] = batcher.pipeline_depth
                entry["kv_block_size"] = batcher.cengine.block_size
                entry["kv_pool_blocks"] = batcher.cengine.num_blocks
                entry["prefix_cache"] = batcher.prefix_cache_stats()
                tstats = batcher.tenant_stats()
                if tstats:
                    entry["tenants"] = tstats
                if batcher._prefixes:
                    entry["prefixes"] = {
                        n: len(t) for n, t in batcher._prefixes.items()}
            else:
                entry["batcher_mode"] = "window"
        out.append(entry)
    return web.json_response({"models": out})


# Server-side decode granularity for SSE streams: fixed (not a client
# knob) so a client sweeping max_new can mint at most STREAM_CHUNK
# distinct tail-chunk programs per prompt shape (plus prefill + the
# full chunk) — bounded, never one compile per max_new value.
STREAM_CHUNK = 8

# Retry-After ceiling: past this, a client should re-resolve (hit the
# fleet router / another replica) rather than camp on one server.
RETRY_AFTER_CAP_S = 60


def _retry_after_s(batcher, exc) -> str:
    """Dynamic Retry-After for a 429, replacing the old hardcoded "1".
    Throttled carries the tenant bucket's actual refill time; for
    Overloaded (queue full) estimate when the backlog clears: queue
    depth x the recent per-request service time, spread over the slot
    count. Clamped to [1, RETRY_AFTER_CAP_S] whole seconds."""
    if isinstance(exc, Throttled):
        est = exc.retry_after
    else:
        slots = max(1, len(batcher._free) + len(batcher._active))
        # service_ewma is 0.0 until the first completion; fall back to
        # a second per request — the old constant, now a floor
        est = (len(batcher._pending) + 1) \
            * (batcher.service_ewma or 1.0) / slots
    return str(max(1, min(RETRY_AFTER_CAP_S, math.ceil(est))))


async def _stream_generate(request, engine, arr, max_new, sampling,
                           text_mode, tokenizer):
    """SSE token streaming: `data: {"tokens": [[...]]}` per decoded
    chunk, then `data: {"done": true, ...}`. Same sampling law as the
    one-shot path (engine.generate_stream's equality guarantee); the
    stream ends early once every row hits EOS."""
    import json as _json

    # Build the generator BEFORE sending SSE headers: generate_stream
    # validates eagerly, so an argument the handler's own checks missed
    # is still a clean 400 here — never a 200 that dies mid-stream.
    try:
        gen = engine.generate_stream(
            jnp.asarray(arr), max_new=max_new, chunk=STREAM_CHUNK,
            **sampling)
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    sobs = request.app[OBS_KEY]
    model = request.match_info.get("name", "")
    headers = {
        "Content-Type": "text/event-stream",
        "Cache-Control": "no-cache",
        "X-Accel-Buffering": "no",
    }
    # The obs middleware cannot add headers after prepare(); stream
    # responses carry their trace id from birth.
    trace_id = sobs.tracer.current_trace_id()
    if trace_id:
        headers["X-Trace-Id"] = trace_id
    resp = web.StreamResponse(headers=headers)
    await resp.prepare(request)
    loop = asyncio.get_event_loop()
    chunks: list[np.ndarray] = []
    error: str | None = None
    with sobs.tracer.span("stream.decode", model=model):
        while True:
            # Lock only around the device work, NOT the client write: a
            # slow-reading client must back-pressure its own stream,
            # never stall every other request behind the GPU lock.
            # Other requests interleave between chunks (each chunk call
            # is self-contained).
            try:
                async with request.app[GPU_LOCK_KEY]:
                    part = await loop.run_in_executor(
                        None, lambda: next(gen, None))
            except Exception as e:  # noqa: BLE001
                # Same terminal-event contract as _stream_continuous:
                # headers are out, so raising would abort the connection
                # indistinguishably from a network drop. Log server-side
                # — the raise-through path used to leave an aiohttp
                # traceback, and a device falling over mid-stream must
                # stay diagnosable from the server logs.
                logging.getLogger(__name__).exception(
                    "decode failed mid-stream")
                error = f"{type(e).__name__}: {e}"
                break
            if part is None:
                break
            chunks.append(part)
            _observe_first_token(request, model)
            await resp.write(
                b"data: " + _json.dumps(
                    {"tokens": part.tolist()}).encode() + b"\n\n")
    total = int(sum(c.shape[1] for c in chunks))
    if error is not None:
        final: dict[str, Any] = {"error": error, "total": total}
    else:
        final = {"done": True, "total": total}
        if text_mode and chunks:
            ids = np.concatenate(chunks, axis=1)[0].tolist()
            final["text"] = (tokenizer.decode(ids) if tokenizer
                             else byte_decode(
                                 ids,
                                 on_dropped=lambda n: sobs.dropped_tokens
                                 .inc(n, model=model)))
    await resp.write(b"data: " + _json.dumps(final).encode() + b"\n\n")
    await resp.write_eof()
    return resp


async def _stream_continuous(request, batcher, arr, max_new, sampling,
                             text_mode, tokenizer):
    """SSE token streaming through the continuous batcher: one event
    per decoded token (`data: {"tokens": [[t]]}`), then the same final
    `{"done": true, ...}` record as `_stream_generate`. Concurrent
    streams SHARE the slot batch — each consumer awaits only its own
    tokens, never the GPU lock (the batcher's worker owns that)."""
    import json as _json

    try:
        # enqueue BEFORE the SSE headers: admission errors (Overloaded
        # included) must be a clean 429/4xx, never a mid-stream abort —
        # a depth pre-check alone would race a concurrent admission
        fut, q = batcher.open_stream(
            arr[0].tolist(), max_new, tuple(sorted(sampling.items())))
    except Throttled as e:
        return web.json_response(
            {"error": str(e)}, status=429,
            headers={"Retry-After": _retry_after_s(batcher, e)})
    except Overloaded as e:
        return web.json_response(
            {"error": f"server overloaded: {e}"}, status=429,
            headers={"Retry-After": _retry_after_s(batcher, e)})
    sobs = request.app[OBS_KEY]
    model = request.match_info.get("name", "")
    headers = {
        "Content-Type": "text/event-stream",
        "Cache-Control": "no-cache",
        "X-Accel-Buffering": "no",
    }
    trace_id = sobs.tracer.current_trace_id()
    if trace_id:
        headers["X-Trace-Id"] = trace_id
    rid = sampling.get("request_id")
    if rid:
        headers["X-Request-Id"] = rid
    resp = web.StreamResponse(headers=headers)
    await resp.prepare(request)
    ids: list[int] = []
    error: str | None = None
    try:
        with sobs.tracer.span("stream.continuous", model=model):
            while True:
                tok = await q.get()
                if tok is None:
                    break
                ids.append(tok)
                _observe_first_token(request, model)
                await resp.write(
                    b"data: " + _json.dumps({"tokens": [[tok]]}).encode()
                    + b"\n\n")
        try:
            await fut  # surface admission/step errors after drain
        except Exception as e:  # noqa: BLE001
            # Headers are already sent: a raise here would abort the
            # connection, indistinguishable from a network drop. Emit
            # a deterministic terminal error event instead (and keep
            # the server-side trail — see _stream_generate).
            logging.getLogger(__name__).exception(
                "continuous decode failed mid-stream")
            error = f"{type(e).__name__}: {e}"
    finally:
        if not fut.done():
            fut.cancel()  # consumer gone: release the slot
    if error is not None:
        final: dict[str, Any] = {"error": error, "total": len(ids)}
    else:
        final = {"done": True, "total": len(ids)}
        if text_mode and ids:
            final["text"] = (tokenizer.decode(ids) if tokenizer
                             else byte_decode(
                                 ids,
                                 on_dropped=lambda n: sobs.dropped_tokens
                                 .inc(n, model=model)))
    await resp.write(b"data: " + _json.dumps(final).encode() + b"\n\n")
    await resp.write_eof()
    return resp


def _parse_token_lists(body: dict, tokenizer, *, min_len: int):
    """Materialize token rows from "text" or "tokens" — the ONE
    definition of request-token parsing for the generate and score
    doors (drifted copies once meant the two validated differently).
    Returns (token_lists, text_mode) or a 400 Response. `min_len` is
    the per-row floor: 1 for generation, 2 for teacher-forced scoring
    (a single token has nothing to predict)."""
    text_mode = "text" in body
    if text_mode:
        if not isinstance(body["text"], str):
            return web.json_response(
                {"error": "'text' must be a string"}, status=400)
        token_lists = [tokenizer.encode(body["text"], bos=True)
                       if tokenizer else byte_encode(body["text"])]
        if len(token_lists[0]) < min_len:
            return web.json_response(
                {"error": f"text encodes to fewer than {min_len} "
                          "tokens (at least 2 needed to score)"
                 if min_len > 1 else "text encodes to no tokens"},
                status=400)
    elif "tokens" in body:
        token_lists = body["tokens"]
        if (not isinstance(token_lists, list) or not token_lists
                or not all(
                    isinstance(t, list) and len(t) >= min_len
                    and all(isinstance(x, int) and not isinstance(x, bool)
                            for x in t)
                    for t in token_lists)):
            return web.json_response(
                {"error": "tokens must be a non-empty list of integer "
                          f"token-id lists with at least {min_len} "
                          "token(s) each"}, status=400)
    else:
        return web.json_response(
            {"error": "body needs 'text' or 'tokens'"}, status=400)
    return token_lists, text_mode


async def score(request: web.Request):
    """Teacher-forced scoring: log P(token_i | prefix) for a given
    sequence — the perplexity/eval door (lm-eval style). Body:
    {"tokens": [[...]]} or {"text": "..."}; response: per-position
    logprobs (s-1 per row), each row's total, and token count."""
    if request.app[DRAIN_KEY]["draining"]:
        return web.json_response(
            {"error": "server is draining"}, status=503,
            headers={"Retry-After": "5"})
    name = request.match_info["name"]
    engine = request.app[ENGINES_KEY].get(name)
    if engine is None:
        return web.json_response(
            {"error": f"no model {name!r}"}, status=404)
    try:
        body: dict[str, Any] = await request.json()
    except Exception:
        return web.json_response({"error": "invalid JSON"}, status=400)
    tokenizer = request.app[TOKENIZER_KEY]
    parsed = _parse_token_lists(body, tokenizer, min_len=2)
    if isinstance(parsed, web.Response):
        return parsed
    token_lists, _ = parsed
    if len({len(t) for t in token_lists}) != 1:
        return web.json_response(
            {"error": "all rows must share a length (static shapes)"},
            status=400)
    if len(token_lists[0]) > engine.ec.max_len:
        return web.json_response(
            {"error": f"sequence {len(token_lists[0])} exceeds model "
                      f"max_len {engine.ec.max_len}"}, status=400)
    vocab = engine.cfg.vocab_size
    try:
        arr = np.asarray(token_lists, dtype=np.int32)
    except OverflowError:
        return web.json_response(
            {"error": f"token ids must be in [0, {vocab})"}, status=400)
    if arr.min() < 0 or arr.max() >= vocab:
        return web.json_response(
            {"error": f"token ids must be in [0, {vocab})"}, status=400)

    sobs: ServingObs = request.app[OBS_KEY]
    with sobs.tracer.span("engine.score", model=name,
                          batch=int(arr.shape[0])):
        async with request.app[GPU_LOCK_KEY]:
            lps = await asyncio.get_event_loop().run_in_executor(
                None, sobs.tracer.wrap(
                    lambda: np.asarray(engine.score(jnp.asarray(arr))),
                    "device.score"))
    return web.json_response({
        "logprobs": [[round(float(x), 6) for x in row] for row in lps],
        "total": [round(float(row.sum()), 6) for row in lps],
        "count": int(arr.shape[1] - 1),
    })


async def generate(request: web.Request):
    if request.app[DRAIN_KEY]["draining"]:
        # admission stops at the door; in-flight work keeps decoding.
        # 503 (not 429): the SERVER is going away — a client or the
        # fleet router should try another replica, not wait this one out
        return web.json_response(
            {"error": "server is draining"}, status=503,
            headers={"Retry-After": "5"})
    name = request.match_info["name"]
    engine = request.app[ENGINES_KEY].get(name)
    if engine is None:
        return web.json_response(
            {"error": f"no model {name!r}"}, status=404)
    # Chaos defect planted by /v1/reload (the rollout loadtest's bad-
    # version arm): a deliberate TTFT stall the canary judge must catch.
    _delay = request.app[DEFECT_KEY].get("ttft_delay_s", 0.0)
    if _delay:
        await asyncio.sleep(float(_delay))
    # tenant identity is a HEADER, not a body field: proxies (the fleet
    # router) forward it without parsing the payload, and a gateway can
    # inject it from auth without rewriting bodies. Absent/unknown
    # resolves to the `default` tenant inside the batcher.
    tenant_hdr = request.headers.get("X-Tenant", "")
    req_id: str | None = None  # minted on continuous-batcher paths
    try:
        body: dict[str, Any] = await request.json()
    except Exception:
        return web.json_response({"error": "invalid JSON"}, status=400)

    tokenizer = request.app[TOKENIZER_KEY]
    parsed = _parse_token_lists(body, tokenizer, min_len=1)
    if isinstance(parsed, web.Response):
        return parsed
    token_lists, text_mode = parsed

    max_new = body.get("max_new", 16)
    if not isinstance(max_new, int) or isinstance(max_new, bool) \
            or max_new < 1:
        return web.json_response(
            {"error": "max_new must be a positive integer"}, status=400)

    # Per-request sampling (dynamic in the compiled scan — no recompile).
    sampling: dict[str, Any] = {}
    temperature = body.get("temperature")
    if temperature is not None:
        # isfinite also rejects NaN/Infinity, which json.loads accepts
        # and which would otherwise pass a `< 0` check silently.
        if not isinstance(temperature, (int, float)) \
                or isinstance(temperature, bool) \
                or not math.isfinite(temperature) or temperature < 0:
            return web.json_response(
                {"error": "temperature must be a finite number >= 0"},
                status=400)
        sampling["temperature"] = float(temperature)
    top_k = body.get("top_k")
    if top_k is not None:
        if not isinstance(top_k, int) or isinstance(top_k, bool) \
                or top_k < 0 or top_k >= 2**31:
            return web.json_response(
                {"error": "top_k must be an integer in [0, 2**31)"},
                status=400)
        sampling["top_k"] = top_k
    top_p = body.get("top_p")
    if top_p is not None:
        if not isinstance(top_p, (int, float)) \
                or isinstance(top_p, bool) or not 0.0 < top_p <= 1.0:
            return web.json_response(
                {"error": "top_p must be in (0, 1]"}, status=400)
        sampling["top_p"] = float(top_p)
    adapter = body.get("adapter", "")
    if not isinstance(adapter, str):
        return web.json_response(
            {"error": "adapter must be a string"}, status=400)
    if adapter:
        if engine.adapter_pack is None:
            return web.json_response(
                {"error": f"model {name!r} has no adapters loaded"},
                status=400)
        try:
            engine.adapter_pack.resolve(adapter)
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
    prefix = body.get("prefix", "")
    if not isinstance(prefix, str):
        return web.json_response(
            {"error": "prefix must be a string"}, status=400)
    logprobs = body.get("logprobs", False)
    if not isinstance(logprobs, bool):
        return web.json_response(
            {"error": "logprobs must be a boolean"}, status=400)
    stop = body.get("stop", [])
    if (not isinstance(stop, list) or len(stop) > 4
            or not all(isinstance(s, list) and 0 < len(s) <= 16
                       and all(isinstance(t, int)
                               and not isinstance(t, bool) for t in s)
                       for s in stop)):
        return web.json_response(
            {"error": "stop must be up to 4 non-empty token-id lists "
                      "of at most 16 tokens"}, status=400)
    lens = {len(t) for t in token_lists}
    if len(lens) != 1:
        return web.json_response(
            {"error": "all prompts in a batch must share a length "
                      "(static shapes); pad client-side"}, status=400)
    prompt_len = lens.pop()
    if prompt_len + max_new > engine.ec.max_len:
        return web.json_response(
            {"error": f"prompt {prompt_len} + max_new {max_new} exceeds "
                      f"model max_len {engine.ec.max_len}"}, status=400)
    if prefix:
        pbatcher = request.app[BATCHERS_KEY].get(name)
        if not isinstance(pbatcher, ContinuousBatcher):
            return web.json_response(
                {"error": "prefix requires continuous batching"},
                status=400)
        if prefix not in pbatcher._prefixes:
            return web.json_response(
                {"error": f"unknown prefix {prefix!r}; registered: "
                          f"{sorted(pbatcher._prefixes)}"}, status=400)
        if adapter:
            return web.json_response(
                {"error": "prefix does not compose with adapter"},
                status=400)
        if len(token_lists) != 1:
            return web.json_response(
                {"error": "prefix requests are single-prompt"},
                status=400)
        if body.get("speculative", False) is True:
            return web.json_response(
                {"error": "prefix does not compose with speculative"},
                status=400)
        plen = len(pbatcher._prefixes[prefix])
        if plen + prompt_len + max_new > engine.ec.max_len:
            return web.json_response(
                {"error": f"prefix {plen} + prompt {prompt_len} + "
                          f"max_new {max_new} exceeds model max_len "
                          f"{engine.ec.max_len}"}, status=400)
        sampling["prefix"] = prefix
    vocab = engine.cfg.vocab_size
    try:
        arr = np.asarray(token_lists, dtype=np.int32)
    except OverflowError:
        return web.json_response(
            {"error": f"token ids must be in [0, {vocab})"}, status=400)
    if arr.min() < 0 or arr.max() >= vocab:
        return web.json_response(
            {"error": f"token ids must be in [0, {vocab})"}, status=400)

    # Fleet cache tier (ISSUE 19): the router attaches X-KV-Peer when
    # a peer's heat digest carries this prompt's first-block prefix
    # and the chosen replica's doesn't — pull the hot blocks before
    # admission so the prefill radix-hits instead of recomputing.
    # Strictly best-effort: every failure path degrades to the plain
    # prefill this request would have run anyway.
    peer_hint = request.headers.get("X-KV-Peer", "")
    if (peer_hint and not prefix and arr.shape[0] == 1
            and not request.app[DRAIN_KEY]["draining"]):
        peer_batcher = request.app[BATCHERS_KEY].get(name)
        if isinstance(peer_batcher, ContinuousBatcher):
            await _peer_fetch_blocks(request.app, name, peer_batcher,
                                     arr[0].tolist(), peer_hint)

    speculative = body.get("speculative", False)
    if not isinstance(speculative, bool):
        return web.json_response(
            {"error": "speculative must be a boolean"}, status=400)
    # max_new is jit-static on the speculative and direct paths (the
    # Batcher already buckets its groups): bucket it the same way so a
    # client sweeping max_new mints O(log max_len) compiles, not one
    # per value, while holding the GPU lock. Generation runs to the
    # bucket; the response is trimmed back to the client's ask below.
    max_new_req = max_new
    max_new = Batcher._bucket(max_new, engine.ec.max_len - prompt_len)
    if max_new < max_new_req:  # cap clamped below the ask — cannot happen
        max_new = max_new_req  # (capacity was checked), but stay safe
    gamma = body.get("gamma", 4)
    if not isinstance(gamma, int) or isinstance(gamma, bool) or gamma < 1:
        return web.json_response(
            {"error": "gamma must be a positive integer"}, status=400)
    stream = body.get("stream", False)
    if not isinstance(stream, bool):
        return web.json_response(
            {"error": "stream must be a boolean"}, status=400)
    if stream:
        if speculative:
            return web.json_response(
                {"error": "stream does not compose with speculative"},
                status=400)
        if stop:
            # a streamed stop would need partial-match buffering to
            # avoid emitting a half-completed stop sequence; explicit
            # 400 beats silently different trimming semantics
            return web.json_response(
                {"error": "stop does not compose with stream"},
                status=400)
        if logprobs:
            return web.json_response(
                {"error": "logprobs does not compose with stream"},
                status=400)
        cbatcher = request.app[BATCHERS_KEY].get(name)
        if isinstance(cbatcher, ContinuousBatcher) and arr.shape[0] == 1:
            # a continuous-batched stream shares the slot batch with
            # every other request instead of holding the GPU per chunk
            if adapter:
                sampling["adapter"] = adapter
            if tenant_hdr:
                # rides the sampling channel like adapter/prefix; the
                # batcher pops it back out before grouping
                sampling["tenant"] = tenant_hdr
            # timeline key; _stream_continuous echoes X-Request-Id.
            # The fleet router mints its own id so a failover resume
            # keeps the same timeline — honor it when present.
            sampling["request_id"] = (
                request.headers.get("X-Request-Id")
                or secrets.token_hex(8))
            return await _stream_continuous(
                request, cbatcher, arr, max_new_req, sampling,
                text_mode, tokenizer)
        if adapter:
            return web.json_response(
                {"error": "adapter streaming requires continuous "
                          "batching (create_serving_app continuous)"},
                status=400)
        return await _stream_generate(
            request, engine, arr, max_new_req, sampling, text_mode,
            tokenizer)

    resp_extra: dict[str, Any] = {}
    if speculative and logprobs:
        return web.json_response(
            {"error": "logprobs does not compose with speculative"},
            status=400)
    if speculative and adapter:
        return web.json_response(
            {"error": "adapter does not compose with speculative"},
            status=400)
    if speculative:
        spec = request.app[SPEC_KEY].get(name)
        if spec is None:
            return web.json_response(
                {"error": f"no draft model registered for {name!r}"},
                status=400)
        if arr.shape[0] != 1:
            return web.json_response(
                {"error": "speculative decoding is batch-1"}, status=400)
        # gamma is jit-static: bucket it to a power of two <= 8 BEFORE
        # the capacity check, so a client sweeping gamma cannot mint
        # unbounded compiles while holding the GPU lock (gamma is
        # purely a perf knob — bucketing never changes the output law)
        g = 1
        while g * 2 <= min(gamma, 8):
            g *= 2
        gamma = g
        # the draft's cache must hold the window too (it is usually the
        # smaller model — and often configured with a smaller bucket).
        # The bucketed max_new shrinks back toward the exact ask before
        # rejecting: only the CLIENT's numbers may cause a 400.
        cap = min(engine.ec.max_len, spec.draft.ec.max_len)
        if prompt_len + max_new + gamma > cap:
            max_new = max(cap - prompt_len - gamma, max_new_req)
        if prompt_len + max_new_req + gamma > cap:
            return web.json_response(
                {"error": f"prompt {prompt_len} + max_new {max_new_req} "
                          f"+ gamma {gamma} exceeds model max_len {cap}"},
                status=400)

        def run_spec():
            toks_, stats = spec.generate(
                jnp.asarray(arr), max_new=max_new, gamma=gamma,
                **sampling)
            return np.asarray(toks_), stats

        sobs: ServingObs = request.app[OBS_KEY]
        with sobs.tracer.span("engine.speculative", model=name,
                              gamma=gamma, max_new=max_new):
            async with request.app[GPU_LOCK_KEY]:
                toks, stats = await asyncio.get_event_loop(
                ).run_in_executor(
                    None, sobs.tracer.wrap(run_spec, "device.generate"))
        _observe_first_token(request, name)
        # SpeculativeEngine does not special-case EOS; match the plain
        # path's contract (post-EOS tail pinned to EOS) server-side so
        # the two modes are interchangeable for clients.
        eos = engine.ec.eos_token
        if eos is not None:
            hits = np.where(toks[0] == eos)[0]
            if hits.size:
                toks = toks.copy()
                toks[0, hits[0]:] = eos
        resp_extra["speculative"] = {
            "acceptance_rate": round(stats.acceptance_rate, 4),
            "proposed": int(stats.proposed),
            "accepted": int(stats.accepted),
            "gamma": gamma,  # the EFFECTIVE (bucketed) window
        }
    elif (batcher := request.app[BATCHERS_KEY].get(name)) is not None \
            and arr.shape[0] == 1 \
            and (not adapter or isinstance(batcher, ContinuousBatcher)) \
            and (not logprobs or isinstance(batcher, ContinuousBatcher)):
        # single-prompt requests ride the dynamic batcher; explicit
        # client-side batches keep their one-shot path. Adapter
        # requests ride the CONTINUOUS batcher (per-slot ids); under a
        # window batcher they fall through to the direct path, which
        # supports adapters batch-uniformly.
        if adapter:
            sampling["adapter"] = adapter
        submit_sampling = dict(sampling)
        if tenant_hdr and isinstance(batcher, ContinuousBatcher):
            # NOT under the window Batcher: its sampling tuple is the
            # coalescing group key, and a per-tenant key would split
            # batches by identity for no scheduling benefit
            submit_sampling["tenant"] = tenant_hdr
        if isinstance(batcher, ContinuousBatcher):
            # server-minted id keys the token timeline
            # (/v1/requests/{id}/timeline); echoed as X-Request-Id.
            # Router-supplied ids win so failover resumes share one
            # timeline across replicas.
            req_id = (request.headers.get("X-Request-Id")
                      or secrets.token_hex(8))
            submit_sampling["request_id"] = req_id
        if stop and isinstance(batcher, ContinuousBatcher):
            # the continuous batcher retires the slot the moment a
            # stop sequence completes (compute freed); the window
            # batcher runs its group to the group max and the shared
            # post-trim below applies the semantics
            submit_sampling["stop"] = tuple(tuple(s) for s in stop)
        sobs: ServingObs = request.app[OBS_KEY]
        try:
            with sobs.tracer.span("batcher.submit", model=name,
                                  max_new=max_new_req):
                if logprobs and isinstance(batcher, ContinuousBatcher):
                    ids, req_lps = await batcher.submit(
                        arr[0].tolist(), max_new_req,
                        tuple(sorted(submit_sampling.items())),
                        with_logprobs=True)
                    lp_rows = [list(req_lps)]
                else:
                    ids = await batcher.submit(
                        arr[0].tolist(), max_new_req,
                        tuple(sorted(submit_sampling.items())))
                    lp_rows = None
        except Throttled as e:
            return web.json_response(
                {"error": str(e)}, status=429,
                headers={"Retry-After": _retry_after_s(batcher, e)})
        except Overloaded as e:
            return web.json_response(
                {"error": f"server overloaded: {e}"}, status=429,
                headers={"Retry-After": _retry_after_s(batcher, e)})
        except MigratedAway as e:
            # instant drain shipped this sequence to a peer; the
            # router treats the 503 as retryable and resumes from its
            # checkpoint (or the migrated prefix) elsewhere
            return web.json_response(
                {"error": str(e), "migrated": True}, status=503,
                headers={"Retry-After": "0"})
        _observe_first_token(request, name)
        toks = np.asarray([ids], np.int32)
    else:
        if adapter:
            sampling["adapter"] = adapter  # engine.generate kwarg

        def run_direct():
            out = engine.generate(jnp.asarray(arr), max_new=max_new,
                                  return_logprobs=logprobs, **sampling)
            if logprobs:
                t, lp = out
                return np.asarray(t), np.asarray(lp)
            return np.asarray(out), None

        sobs = request.app[OBS_KEY]
        with sobs.tracer.span("engine.generate", model=name,
                              batch=int(arr.shape[0]),
                              max_new=max_new):
            async with request.app[GPU_LOCK_KEY]:
                toks, lp_arr = await asyncio.get_event_loop(
                ).run_in_executor(
                    None, sobs.tracer.wrap(run_direct, "device.generate"))
        sobs.batch_size.observe(arr.shape[0], model=name)
        _observe_first_token(request, name)
        lp_rows = (lp_arr[:, :max_new_req].tolist()
                   if lp_arr is not None else None)
    toks = toks[:, :max_new_req]  # trim the bucket back to the ask
    rows = toks.tolist()
    if speculative:
        lp_rows = None
    if stop:
        # OpenAI semantics on every path: output ends BEFORE the
        # earliest stop-sequence occurrence (the continuous batcher
        # already trimmed its suffix; re-scanning is a no-op there)
        rows = [_apply_stop(r, stop) for r in rows]
        if lp_rows is not None:
            lp_rows = [lp[:len(r)] for lp, r in zip(lp_rows, rows)]
    resp: dict[str, Any] = {"tokens": rows, **resp_extra}
    if logprobs and lp_rows is not None:
        # uniform contract on every path: entries cover tokens up to
        # AND INCLUDING the row's first EOS — the direct path's
        # post-EOS tail describes pre-forcing samples of the padded
        # EOS tokens, which would silently corrupt a client's sequence
        # total (the continuous path already stops computing there)
        eos = engine.ec.eos_token
        out_lps = []
        for lp, r in zip(lp_rows, rows):
            n = len(r)
            if eos is not None and eos in r:
                n = r.index(eos) + 1
            out_lps.append([round(float(x), 6) for x in lp[:n]])
        resp["logprobs"] = out_lps
    if text_mode:
        resp["text"] = (tokenizer.decode(rows[0]) if tokenizer
                        else byte_decode(
                            rows[0],
                            on_dropped=lambda n: sobs.dropped_tokens
                            .inc(n, model=name)))
    return web.json_response(
        resp, headers={"X-Request-Id": req_id} if req_id else None)


def _apply_stop(row: list[int], stop: list[list[int]]) -> list[int]:
    """Cut `row` before the earliest occurrence of any stop sequence."""
    cut = None
    for seq in stop:
        n = len(seq)
        for i in range(len(row) - n + 1):
            if row[i:i + n] == seq:
                cut = i if cut is None else min(cut, i)
                break
    return row if cut is None else row[:cut]


def enable_fleet_registration(app: web.Application, router_url: str,
                              advertise_url: str, *,
                              replica_id: str | None = None,
                              period_s: float = 2.0) -> None:
    """Wire this replica into a fleet router (kubeflow_tpu.fleet):
    register on startup, heartbeat `fleet_stats` every `period_s`
    (re-registering when the router answers 404 — it restarted and
    lost its table), deregister on cleanup. Router unavailability is
    never fatal: the replica serves standalone and keeps retrying —
    the router and replicas boot in either order."""
    import aiohttp

    router = router_url.rstrip("/")
    state: dict[str, Any] = {
        "router": router, "advertise": advertise_url,
        "id": replica_id or advertise_url, "period_s": period_s,
        "session": None, "task": None,
    }
    app[FLEET_REG_KEY] = state
    log = logging.getLogger(__name__)

    def _payload(app_) -> dict:
        return {"id": state["id"], "url": state["advertise"],
                "models": sorted(app_[ENGINES_KEY]),
                "checkpoints": sequence_checkpoints(app_),
                **fleet_stats(app_)}

    async def _register(app_) -> bool:
        try:
            async with state["session"].post(
                    f"{router}/fleet/register", json=_payload(app_),
                    timeout=aiohttp.ClientTimeout(total=5)) as r:
                return r.status == 200
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            return False

    async def _register_now() -> bool:
        # /v1/reload forces an immediate re-registration so the router
        # sees the new version label without waiting a heartbeat period
        if state["session"] is None:
            return False
        return await _register(app)

    state["register_fn"] = _register_now

    async def _beat_loop(app_):
        while True:
            await asyncio.sleep(state["period_s"])
            try:
                async with state["session"].post(
                        f"{router}/fleet/heartbeat",
                        json=_payload(app_),
                        timeout=aiohttp.ClientTimeout(total=5)) as r:
                    if r.status == 404:
                        await _register(app_)
            except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
                pass  # router down/restarting: keep beating

    async def _start(app_):
        state["session"] = aiohttp.ClientSession()
        if not await _register(app_):
            log.warning("fleet: could not register with router %s "
                        "(will keep retrying via heartbeat)", router)
        state["task"] = asyncio.get_event_loop().create_task(
            _beat_loop(app_))

    async def _stop(app_):
        task = state["task"]
        if task is not None:
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
        if state["session"] is not None:
            try:
                async with state["session"].post(
                        f"{router}/fleet/deregister",
                        json={"id": state["id"]},
                        timeout=aiohttp.ClientTimeout(total=5)):
                    pass
            except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
                pass
            await state["session"].close()

    app.on_startup.append(_start)
    # deregister BEFORE the drain-and-close hook: the router must stop
    # routing here while the drain window is still finishing in-flight
    app.on_cleanup.insert(0, _stop)
