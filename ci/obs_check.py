"""Metrics-contract gate: scrape a live platform app, parse STRICTLY.

`make obs-check` (and the observability CI workflow) boots the
in-process Cluster + platform web app, generates traffic through all
three instrumented layers it can reach on CPU (HTTP requests, notebook
reconciles), then:

  1. scrapes `/metrics` and runs it through `parse_exposition`, a
     strict Prometheus text-format parser — HELP/TYPE coverage, label
     escape round-trips, histogram invariants (cumulative nondecreasing
     buckets ending at `+Inf` == `_count`, `_sum` present), duplicate
     series detection;
  2. pulls `/debug/traces` and checks it is Chrome-trace-loadable JSON
     containing an `http.request` span.

The parser is intentionally pedantic where Prometheus' own parser is
forgiving: render bugs (a histogram that forgets `+Inf`, an unescaped
quote in a label) should fail CI here, not corrupt dashboards later.
Tests import `parse_exposition` directly (tests/test_obs.py).

The parser itself moved to `kubeflow_tpu.obs.exposition` when metrics
federation made it a runtime dependency of the fleet router (ISSUE 6);
this module re-exports it so existing importers keep working, and the
gate grew a second act: boot a router over two stub replicas, scrape
the federated `/fleet/metrics`, and hold it to the same strict
contract plus zero-seeded `slo_burn_rate` gauges.
"""

from __future__ import annotations

import json
import sys

from kubeflow_tpu.obs.exposition import (  # noqa: F401  (re-exports)
    ExpositionError,
    _check_histogram,
    _parse_labels,
    _parse_value,
    _unescape_label_value,
    parse_exposition,
)

# -- the live scrape gate -----------------------------------------------

REQUIRED_FAMILIES = (
    "reconcile_duration_seconds",
    "workqueue_queue_latency_seconds",
    "workqueue_depth",
    "request_duration_seconds",
    "request_total",
)

# The step-anatomy families (ISSUE 8) every serving /metrics must
# expose ZERO-SEEDED: a dashboard built before traffic arrives sees the
# full phase/fn label space, not holes.
PROFILE_FAMILIES = (
    "serving_step_phase_seconds",
    "serving_step_tokens",
    "serving_goodput_ratio",
    "serving_bubble_fraction",
    "serving_kv_blocks_high_water",
    "serving_recompiles_total",
    "serving_startup_seconds",
)


def _check_trace_events(events: list, where: str,
                        failures: list[str]) -> None:
    """Chrome-trace event shape: complete spans (`X`: ts + dur), the
    profiler's counter tracks (`C`: ts + args), and metadata (`M`).
    Anything else is malformed for our payloads."""
    for e in events:
        ph = e.get("ph")
        if ph == "X":
            ok = "ts" in e and "dur" in e
        elif ph == "C":
            ok = "ts" in e and isinstance(e.get("args"), dict)
        elif ph == "M":
            ok = "name" in e
        else:
            ok = False
        if not ok:
            failures.append(f"{where}: malformed trace event: {e!r:.120}")
            break


async def run_check() -> list[str]:
    """Boot Cluster + platform app, drive traffic, validate /metrics and
    /debug/traces. Returns a list of failures (empty = pass)."""
    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.api.core import Container, PodTemplateSpec
    from kubeflow_tpu.api.crds import Notebook
    from kubeflow_tpu.controlplane.cluster import Cluster, ClusterConfig

    failures: list[str] = []
    with Cluster(ClusterConfig(tpu_slices={"v5e-1": 2})) as cluster:
        # control-plane traffic: reconcile a notebook end to end
        nb = Notebook()
        nb.metadata.name = "obs-check"
        nb.metadata.namespace = "default"
        nb.spec.template = PodTemplateSpec()
        nb.spec.template.spec.containers.append(
            Container(name="obs-check",
                      image="kubeflow-tpu/jupyter-jax:latest"))
        cluster.store.create(nb)
        cluster.wait_idle()

        app = cluster.create_web_app(csrf=False)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            # web traffic (auth-exempt paths: keep the gate hermetic)
            for path in ("/healthz", "/healthz", "/readyz"):
                resp = await client.get(path)
                if resp.status != 200:
                    failures.append(f"GET {path} -> {resp.status}")
                if "X-Trace-Id" not in resp.headers:
                    failures.append(f"GET {path}: no X-Trace-Id header")

            resp = await client.get("/metrics")
            text = await resp.text()
            try:
                families = parse_exposition(text)
            except ExpositionError as e:
                return [f"/metrics failed strict parse: {e}"]
            for fam in REQUIRED_FAMILIES:
                if fam not in families:
                    failures.append(f"/metrics missing family {fam}")
                elif not families[fam]["samples"]:
                    failures.append(f"/metrics family {fam} has no samples")
            recon = families.get("reconcile_duration_seconds")
            if recon and not any(
                    ("kind", "NotebookController") in labels
                    for _, labels in recon["samples"]):
                failures.append(
                    "no NotebookController reconcile_duration samples — "
                    "did the reconcile instrumentation regress?")
            # Instrumentation must never break the instrumented path: a
            # broken span call surfaces as reconcile errors here.
            errs = families.get("reconcile_total", {"samples": {}})
            for (sname, labels), v in errs["samples"].items():
                if ("severity", "error") in labels and v > 0:
                    failures.append(
                        f"reconcile errors during the check: "
                        f"{sname}{dict(labels)} = {v}")

            resp = await client.get("/debug/traces")
            if resp.content_type != "application/json":
                failures.append(
                    f"/debug/traces content type {resp.content_type}")
            payload = json.loads(await resp.text())
            events = payload.get("traceEvents")
            if not isinstance(events, list) or not events:
                failures.append("/debug/traces has no traceEvents")
            else:
                names = {e.get("name") for e in events}
                if "http.request" not in names:
                    failures.append(
                        "/debug/traces missing http.request spans")
                _check_trace_events(events, "/debug/traces", failures)
        finally:
            await client.close()
    return failures


async def run_profile_check() -> list[str]:
    """Third act (ISSUE 8): boot the serving app with a tiny continuous
    engine, drive one real generate, and hold the step-anatomy plane to
    the contract: `/metrics` strict-parses with every PROFILE_FAMILIES
    member zero-seeded over its CLOSED label sets (all phases, all
    watched fns), `/debug/profile` serves the rolling anatomy with the
    goodput ledger and recompile counts, and `/debug/traces` carries
    the profiler's counter tracks alongside the spans."""
    import jax
    import numpy as np
    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu import obs as obs_lib
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        LLAMA_FAMILY,
    )
    from kubeflow_tpu.serving import server as server_lib

    failures: list[str] = []
    cfg = llama.LLAMA_TINY
    params = llama.init(jax.random.key(0), cfg)
    engine = InferenceEngine(params, cfg, LLAMA_FAMILY,
                             EngineConfig(max_len=64))
    app = server_lib.create_serving_app(
        {"m": engine}, continuous=True, max_batch=2)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        import asyncio

        gen = np.random.default_rng(0)
        prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
                   for n in (4, 6)]
        resps = await asyncio.gather(*(
            client.post("/v1/models/m:generate",
                        json={"tokens": [p], "max_new": 4})
            for p in prompts))
        for resp in resps:
            if resp.status != 200:
                return [f"generate -> {resp.status}: "
                        f"{await resp.text()}"]

        # 1. /metrics: strict parse + zero-seeded closed label sets
        text = await (await client.get("/metrics")).text()
        try:
            families = parse_exposition(text)
        except ExpositionError as e:
            return [f"serving /metrics failed strict parse: {e}"]
        for fam in PROFILE_FAMILIES:
            if fam not in families:
                failures.append(f"/metrics missing family {fam}")
        phased = families.get("serving_step_phase_seconds",
                              {"samples": {}})
        have = {dict(labels).get("phase")
                for (sname, labels) in phased["samples"]
                if sname.endswith("_count")}
        missing = set(obs_lib.SERVING_PHASES) - have
        if missing:
            failures.append(
                f"serving_step_phase_seconds not zero-seeded for "
                f"phases {sorted(missing)}")
        rec = families.get("serving_recompiles_total", {"samples": {}})
        have_fns = {dict(labels).get("fn")
                    for (_s, labels) in rec["samples"]}
        missing = set(obs_lib.WATCHED_SERVING_FNS) - have_fns
        if missing:
            failures.append(
                f"serving_recompiles_total not zero-seeded for fns "
                f"{sorted(missing)}")
        started = families.get("serving_startup_seconds", {"samples": {}})
        missing = set(obs_lib.STARTUP_PHASES) - {
            dict(labels).get("phase") for (_s, labels) in started["samples"]}
        if missing:
            failures.append(
                f"serving_startup_seconds not zero-seeded for phases "
                f"{sorted(missing)}")

        # 2. /debug/profile: the rolling anatomy
        resp = await client.get("/debug/profile")
        if resp.content_type != "application/json":
            failures.append(
                f"/debug/profile content type {resp.content_type}")
        prof = json.loads(await resp.text())
        m = prof.get("models", {}).get("m")
        if m is None:
            failures.append("/debug/profile has no model 'm'")
        else:
            for key in ("phases", "goodput", "wall_s", "recompiles"):
                if key not in m:
                    failures.append(f"/debug/profile missing {key!r}")
            for p in obs_lib.SERVING_PHASES:
                if p not in m.get("phases", {}):
                    failures.append(
                        f"/debug/profile missing phase {p!r}")
            if m.get("phases", {}).get("decode", {}).get("count", 0) < 1:
                failures.append(
                    "/debug/profile: no decode phase samples after a "
                    "generate — is the batcher instrumented?")
            for fn in obs_lib.WATCHED_SERVING_FNS:
                # this app has no draft model: nothing to watch there
                if fn not in m.get("recompiles", {}) \
                        and not fn.startswith("spec_"):
                    failures.append(
                        f"/debug/profile missing recompile fn {fn!r}")
        # the process's compile ledger and start-up spans ride along
        for key, inner in (("compiles", "programs"), ("startup", "spans")):
            if inner not in prof.get(key, {}):
                failures.append(f"/debug/profile missing {key}.{inner}")
        if "startup.batcher" not in prof.get("startup", {}).get("spans", {}):
            failures.append("/debug/profile startup has no startup.batcher "
                            "span after a batcher was built")

        # 3. /debug/traces: spans + the profiler's counter tracks
        payload = json.loads(
            await (await client.get("/debug/traces")).text())
        events = payload.get("traceEvents")
        if not isinstance(events, list) or not events:
            failures.append("serving /debug/traces has no traceEvents")
        else:
            _check_trace_events(events, "serving /debug/traces",
                                failures)
            counters = {e.get("name") for e in events
                        if e.get("ph") == "C"}
            if not any(c.startswith("m.") for c in counters):
                failures.append(
                    "serving /debug/traces has no per-model counter "
                    f"tracks (got {sorted(counters)})")
    finally:
        await client.close()
    return failures


async def run_fleet_check() -> list[str]:
    """Second act (ISSUE 6): boot a fleet router over two STUB
    replicas — real metric registries behind real HTTP servers, no jax
    — and hold the federated `/fleet/metrics` to the same strict
    contract: parseable, counters summed, histogram buckets merged,
    `slo_burn_rate` zero-seeded, `fleet_federation_up` covering every
    replica. Stubs keep the gate fast and make the expected sums exact."""
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu import obs as obs_lib
    from kubeflow_tpu.controlplane.metrics import Counter, Registry
    from kubeflow_tpu.fleet.router import create_router_app
    from kubeflow_tpu.obs import endpoints as obs_endpoints

    failures: list[str] = []

    def stub_replica(reqs: int, latencies: list[float]):
        reg = Registry()
        Counter("stub_requests_total", "stub traffic", reg).inc(reqs)
        hist = obs_lib.get_or_create_histogram(
            reg, "stub_latency_seconds", "stub latency")
        for v in latencies:
            hist.observe(v)
        reg.register(obs_lib.SloEngine([
            obs_lib.Slo("stub_latency", 0.95, threshold_s=1.0)]))
        # the step-anatomy families exactly as a serving replica
        # zero-seeds them (ISSUE 8): federation must merge the closed
        # phase/fn label sets without traffic
        phase = obs_lib.get_or_create_histogram(
            reg, "serving_step_phase_seconds", "stub step anatomy")
        for p in obs_lib.SERVING_PHASES:
            phase.seed(model="stub", phase=p)
        from kubeflow_tpu.controlplane.metrics import Gauge

        Gauge("serving_goodput_ratio", "stub goodput",
              reg).set(0.0, model="stub")
        rec = Counter("serving_recompiles_total", "stub retraces", reg)
        for fn in obs_lib.WATCHED_SERVING_FNS:
            rec.inc(0, model="stub", fn=fn)
        app = web.Application()
        obs_endpoints.mount_observability(
            app, registry=reg, tracer=obs_lib.Tracer())
        return app

    replicas = [TestServer(stub_replica(3, [0.1, 0.2])),
                TestServer(stub_replica(4, [0.3]))]
    router = TestClient(TestServer(create_router_app()))
    try:
        for srv in replicas:
            await srv.start_server()
        await router.start_server()
        for i, srv in enumerate(replicas):
            resp = await router.post("/fleet/register", json={
                "id": f"stub-{i}",
                "url": str(srv.make_url("")).rstrip("/")})
            if resp.status != 200:
                failures.append(
                    f"register stub-{i} -> {resp.status}")
        resp = await router.get("/fleet/metrics")
        text = await resp.text()
        try:
            families = parse_exposition(text)
        except ExpositionError as e:
            return [f"/fleet/metrics failed strict parse: {e}"]

        def sample(fam: str, sname: str, **labels):
            f = families.get(fam)
            if f is None:
                failures.append(f"/fleet/metrics missing family {fam}")
                return None
            key = (sname, tuple(sorted(labels.items())))
            if key not in f["samples"]:
                failures.append(
                    f"/fleet/metrics missing sample {sname}{labels}")
                return None
            return f["samples"][key]

        if sample("stub_requests_total", "stub_requests_total") != 7:
            failures.append(
                "counters not summed across replicas (want 3+4=7)")
        if sample("stub_latency_seconds",
                  "stub_latency_seconds_count") != 3:
            failures.append(
                "histogram _count not merged (want 2+1=3)")
        # burn-rate gauges federate like any gauge, zero-seeded
        for window in ("short", "long"):
            sample("slo_burn_rate", "slo_burn_rate",
                   slo="stub_latency", window=window)
        # zero-seeded step-anatomy families survive federation with
        # their closed label sets intact: phase histograms merge
        # (2 replicas x 0 observations), recompile counters sum
        from kubeflow_tpu.obs.profiling import (
            SERVING_PHASES,
            WATCHED_SERVING_FNS,
        )

        for p in SERVING_PHASES:
            if sample("serving_step_phase_seconds",
                      "serving_step_phase_seconds_count",
                      model="stub", phase=p) not in (0, None):
                failures.append(
                    f"federated phase histogram [{p}] not zero")
        for fn in WATCHED_SERVING_FNS:
            if sample("serving_recompiles_total",
                      "serving_recompiles_total",
                      model="stub", fn=fn) not in (0, None):
                failures.append(
                    f"federated serving_recompiles_total[{fn}] not zero")
        sample("serving_goodput_ratio", "serving_goodput_ratio",
               model="stub")
        for i in range(len(replicas)):
            if sample("fleet_federation_up", "fleet_federation_up",
                      replica=f"stub-{i}") != 1:
                failures.append(f"fleet_federation_up[stub-{i}] != 1")
    finally:
        await router.close()
        for srv in replicas:
            await srv.close()
    return failures


async def run_cache_check() -> list[str]:
    """Sixth act (ISSUE 13): the KV-cache observatory contract. Boot
    the serving app with a tiny continuous engine, drive a cold miss +
    a warm hit (one request tenant-labelled), then hold the cache
    plane to its contract: `/metrics` strict-parses with the eviction
    cause set, defer cause set, and tenant-labelled hit/miss series
    all zero-seeded; the block lifecycle ledger CONSERVES (cause sums
    == total frees, `unattributed` == 0, births - frees == live) and
    the per-cause metric values equal the ledger's; `/debug/profile`
    carries the cache anatomy + hashed heat digest; `/debug/traces`
    carries the kv_evictions counter track; `/v1/models` exports the
    heat digest in 16-hex hashed form."""
    import jax
    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu import obs as obs_lib
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        LLAMA_FAMILY,
    )
    from kubeflow_tpu.serving import server as server_lib
    from kubeflow_tpu.tenancy import config_from_dict

    failures: list[str] = []
    cfg = llama.LLAMA_TINY
    params = llama.init(jax.random.key(0), cfg)
    engine = InferenceEngine(params, cfg, LLAMA_FAMILY,
                             EngineConfig(max_len=64))
    # block size 8 so a short prompt still fills whole KV blocks (the
    # unit the ledger and the heat digest account in); a tenancy
    # config so the X-Tenant header reaches the tenant-labelled
    # hit/miss series
    app = server_lib.create_serving_app(
        {"m": engine}, continuous=True, max_batch=2, kv_block_size=8,
        tenancy=config_from_dict({"tenants": {"acme": {}}}))
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        prompt = [3, 5, 7, 11, 13, 17, 19, 23]  # one full block
        r = await client.post("/v1/models/m:generate",
                              json={"tokens": [prompt], "max_new": 4})
        if r.status != 200:
            return [f"generate -> {r.status}: {await r.text()}"]
        # warm repeat, tenant-labelled: radix hit + tenant series inc
        r = await client.post("/v1/models/m:generate",
                              json={"tokens": [prompt], "max_new": 4},
                              headers={"X-Tenant": "acme"})
        if r.status != 200:
            return [f"generate -> {r.status}: {await r.text()}"]

        # 1. /metrics: strict parse + zero-seeded closed cause sets
        text = await (await client.get("/metrics")).text()
        try:
            families = parse_exposition(text)
        except ExpositionError as e:
            return [f"serving /metrics failed strict parse: {e}"]

        def sample(fam: str, sname: str, **labels):
            f = families.get(fam)
            if f is None:
                failures.append(f"/metrics missing family {fam}")
                return None
            key = (sname, tuple(sorted(labels.items())))
            if key not in f["samples"]:
                failures.append(
                    f"/metrics missing sample {sname}{labels}")
                return None
            return f["samples"][key]

        causes = (*obs_lib.EVICTION_CAUSES, obs_lib.UNATTRIBUTED)
        evict = {c: sample("serving_kv_evictions_total",
                           "serving_kv_evictions_total",
                           model="m", cause=c) for c in causes}
        for c in obs_lib.DEFER_CAUSES:
            sample("serving_kv_admission_defers_total",
                   "serving_kv_admission_defers_total",
                   model="m", cause=c)
        for fam in ("serving_kv_reuse_distance_admissions",
                    "serving_kv_block_age_admissions"):
            sample(fam, f"{fam}_count", model="m")
        if (sample("serving_kv_reuse_distance_admissions",
                   "serving_kv_reuse_distance_admissions_count",
                   model="m") or 0) < 1:
            failures.append(
                "no reuse-distance sample after a radix hit")
        if evict.get(obs_lib.UNATTRIBUTED):
            failures.append(
                f"unattributed evictions: {evict[obs_lib.UNATTRIBUTED]}"
                " — some pool.free() site forgot its cause")
        # tenant-labelled hit/miss series: zero-seeded "other" plus
        # the real tenant, alongside the bitwise-compatible unlabelled
        # (model-only) series
        for fam in ("serving_prefix_cache_hits_total",
                    "serving_prefix_cache_misses_total"):
            plain = sample(fam, fam, model="m")
            sample(fam, fam, model="m", tenant="other")
            tenanted = sample(fam, fam, model="m", tenant="acme")
            if plain is not None and tenanted is not None \
                    and plain < tenanted:
                failures.append(
                    f"{fam}: model-only series ({plain}) < tenant "
                    f"series ({tenanted}) — totals must stay supersets")
        hits = sample("serving_prefix_cache_hits_total",
                      "serving_prefix_cache_hits_total",
                      model="m", tenant="acme")
        if hits is not None and hits < 1:
            failures.append(
                "tenant-labelled prefix hit not booked for the warm "
                f"repeat (got {hits})")

        # 2. /debug/profile: cache anatomy, conservation, heat digest
        prof = json.loads(
            await (await client.get("/debug/profile")).text())
        cache = prof.get("models", {}).get("m", {}).get("cache")
        if cache is None:
            failures.append("/debug/profile has no cache anatomy")
        else:
            led = cache.get("ledger", {})
            for key in ("births", "frees", "frees_total",
                        "live_blocks", "defers", "reuse_distance",
                        "block_age", "conserved"):
                if key not in led:
                    failures.append(
                        f"/debug/profile cache.ledger missing {key!r}")
            if not led.get("conserved"):
                failures.append(
                    f"cache ledger NOT conserved: {led}")
            if sum(led.get("frees", {}).values()) \
                    != led.get("frees_total"):
                failures.append(
                    "eviction causes do not sum to total frees: "
                    f"{led.get('frees')}")
            # the /metrics counters and the ledger are the same books
            for c, v in (led.get("frees") or {}).items():
                if evict.get(c) is not None and evict[c] != v:
                    failures.append(
                        f"serving_kv_evictions_total{{cause={c}}} = "
                        f"{evict[c]} but ledger says {v}")
            heat = cache.get("heat")
            if not heat:
                failures.append("/debug/profile cache.heat is empty "
                                "after two admissions")
            else:
                want = obs_lib.prefix_hash(prompt)
                if heat[0].get("prefix") != want:
                    failures.append(
                        f"hottest prefix {heat[0]} is not the hashed "
                        f"prompt block {want}")

        # 3. /debug/traces: the kv_evictions counter track
        payload = json.loads(
            await (await client.get("/debug/traces")).text())
        events = payload.get("traceEvents") or []
        counters = {e.get("name") for e in events
                    if e.get("ph") == "C"}
        if "m.kv_evictions" not in counters:
            failures.append(
                "serving /debug/traces has no m.kv_evictions counter "
                f"track (got {sorted(counters)})")

        # 4. /v1/models: bounded hashed heat digest on the model card
        models = json.loads(
            await (await client.get("/v1/models")).text())["models"]
        pc = models[0].get("prefix_cache", {})
        dg = pc.get("heat")
        if not isinstance(dg, list) or not dg:
            failures.append("/v1/models prefix_cache.heat missing")
        elif not all(
                isinstance(e.get("prefix"), str)
                and len(e["prefix"]) == 16
                and all(ch in "0123456789abcdef" for ch in e["prefix"])
                and isinstance(e.get("score"), (int, float))
                for e in dg):
            failures.append(
                f"/v1/models heat digest is not 16-hex + score: {dg}")
    finally:
        await client.close()
    return failures


async def run_cache_tier_check() -> list[str]:
    """Cache-tier act (ISSUE 19): the host-RAM spill tier contract.
    Boot the serving app with the smallest legal paged pool plus a
    spill tier, drive enough distinct prompts that allocation pressure
    demotes cold radix chains to the host, then re-request the first
    prompt so a demoted block is RESTORED — and hold the plane to its
    contract: `serving_prefill_tokens{source}` zero-seeded over the
    CLOSED four-source set and `fleet_peer_fetch_total{outcome}` over
    the CLOSED outcome set; the spill demotion/restore counters and
    byte gauge agree with the ledger; the restored re-request books
    `source="restored"` tokens AND replays token-identically; and the
    EXTENDED conservation (births − frees == live + spilled, with
    restores netted out) holds under pressure."""
    import jax
    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu import obs as obs_lib
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        LLAMA_FAMILY,
    )
    from kubeflow_tpu.serving import server as server_lib

    failures: list[str] = []
    cfg = llama.LLAMA_TINY
    params = llama.init(jax.random.key(0), cfg)
    engine = InferenceEngine(params, cfg, LLAMA_FAMILY,
                             EngineConfig(max_len=64))
    # 9 blocks = trash + one slot's worth at max_len 64 / block 8: the
    # smallest legal pool. Each retired 12-token prompt parks one full
    # KV block in the radix, so ten distinct prompts overflow the 8
    # usable blocks and the allocator demotes the LRU chains to the
    # host tier instead of discarding them.
    app = server_lib.create_serving_app(
        {"m": engine}, continuous=True, max_batch=2, kv_block_size=8,
        kv_pool_blocks=9, kv_spill_bytes=64 << 20)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        def prompt(i: int) -> list[int]:
            # distinct FIRST blocks (the spill key is the full token
            # path, so the lead tokens must differ per prompt)
            return [40 + i] * 4 + [3, 5, 7, 11, 13, 17, 19, 23]

        first = None
        for i in range(10):
            r = await client.post(
                "/v1/models/m:generate",
                json={"tokens": [prompt(i)], "max_new": 4})
            if r.status != 200:
                return [f"generate[{i}] -> {r.status}: "
                        f"{await r.text()}"]
            if i == 0:
                first = (await r.json())["tokens"]
        # the re-request: its first block was demoted under pressure,
        # so the radix miss must be answered from the host tier
        r = await client.post(
            "/v1/models/m:generate",
            json={"tokens": [prompt(0)], "max_new": 4})
        if r.status != 200:
            return [f"restored generate -> {r.status}: "
                    f"{await r.text()}"]
        again = (await r.json())["tokens"]
        if again != first:
            failures.append(
                f"restored replay diverged: {again} != {first} — the "
                "spill tier returned different KV content than the "
                "original prefill")

        text = await (await client.get("/metrics")).text()
        try:
            families = parse_exposition(text)
        except ExpositionError as e:
            return [f"serving /metrics failed strict parse: {e}"]

        def sample(fam: str, sname: str, **labels):
            f = families.get(fam)
            if f is None:
                failures.append(f"/metrics missing family {fam}")
                return None
            key = (sname, tuple(sorted(labels.items())))
            if key not in f["samples"]:
                failures.append(
                    f"/metrics missing sample {sname}{labels}")
                return None
            return f["samples"][key]

        # 1. zero-seeded CLOSED grids: all four prefill sources, all
        # three peer-fetch outcomes, from the first scrape
        counts = {s: sample("serving_prefill_tokens",
                            "serving_prefill_tokens_count",
                            model="m", source=s)
                  for s in obs_lib.PREFILL_SOURCES}
        fetches = {o: sample("fleet_peer_fetch_total",
                             "fleet_peer_fetch_total",
                             model="m", outcome=o)
                   for o in obs_lib.PEER_FETCH_OUTCOMES}
        if any(v for v in fetches.values() if v):
            failures.append(
                f"peer fetches booked with no peer configured: "
                f"{fetches}")
        if not counts.get("restored"):
            failures.append(
                "serving_prefill_tokens{source=restored} never "
                f"observed (counts: {counts}) — the spilled block was "
                "not promoted back on the warm re-request")
        if counts.get("peer_fetched"):
            failures.append(
                "peer_fetched tokens booked on a single replica: "
                f"{counts}")

        # 2. spill counters + byte gauge vs the ledger's books
        demos = sample("serving_kv_spill_demotions_total",
                       "serving_kv_spill_demotions_total", model="m")
        rests = sample("serving_kv_spill_restores_total",
                       "serving_kv_spill_restores_total", model="m")
        gauge = sample("serving_kv_spill_bytes",
                       "serving_kv_spill_bytes", model="m")
        spill_evict = sample("serving_kv_evictions_total",
                             "serving_kv_evictions_total",
                             model="m", cause="spill")
        if not demos:
            failures.append(
                "no spill demotions under a pool 4x smaller than the "
                "working set — pressure evictions bypassed the tier")
        if not rests:
            failures.append("no spill restores after the warm "
                            "re-request of a demoted prefix")
        if demos is not None and spill_evict != demos:
            failures.append(
                f"evictions{{cause=spill}} = {spill_evict} != "
                f"demotions counter {demos}: one booking chokepoint "
                "drifted from the other")

        prof = json.loads(
            await (await client.get("/debug/profile")).text())
        led = (prof.get("models", {}).get("m", {})
               .get("cache", {}).get("ledger", {}))
        sp = led.get("spill")
        if not isinstance(sp, dict):
            failures.append("/debug/profile cache.ledger has no spill "
                            "section")
        else:
            if demos is not None and sp.get("demotions") != demos:
                failures.append(
                    f"ledger demotions {sp.get('demotions')} != metric "
                    f"{demos}")
            if rests is not None and sp.get("restores") != rests:
                failures.append(
                    f"ledger restores {sp.get('restores')} != metric "
                    f"{rests}")
            if gauge is not None and \
                    (gauge > 0) != (sp.get("spilled", 0) > 0):
                failures.append(
                    f"serving_kv_spill_bytes = {gauge} disagrees with "
                    f"ledger spilled = {sp.get('spilled')}")
        if not led.get("conserved"):
            failures.append(
                "cache ledger NOT conserved under spill pressure: "
                f"{led}")
    finally:
        await client.close()
    return failures


async def run_train_check() -> list[str]:
    """Fourth act (ISSUE 11): boot the elastic-training coordinator —
    real aiohttp app, no jax — and hold its /metrics to the strict
    contract: the full train_* catalog visible zero-seeded in ONE
    scrape before any trainer ever checkpointed, then the gauges and
    the restart counter tracking a registered gang losing a member."""
    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.controlplane.metrics import Registry
    from kubeflow_tpu.fleet.registry import STATES
    from kubeflow_tpu.train.elastic import (
        ElasticCoordinator,
        create_coordinator_app,
    )

    failures: list[str] = []
    clock_t = [0.0]
    coord = ElasticCoordinator(
        min_replicas=2, degraded_after_s=5.0, dead_after_s=10.0,
        clock=lambda: clock_t[0], registry=Registry())
    client = TestClient(TestServer(create_coordinator_app(coord)))
    try:
        await client.start_server()

        async def scrape() -> dict:
            resp = await client.get("/metrics")
            text = await resp.text()
            try:
                return parse_exposition(text)
            except ExpositionError as e:
                failures.append(f"/metrics failed strict parse: {e}")
                return {}

        def sample(families: dict, fam: str, sname: str, **labels):
            f = families.get(fam)
            if f is None:
                failures.append(f"/metrics missing family {fam}")
                return None
            key = (sname, tuple(sorted(labels.items())))
            if key not in f["samples"]:
                failures.append(
                    f"/metrics missing sample {sname}{labels}")
                return None
            return f["samples"][key]

        fams = await scrape()
        for state in STATES:
            if sample(fams, "train_replicas", "train_replicas",
                      state=state) not in (0, None):
                failures.append(
                    f"train_replicas[{state}] not zero-seeded")
        if sample(fams, "train_generation", "train_generation") \
                not in (0, None):
            failures.append("train_generation not zero-seeded")
        if sample(fams, "train_restarts_total",
                  "train_restarts_total") not in (0, None):
            failures.append("train_restarts_total not zero-seeded")
        for fam in ("train_checkpoint_save_seconds",
                    "train_checkpoint_restore_seconds"):
            if sample(fams, fam, f"{fam}_count") not in (0, None):
                failures.append(f"{fam}_count not zero-seeded")

        # a gang forms, then loses a member: gauges + counter move
        for rid in ("tr0", "tr1"):
            resp = await client.post(
                "/elastic/register",
                json={"replica_id": rid, "step": 0})
            if resp.status != 200:
                failures.append(f"register {rid} -> {resp.status}")
        clock_t[0] = 11.0  # tr0 never beats again -> dead
        await client.post("/elastic/heartbeat",
                          json={"replica_id": "tr1", "step": 4})
        world = await (await client.get("/elastic/world")).json()
        if world.get("members") != ["tr1"]:
            failures.append(
                f"/elastic/world kept a dead member: {world}")
        fams = await scrape()
        if sample(fams, "train_replicas", "train_replicas",
                  state="ready") != 1:
            failures.append("train_replicas[ready] != 1 after death")
        if sample(fams, "train_replicas", "train_replicas",
                  state="dead") != 1:
            failures.append("train_replicas[dead] != 1 after death")
        if sample(fams, "train_restarts_total",
                  "train_restarts_total") != 1:
            failures.append(
                "train_restarts_total != 1 after losing a member")
        gen = sample(fams, "train_generation", "train_generation")
        if gen is not None and gen < 3:
            failures.append(
                f"train_generation {gen} did not track two joins + "
                "one death")
    finally:
        await client.close()
    return failures


async def run_train_obs_check() -> list[str]:
    """Seventh act (ISSUE 14): the training observatory. Boot the
    coordinator — real aiohttp app, no jax — plus two fake workers
    that carry REAL goodput ledgers and registries in their
    heartbeats, and hold `GET /elastic/metrics` to the contract:

    - the federated exposition strict-parses with the goodput catalog
      (`train_goodput_seconds_total{cause}`, wall gauge, tokens/s,
      straggler + fraction gauges, `slo_burn_rate{slo=train_*}`)
      zero-seeded before any worker ever stepped;
    - CONSERVATION as an equality between planes: the summed per-cause
      counters in the federated scrape == the summed wall gauge == the
      workers' own ledger books (every worker-second attributed,
      nothing minted in flight);
    - `GET /elastic/traces` merges the workers' Chrome traces onto
      per-worker process tracks.
    """
    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.controlplane.metrics import Registry
    from kubeflow_tpu.obs.slo import WINDOWS
    from kubeflow_tpu.train.elastic import (
        ElasticCoordinator,
        create_coordinator_app,
    )
    from kubeflow_tpu.train.goodput import (
        GOODPUT_CAUSES,
        LOST_CAUSES,
        GoodputLedger,
        bind_ledger_metrics,
    )

    failures: list[str] = []
    clock_t = [0.0]
    coord = ElasticCoordinator(
        min_replicas=2, degraded_after_s=5.0, dead_after_s=10.0,
        clock=lambda: clock_t[0], registry=Registry())
    client = TestClient(TestServer(create_coordinator_app(coord)))

    class FakeWorker:
        """A trainer worker reduced to its telemetry: a goodput ledger
        on a scripted clock, a registry exposing it, and a canned
        Chrome trace — exactly the payload run_worker's heartbeater
        enriches beats with."""

        def __init__(self, rid: str):
            self.rid = rid
            self.t = [0.0]
            self.ledger = GoodputLedger(clock=lambda: self.t[0],
                                        wall=lambda: self.t[0])
            self.registry = Registry()
            bind_ledger_metrics(self.registry, self.ledger)

        def payload(self, **extra) -> dict:
            trace = {"displayTimeUnit": "ms", "traceEvents": [
                {"name": "train.step", "ph": "X", "ts": 0,
                 "dur": 1000, "pid": 1, "tid": 1}]}
            return {"replica_id": self.rid,
                    "goodput": self.ledger.snapshot(),
                    "metrics": self.registry.render(),
                    "trace": trace, **extra}

    try:
        await client.start_server()

        async def federated() -> dict:
            resp = await client.get("/elastic/metrics")
            text = await resp.text()
            if resp.status != 200:
                failures.append(f"/elastic/metrics -> {resp.status}")
                return {}
            try:
                return parse_exposition(text)
            except ExpositionError as e:
                failures.append(
                    f"/elastic/metrics failed strict parse: {e}")
                return {}

        def sample(families: dict, fam: str, sname: str, **labels):
            f = families.get(fam)
            if f is None:
                failures.append(
                    f"/elastic/metrics missing family {fam}")
                return None
            key = (sname, tuple(sorted(labels.items())))
            if key not in f["samples"]:
                failures.append(
                    f"/elastic/metrics missing sample {sname}{labels}")
                return None
            return f["samples"][key]

        # 1. zero-seeded goodput catalog before ANY worker exists
        fams = await federated()
        for c in (*GOODPUT_CAUSES, "unattributed"):
            if sample(fams, "train_goodput_seconds_total",
                      "train_goodput_seconds_total",
                      cause=c) not in (0, None):
                failures.append(
                    f"train_goodput_seconds_total[{c}] not zero-seeded")
        for c in LOST_CAUSES:
            if sample(fams, "train_replay_seconds_total",
                      "train_replay_seconds_total",
                      cause=c) not in (0, None):
                failures.append(
                    f"train_replay_seconds_total[{c}] not zero-seeded")
        for g in ("train_goodput_wall_seconds", "train_tokens_per_second",
                  "train_straggler_ratio", "train_goodput_fraction"):
            if sample(fams, g, g) not in (0, None):
                failures.append(f"{g} not zero-seeded")
        if sample(fams, "train_worker_step_seconds",
                  "train_worker_step_seconds",
                  worker="other") not in (0, None):
            failures.append(
                "train_worker_step_seconds[other] not zero-seeded")
        for slo in ("train_step_time", "train_checkpoint_save",
                    "train_goodput", "train_restart_burn"):
            for w in WINDOWS:
                if sample(fams, "slo_burn_rate", "slo_burn_rate",
                          slo=slo, window=w) not in (0, None):
                    failures.append(
                        f"slo_burn_rate[{slo},{w}] not zero-seeded")

        # 2. a gang of two ledger-carrying workers steps, one stalls
        workers = [FakeWorker("tr0"), FakeWorker("tr1")]
        for w in workers:
            resp = await client.post("/elastic/register",
                                     json=w.payload(step=0))
            if resp.status != 200:
                failures.append(f"register {w.rid} -> {resp.status}")
        for i in range(3):
            for w, dt in zip(workers, (0.1, 0.3)):
                w.t[0] += dt
                w.ledger.note_step(i, dt, tokens=64, flops=100.0)
            workers[1].t[0] += 0.1
            with workers[1].ledger.book("stall"):
                workers[1].t[0] += 0.2
            clock_t[0] += 0.5
            for w, dt in zip(workers, (0.1, 0.3)):
                resp = await client.post(
                    "/elastic/heartbeat",
                    json=w.payload(step=i + 1, step_seconds=dt))
                if resp.status != 200:
                    failures.append(
                        f"heartbeat {w.rid} -> {resp.status}")

        # 3. conservation equality across the federation boundary
        fams = await federated()
        fam = fams.get("train_goodput_seconds_total", {"samples": {}})
        booked = sum(fam["samples"].values())
        wall_fam = fams.get("train_goodput_wall_seconds",
                            {"samples": {}})
        wall = sum(wall_fam["samples"].values())
        ledgers = sum(w.ledger.snapshot()["wall_seconds"]
                      for w in workers)
        if abs(booked - wall) > 1e-6:
            failures.append(
                f"federated goodput not conserved: cause counters sum "
                f"{booked} != wall gauge {wall}")
        if abs(wall - ledgers) > 1e-6:
            failures.append(
                f"federated wall {wall} != workers' own ledgers "
                f"{ledgers} (seconds minted or lost in federation)")
        if not any(w.ledger.snapshot()["conserved"] for w in workers):
            failures.append("worker ledgers report conserved=False")
        for rid in ("coordinator", "tr0", "tr1"):
            if sample(fams, "fleet_federation_up",
                      "fleet_federation_up", replica=rid) != 1:
                failures.append(
                    f"fleet_federation_up[{rid}] != 1 with the gang "
                    "live")
        # the stalling worker moved the forensics gauges
        ratio = sample(fams, "train_straggler_ratio",
                       "train_straggler_ratio")
        if ratio is not None and not ratio > 1.0:
            failures.append(
                f"train_straggler_ratio {ratio} did not flag the 3x "
                "straggler")
        if sample(fams, "train_worker_step_seconds",
                  "train_worker_step_seconds", worker="tr1") != 0.3:
            failures.append(
                "train_worker_step_seconds[tr1] != its reported 0.3")
        stall = sample(fams, "train_replay_seconds_total",
                       "train_replay_seconds_total", cause="stall")
        if stall is not None and not stall > 0:
            failures.append(
                "train_replay_seconds_total[stall] stayed 0 through a "
                "booked stall")

        # 4. merged traces: one process track per live worker
        resp = await client.get("/elastic/traces")
        payload = json.loads(await resp.text())
        tracks = {e["args"]["name"]
                  for e in payload.get("traceEvents", [])
                  if e.get("ph") == "M"
                  and e.get("name") == "process_name"}
        if tracks != {"tr0", "tr1"}:
            failures.append(
                f"/elastic/traces tracks {sorted(tracks)} != one per "
                "worker ['tr0', 'tr1']")
    finally:
        await client.close()
    return failures


async def run_disagg_check() -> list[str]:
    """Fifth act (ISSUE 12): boot the router over pool-labeled STUB
    replicas — no jax — and hold the disaggregation plane to the
    contract: the pool-labeled fleet catalog (`fleet_replicas{state,
    pool}`, `fleet_route_total{reason,pool}`, `fleet_handoff_seconds`,
    `fleet_handoff_bytes_total`) visible ZERO-SEEDED in one scrape
    before any replica registers, then a real prefill->decode handoff
    moving the ok-counter and the shipped-bytes counter, and
    `/fleet/autoscale?pools=1` splitting replicas off the federated
    phase attribution."""
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.fleet.registry import DECODE, MIXED, POOLS, PREFILL, STATES
    from kubeflow_tpu.fleet.router import ROUTE_REASONS, create_router_app

    failures: list[str] = []

    def stub_pool_app(replica_name: str):
        async def gen(request):
            body = await request.json()
            return web.json_response(
                {"tokens": [[7] * int(body.get("max_new", 4))],
                 "served_by": replica_name})

        async def prefill(request):
            await request.json()
            return web.json_response(
                {"prefilled": True, "handoff": True, "blocks": 2,
                 "bytes": 4096, "handoff_s": 0.01, "request_id": ""})

        app = web.Application()
        app.router.add_post("/v1/models/{name}:generate", gen)
        app.router.add_post("/v1/models/{name}:prefill", prefill)
        return app

    router = TestClient(TestServer(
        create_router_app(block_size=4, hedge_after_s=0)))
    replicas = [TestServer(stub_pool_app(f"stub-{p}-{i}"))
                for p, i in (("prefill", 0), ("decode", 0), ("decode", 1))]
    try:
        await router.start_server()

        async def scrape() -> dict:
            text = await (await router.get("/metrics")).text()
            try:
                return parse_exposition(text)
            except ExpositionError as e:
                failures.append(f"router /metrics failed strict "
                                f"parse: {e}")
                return {}

        def sample(families: dict, fam: str, sname: str, **labels):
            f = families.get(fam)
            if f is None:
                failures.append(f"router /metrics missing family {fam}")
                return None
            key = (sname, tuple(sorted(labels.items())))
            if key not in f["samples"]:
                failures.append(
                    f"router /metrics missing sample {sname}{labels}")
                return None
            return f["samples"][key]

        # 1. the pool-labeled catalog zero-seeds before any replica
        fams = await scrape()
        for state in STATES:
            for pool in POOLS:
                if sample(fams, "fleet_replicas", "fleet_replicas",
                          state=state, pool=pool) not in (0, None):
                    failures.append(
                        f"fleet_replicas[{state},{pool}] not "
                        "zero-seeded")
        for reason in ROUTE_REASONS:
            for pool in POOLS:
                if sample(fams, "fleet_route_total", "fleet_route_total",
                          reason=reason, pool=pool) not in (0, None):
                    failures.append(
                        f"fleet_route_total[{reason},{pool}] not "
                        "zero-seeded")
        for outcome in ("ok", "skipped", "failed"):
            if sample(fams, "fleet_handoff_seconds",
                      "fleet_handoff_seconds_count",
                      outcome=outcome) not in (0, None):
                failures.append(
                    f"fleet_handoff_seconds[{outcome}] not zero-seeded")
        if sample(fams, "fleet_handoff_bytes_total",
                  "fleet_handoff_bytes_total") not in (0, None):
            failures.append("fleet_handoff_bytes_total not zero-seeded")

        # 2. register a split fleet with phase attribution, hand off
        pools = (PREFILL, DECODE, DECODE)
        for i, (srv, pool) in enumerate(zip(replicas, pools)):
            await srv.start_server()
            resp = await router.post("/fleet/register", json={
                "id": f"stub-{i}",
                "url": f"http://127.0.0.1:{srv.port}",
                "pool": pool,
                "phase_seconds": {"prefill": 3.0, "decode": 1.0},
                "active": 2, "queue_depth": 2})
            if resp.status != 200:
                failures.append(f"register stub-{i} -> {resp.status}")
        resp = await router.post("/v1/models/m:generate",
                                 json={"tokens": [[5, 6, 7, 8]],
                                       "max_new": 3})
        if resp.status != 200:
            failures.append(
                f"disagg generate -> {resp.status}: "
                f"{await resp.text()}")
        stats = await (await router.get("/fleet/stats")).json()
        if stats.get("handoff", {}).get("ok") != 1:
            failures.append(
                f"handoff did not land: {stats.get('handoff')}")
        fams = await scrape()
        if sample(fams, "fleet_handoff_seconds",
                  "fleet_handoff_seconds_count", outcome="ok") != 1:
            failures.append("fleet_handoff_seconds[ok] != 1 after "
                            "a handoff")
        if sample(fams, "fleet_handoff_bytes_total",
                  "fleet_handoff_bytes_total") != 4096:
            failures.append("fleet_handoff_bytes_total != 4096 after "
                            "a 4096-byte handoff")
        if sample(fams, "fleet_replicas", "fleet_replicas",
                  state="ready", pool=PREFILL) != 1:
            failures.append("fleet_replicas[ready,prefill] != 1")
        if sample(fams, "fleet_replicas", "fleet_replicas",
                  state="ready", pool=MIXED) not in (0, None):
            failures.append(
                "fleet_replicas[ready,mixed] != 0 in a split fleet")

        # 3. the autoscaler splits pools off the phase shares
        resp = await router.get("/fleet/autoscale",
                                params={"pools": "1", "min": "2",
                                        "max": "8"})
        rec = await resp.json()
        split = rec.get("pools")
        if not isinstance(split, dict):
            failures.append(
                f"/fleet/autoscale?pools=1 has no pool split: {rec}")
        elif (split.get("prefill", 0) < 1 or split.get("decode", 0) < 1
              or split["prefill"] + split["decode"] != rec.get("desired")):
            failures.append(
                f"pool split does not partition desired: {rec}")
    finally:
        await router.close()
        for srv in replicas:
            await srv.close()
    return failures


async def run_control_check() -> list[str]:
    """Eighth act (ISSUE 16): the decision-plane contract. Boot the
    fleet router with two declarative policies and the controller
    built but NOT ticking (interval 0 — the act drives evaluations by
    hand, no jax, no sleeps), then hold the closed loop to its
    observability promises: the policy x outcome and policy x action
    grids zero-seeded on the first scrape, the ledger at
    /fleet/decisions conserved across a healthy tick + a breach tick,
    the fired action auditable (evidence -> action -> pending
    verdict), its floor visible at /fleet/autoscale, and the
    control.action span in /debug/traces."""
    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.fleet import control
    from kubeflow_tpu.fleet import router as router_mod
    from kubeflow_tpu.obs.decisions import OUTCOMES

    failures: list[str] = []
    policies = [
        control.Policy(
            name="availability_burn_scale_out",
            signal=control.Signal(
                "slo_burn_rate",
                {"slo": "fleet_availability", "window": "short"},
                source="local"),
            threshold=1.0, clear=0.5, cooldown_s=60.0,
            verify_window_s=60.0, action="scale_out"),
        control.Policy(
            name="spec_acceptance_burn_draft_off",
            signal=control.Signal(
                "slo_burn_rate",
                {"slo": "serving_spec_acceptance", "window": "short"},
                source="federated"),
            threshold=1.0, clear=0.5, cooldown_s=60.0,
            verify_window_s=60.0, action="disable_draft"),
    ]
    app = router_mod.create_router_app(policies=policies,
                                       control_interval_s=0)
    client = TestClient(TestServer(app))
    try:
        await client.start_server()
        st = app[router_mod.FLEET_KEY]

        # -- zero-seeded decision plane on the FIRST scrape
        resp = await client.get("/metrics")
        try:
            families = parse_exposition(await resp.text())
        except ExpositionError as e:
            return [f"/metrics failed strict parse: {e}"]

        def sample(fams: dict, fam: str, sname: str, **labels):
            f = fams.get(fam)
            if f is None:
                failures.append(f"missing family {fam}")
                return None
            key = (sname, tuple(sorted(labels.items())))
            if key not in f["samples"]:
                failures.append(f"missing sample {sname}{labels}")
                return None
            return f["samples"][key]

        for pol in policies:
            for oc in OUTCOMES:
                if sample(families, "fleet_control_decisions_total",
                          "fleet_control_decisions_total",
                          policy=pol.name, outcome=oc) not in (0, None):
                    failures.append(
                        f"decisions[{pol.name},{oc}] not zero-seeded")
            for act in control.ACTIONS:
                if sample(families, "fleet_control_actions_total",
                          "fleet_control_actions_total",
                          policy=pol.name, action=act) not in (0, None):
                    failures.append(
                        f"actions[{pol.name},{act}] not zero-seeded")
        if sample(families, "slo_error_budget_remaining",
                  "slo_error_budget_remaining",
                  slo="fleet_availability") != 1.0:
            failures.append(
                "slo_error_budget_remaining[fleet_availability] "
                "should start at full budget 1.0")

        # -- a healthy tick, then a breach tick over the live router
        st.registry.register("http://127.0.0.1:1", replica_id="stub-0")
        st.obs.slo.record("fleet_availability", True)
        await st.controller.evaluate_once()
        for _ in range(4):
            st.obs.slo.record("fleet_availability", False)
        await st.controller.evaluate_once()

        resp = await client.get("/fleet/decisions")
        if resp.status != 200:
            return failures + [f"/fleet/decisions -> {resp.status}"]
        dec = await resp.json()
        if dec.get("conserved") is not True:
            failures.append(f"ledger not conserved: {dec}")
        if dec.get("evaluations") != 4:
            failures.append(
                f"want 4 evaluations (2 ticks x 2 policies), got "
                f"{dec.get('evaluations')}")
        fired = [r for r in dec.get("records", [])
                 if r.get("outcome") == "fired"]
        if len(fired) != 1:
            failures.append(
                f"want exactly one fired decision, got {len(fired)}")
        else:
            rec = fired[0]
            if rec.get("policy") != "availability_burn_scale_out":
                failures.append(f"wrong policy fired: {rec}")
            if rec.get("action") != "scale_out":
                failures.append(f"fired action not audited: {rec}")
            if rec.get("verdict") != "pending":
                failures.append(
                    f"fired decision should await its verdict: {rec}")
            ev = rec.get("evidence") or {}
            if not isinstance(ev.get("signal"), (int, float)) \
                    or ev["signal"] <= 1.0:
                failures.append(
                    f"fired decision lacks breach evidence: {ev}")

        # the ledger's counters moved with it (suppressed-vs-fired
        # split visible per policy)
        families = parse_exposition(
            await (await client.get("/metrics")).text())
        if sample(families, "fleet_control_decisions_total",
                  "fleet_control_decisions_total",
                  policy="availability_burn_scale_out",
                  outcome="fired") != 1:
            failures.append("fired not counted in decisions_total")
        if sample(families, "fleet_control_decisions_total",
                  "fleet_control_decisions_total",
                  policy="spec_acceptance_burn_draft_off",
                  outcome="below_threshold") != 2:
            failures.append(
                "unreadable/healthy policy should book below_threshold")
        if sample(families, "fleet_control_actions_total",
                  "fleet_control_actions_total",
                  policy="availability_burn_scale_out",
                  action="scale_out") != 1:
            failures.append("fired action not counted in actions_total")

        # -- the actuation is live: the desired floor reached
        # /fleet/autoscale
        auto = await (await client.get("/fleet/autoscale")).json()
        if auto.get("controller_floor") != 2:
            failures.append(
                f"scale_out floor not visible at /fleet/autoscale: "
                f"{auto}")

        # -- the fired action left a control.action span
        traces = await (await client.get(
            "/debug/traces?name=control.action&format=summary")).json()
        spans = [s for t in traces.get("traces", [])
                 for s in t.get("spans", [])]
        if not any(s.get("attrs", {}).get("outcome") == "fired"
                   for s in spans):
            failures.append(
                "no control.action span with outcome=fired in "
                "/debug/traces")
    finally:
        await client.close()
    return failures


async def run_rollout_check() -> list[str]:
    """Ninth act (ISSUE 18): the rollout plane's contract. Boot the
    fleet router with the RolloutManager built but NOT ticking
    (interval 0 — the act drives the state machine by hand with stub
    replicas and stub drain/reload/probe fns, no jax, no sleeps), then
    hold the deployment plane to its observability promises: the
    fleet_rollout_* families zero-seeded over their closed phase and
    outcome grids on the first scrape, a full publish -> canary ->
    bake -> promote cycle booked and conserved in /fleet/rollouts, a
    planted-bad second version auto-rolled-back on SLO burn with the
    restore reload counted, the version label live on fleet_replicas
    without disturbing the unlabeled totals, and every transition
    leaving a rollout.phase span in /debug/traces."""
    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.fleet import rollout as rollout_mod
    from kubeflow_tpu.fleet import router as router_mod

    failures: list[str] = []
    # bake window 0 + min_probes 1: one healthy probe promotes, one
    # bad probe burns — the cycle runs on monotonic time, no sleeps
    app = router_mod.create_router_app(
        control_interval_s=0, rollout_interval_s=0,
        rollout_bake_s=0.0, rollout_min_probes=1)
    client = TestClient(TestServer(app))
    try:
        await client.start_server()
        st = app[router_mod.FLEET_KEY]

        resp = await client.get("/metrics")
        try:
            families = parse_exposition(await resp.text())
        except ExpositionError as e:
            return [f"/metrics failed strict parse: {e}"]

        def sample(fams: dict, fam: str, sname: str, **labels):
            f = fams.get(fam)
            if f is None:
                failures.append(f"missing family {fam}")
                return None
            key = (sname, tuple(sorted(labels.items())))
            if key not in f["samples"]:
                failures.append(f"missing sample {sname}{labels}")
                return None
            return f["samples"][key]

        # -- the full phase/outcome grids exist at zero on the FIRST
        # scrape — dashboards must never meet a hole
        if sample(families, "fleet_rollout_published_total",
                  "fleet_rollout_published_total") not in (0, None):
            failures.append("fleet_rollout_published_total not "
                            "zero-seeded")
        for ph in rollout_mod.PHASES:
            if sample(families, "fleet_rollout_transitions_total",
                      "fleet_rollout_transitions_total",
                      phase=ph) not in (0, None):
                failures.append(f"transitions[{ph}] not zero-seeded")
        for oc in rollout_mod.RELOAD_OUTCOMES:
            if sample(families, "fleet_rollout_reloads_total",
                      "fleet_rollout_reloads_total",
                      outcome=oc) not in (0, None):
                failures.append(f"reloads[{oc}] not zero-seeded")
        if sample(families, "fleet_rollout_active",
                  "fleet_rollout_active") not in (0, None):
            failures.append("fleet_rollout_active should start 0")

        book = await (await client.get("/fleet/rollouts")).json()
        if book.get("conserved") is not True or book.get("started"):
            failures.append(f"empty ledger not conserved: {book}")

        # -- stub fleet + stub effectors: the state machine runs for
        # real, the I/O boundary is faked
        st.registry.register("http://127.0.0.1:1", replica_id="s0",
                             models=["m"])
        st.registry.register("http://127.0.0.1:2", replica_id="s1",
                             models=["m"])
        probe_result = {"res": (0.01, True)}
        reloads: list[tuple[str, str]] = []

        async def _drain(rid):
            return None

        async def _reload(rep, entry):
            reloads.append((rep.id, entry["version"]))
            st.registry.heartbeat(rep.id, version=entry["version"])
            return True

        async def _probe(rep):
            return probe_result["res"]

        st.rollout.drain_fn = _drain
        st.rollout.reload_fn = _reload
        st.rollout.probe_fn = _probe

        # -- good cycle: publish step-1, drive to completed
        resp = await client.post(
            "/fleet/versions",
            json={"version": "step-1", "model": "m", "step": 1,
                  "source": {"checkpoint": "/ckpt", "step": 1}})
        if resp.status != 200 or not (await resp.json()).get(
                "published"):
            return failures + [f"publish refused: {resp.status}"]
        for _ in range(20):
            await st.rollout.step()
            if st.rollout_ledger.phase_of("step-1") == "completed":
                break
        else:
            return failures + [
                f"step-1 never completed "
                f"(phase={st.rollout_ledger.phase_of('step-1')})"]

        # -- bad cycle: probes burn the canary SLO, must roll back and
        # restore the touched replica to step-1
        probe_result["res"] = (5.0, False)
        resp = await client.post(
            "/fleet/versions",
            json={"version": "step-2-bad", "model": "m", "step": 2,
                  "source": {"checkpoint": "/ckpt", "step": 2}})
        if resp.status != 200:
            return failures + [f"bad publish -> {resp.status}"]
        for _ in range(20):
            await st.rollout.step()
            if st.rollout_ledger.phase_of("step-2-bad") \
                    == "rolled_back":
                break
        else:
            return failures + [
                f"step-2-bad never rolled back "
                f"(phase={st.rollout_ledger.phase_of('step-2-bad')})"]

        book = await (await client.get("/fleet/rollouts")).json()
        if book.get("conserved") is not True:
            failures.append(f"ledger not conserved: {book}")
        hist = (book.get("rollouts", {}).get("step-1") or {}) \
            .get("history")
        if hist != ["published", "canarying", "baking", "promoting",
                    "completed"]:
            failures.append(f"step-1 history wrong: {hist}")
        hist = (book.get("rollouts", {}).get("step-2-bad") or {}) \
            .get("history")
        if hist != ["published", "canarying", "baking", "rolled_back"]:
            failures.append(f"step-2-bad history wrong: {hist}")
        burn_rec = next(
            (r for r in book.get("records", [])
             if r.get("version") == "step-2-bad"
             and r.get("phase") == "rolled_back"), None)
        if burn_rec is None \
                or burn_rec["evidence"].get("reason") != "slo_burn":
            failures.append(
                f"rollback not booked with slo_burn evidence: "
                f"{burn_rec}")
        if book.get("manager", {}).get("current") != "step-1":
            failures.append(
                f"current should stay step-1 after the rollback: "
                f"{book.get('manager')}")
        if book.get("active") != 0:
            failures.append(f"no rollout should stay active: {book}")
        # the bad canary was restored: its LAST reload is back to
        # step-1 (canary -> bad, restore -> step-1)
        if not reloads or reloads[-1][1] != "step-1":
            failures.append(f"touched replica not restored: {reloads}")

        # -- the counters and the version label moved with the cycle
        families = parse_exposition(
            await (await client.get("/metrics")).text())
        if sample(families, "fleet_rollout_published_total",
                  "fleet_rollout_published_total") != 2:
            failures.append("published_total should count 2 versions")
        for ph, want in (("completed", 1), ("rolled_back", 1),
                         ("published", 2), ("canarying", 2)):
            if sample(families, "fleet_rollout_transitions_total",
                      "fleet_rollout_transitions_total",
                      phase=ph) != want:
                failures.append(f"transitions[{ph}] != {want}")
        if sample(families, "fleet_rollout_reloads_total",
                  "fleet_rollout_reloads_total",
                  outcome="ok") != len(reloads):
            failures.append(
                f"reloads[ok] should count all {len(reloads)} "
                "stub reloads")
        if sample(families, "fleet_rollout_active",
                  "fleet_rollout_active") != 0:
            failures.append("fleet_rollout_active should end 0")
        # both stub replicas ended back on step-1: the versioned
        # fleet_replicas series shows it, the unlabeled total is
        # undisturbed (PR 13 parallel-series pattern)
        if sample(families, "fleet_replicas", "fleet_replicas",
                  state="ready", pool="mixed") != 2:
            failures.append(
                "version-blind fleet_replicas[ready,mixed] != 2")
        if sample(families, "fleet_replicas", "fleet_replicas",
                  state="ready", version="step-1") != 2:
            failures.append(
                "fleet_replicas[ready,version=step-1] != 2")

        # -- every transition left a rollout.phase span
        traces = await (await client.get(
            "/debug/traces?name=rollout.phase&format=summary")).json()
        spans = [s for t in traces.get("traces", [])
                 for s in t.get("spans", [])]
        booked = sum(1 for s in spans
                     if s.get("name") == "rollout.phase")
        if booked != book["transitions"]:
            failures.append(
                f"want one rollout.phase span per transition "
                f"({book['transitions']}), got {booked}")
    finally:
        await client.close()
    return failures


async def run_scenario_check() -> list[str]:
    """Scenario act (ISSUE 20): the record/generate/replay contract,
    no jax. Boot a STUB replica — the real SSE generate surface and
    the real `TimelineStore` behind the real timeline endpoints, with
    a paced fake decode — then hold the engine to its promises: a
    generated flash crowd replays open-loop through `HttpTarget` with
    its expect block green and bounded arrival skew; an abandon-retry
    storm books every scheduled hang-up as abandoned (zero client
    failures — the cancellation path, not an error path); the run
    records back off `/v1/requests/timelines` into a trace whose
    arrivals, shapes, and hang-ups match what was offered; and the
    RECORDING replays with the same outcome (the record -> replay
    loop closed without an engine in sight)."""
    import asyncio

    from aiohttp import web
    from aiohttp.test_utils import TestServer

    from kubeflow_tpu import scenarios
    from kubeflow_tpu.obs.timeline import RequestTimeline, TimelineStore

    failures: list[str] = []
    store = TimelineStore(capacity=256)

    async def gen(request):
        body = await request.json()
        rid = request.headers.get("X-Request-Id", "")
        tl = RequestTimeline(
            rid, tenant=request.headers.get("X-Tenant", ""),
            prompt_tokens=len(body["tokens"][0]),
            max_new=int(body.get("max_new", 4)))
        tl.event("enqueue")
        store.add(tl)
        resp = web.StreamResponse()
        resp.content_type = "text/event-stream"
        await resp.prepare(request)
        tl.event("admit")
        # 4 ms per token: slow enough that an abandoning client's
        # hang-up always lands mid-stream, fast enough to stay a gate
        for _ in range(tl.max_new):
            await asyncio.sleep(0.004)
            tl.token()
            await resp.write(b'data: {"tokens": [[7]]}\n\n')
        tl.event("finish")
        await resp.write(b'data: {"done": true}\n\n')
        return resp

    async def timelines_index(request):
        return web.json_response({"requests": store.ids()})

    async def timeline_one(request):
        tl = store.get(request.match_info["rid"])
        if tl is None:
            raise web.HTTPNotFound
        return web.json_response(tl.to_dict())

    app = web.Application()
    app.router.add_post("/v1/models/{name}:generate", gen)
    app.router.add_get("/v1/requests/timelines", timelines_index)
    app.router.add_get("/v1/requests/{rid}/timeline", timeline_one)
    server = TestServer(app)
    await server.start_server()
    base = f"http://127.0.0.1:{server.port}"
    loop = asyncio.get_running_loop()

    def run(tr, name):
        target = scenarios.HttpTarget(base, seed=tr.seed)
        recs = scenarios.replay(tr, target,
                                max_workers=len(tr.requests) + 8)
        result = scenarios.summarize(tr, recs)
        for f in scenarios.check_expect(tr.expect, result):
            failures.append(f"{name}: {f}")
        return result

    try:
        # 1. a flash crowd replays clean, open-loop
        crowd = scenarios.generate(
            "flash_crowd", 5, duration_s=2.0, base_rps=2.0,
            burst_len_s=0.5, burst_rps=20.0, prompt_tokens=8,
            prefix_tokens=4, max_new=4)
        res = await loop.run_in_executor(
            None, lambda: run(crowd, "flash_crowd"))
        skew = res.get("arrival_skew_p95_s")
        if skew is None or skew > 0.25:
            failures.append(
                f"flash_crowd: open-loop arrival skew p95 {skew}s — "
                "the replayer is not keeping the trace's schedule")

        # 2. an abandon-retry storm: every scheduled hang-up fires,
        # none books as a failure (the expect block pins the count)
        storm = scenarios.generate("abandon_retry", 4, n=6, rps=8.0)
        res = await loop.run_in_executor(
            None, lambda: run(storm, "abandon_retry"))
        n_abandoned = res.get("abandoned", 0)

        # 3. record the storm back off the timeline endpoints
        rec = await loop.run_in_executor(
            None, lambda: scenarios.record_from_server(
                base, ids=[r.id for r in storm.requests],
                name="storm-recorded"))
        if {r.id for r in rec.requests} \
                != {r.id for r in storm.requests}:
            failures.append(
                "recording lost requests: "
                f"{len(rec.requests)}/{len(storm.requests)}")
        want = {r.id: r for r in storm.requests}
        # recordings re-base to their first enqueue; compare against
        # the offered trace re-based the same way
        t0 = min(r.at for r in storm.requests)
        for r in rec.requests:
            w = want.get(r.id)
            if w is None:
                continue
            if (r.prompt_tokens, r.max_new) != (w.prompt_tokens,
                                                w.max_new):
                failures.append(
                    f"recorded shape drifted for {r.id}: "
                    f"({r.prompt_tokens}, {r.max_new}) != "
                    f"({w.prompt_tokens}, {w.max_new})")
            if abs(r.at - (w.at - t0)) > 0.25:
                failures.append(
                    f"recorded arrival drifted for {r.id}: "
                    f"{r.at} vs offered {w.at - t0}")
            if (r.abandon_at is not None) \
                    != (w.abandon_at is not None):
                failures.append(
                    f"recorded hang-up state wrong for {r.id}: "
                    f"abandon_at={r.abandon_at} (offered "
                    f"{w.abandon_at})")
        if scenarios.Trace.loads(rec.dumps()).dumps() != rec.dumps():
            failures.append("recorded trace does not round-trip "
                            "byte-identically")

        # 4. close the loop: the RECORDING replays with the same
        # outcome (same hang-ups, still zero failures)
        rec.expect["abandoned"] = {"min": n_abandoned,
                                   "max": n_abandoned}
        await loop.run_in_executor(
            None, lambda: run(rec, "recorded-replay"))
    finally:
        await server.close()
    return failures


def main(argv: list[str] | None = None) -> int:
    """Default: all seven acts. `python -m ci.obs_check profile` runs
    only the serving step-anatomy act (`make profile-check`); it and
    `cache` are the acts that compile jax programs, so the fast acts
    stay usable on their own. `python -m ci.obs_check disagg` is the
    metrics half of `make disagg-check`, `cache` of
    `make cache-check`."""
    import asyncio

    argv = sys.argv[1:] if argv is None else argv
    acts = {
        "check": run_check,
        "profile": run_profile_check,
        "fleet": run_fleet_check,
        "train": run_train_check,
        "train-obs": run_train_obs_check,
        "disagg": run_disagg_check,
        "cache": run_cache_check,
        "cache-tier": run_cache_tier_check,
        "control": run_control_check,
        "rollout": run_rollout_check,
        "scenario": run_scenario_check,
    }
    wanted = argv or list(acts)
    unknown = [a for a in wanted if a not in acts]
    if unknown:
        print(f"obs-check: unknown acts {unknown}; known: "
              f"{list(acts)}", file=sys.stderr)
        return 2
    failures = []
    for a in wanted:
        failures += asyncio.run(acts[a]())
    if failures:
        for f in failures:
            print(f"obs-check FAIL: {f}", file=sys.stderr)
        return 1
    print(f"obs-check [{','.join(wanted)}]: /metrics strict-parses, "
          "/debug/traces is Chrome-trace-loadable (spans + counter "
          "tracks), /debug/profile serves the step anatomy, "
          "/fleet/metrics federates two replicas under the same "
          "contract, the train_* catalog zero-seeds + tracks "
          "membership, the pool-labeled disaggregation plane "
          "zero-seeds + tracks a prefill->decode handoff, the "
          "KV-cache ledger conserves (causes sum to frees, zero "
          "unattributed) with a hashed heat digest on the model card, "
          "/elastic/metrics federates goodput ledgers conserved "
          "(cause counters == wall) with per-worker trace tracks, "
          "and the decision plane zero-seeds its policy x "
          "outcome/action grids with the /fleet/decisions ledger "
          "conserved and the fired action auditable end to end, "
          "and the rollout plane zero-seeds its phase/outcome grids "
          "with /fleet/rollouts conserved across a promote and an "
          "SLO-burn rollback, and the scenario engine closes its "
          "record -> replay loop against a stub replica (expect "
          "blocks green, hang-ups booked abandoned, recordings "
          "byte-stable and faithful)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
