"""Observability layer: histogram exposition, span tracing, and the
three instrumented layers (control plane, serving, training).

The strict exposition parser under test here is the SAME one the
`make obs-check` CI gate runs against a live app (ci/obs_check.py) —
tests pin its pedantry, the gate applies it.
"""

import json
import math
import threading

import pytest
from aiohttp.test_utils import TestClient, TestServer

pytest_plugins = ("aiohttp.pytest_plugin",)

from ci.obs_check import ExpositionError, parse_exposition
from kubeflow_tpu import obs
from kubeflow_tpu.controlplane.cluster import Cluster, ClusterConfig
from kubeflow_tpu.controlplane.metrics import (
    Counter,
    MetricsHistory,
    Registry,
)


# -- histogram exposition ------------------------------------------------


def _family(text, name):
    fams = parse_exposition(text)
    assert name in fams, f"{name} missing from exposition"
    return fams[name]


def test_histogram_buckets_cumulative_and_inf():
    reg = Registry()
    h = obs.Histogram("lat_seconds", "latency", reg,
                      buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v, op="x")
    fam = _family(reg.render(), "lat_seconds")
    assert fam["type"] == "histogram"
    by_le = {
        dict(labels)["le"]: v
        for (sname, labels), v in fam["samples"].items()
        if sname == "lat_seconds_bucket"
    }
    assert by_le == {"0.1": 1.0, "1": 3.0, "10": 4.0, "+Inf": 5.0}
    samples = {s: v for (s, _), v in fam["samples"].items()}
    assert samples["lat_seconds_count"] == 5.0
    assert samples["lat_seconds_sum"] == pytest.approx(56.05)


def test_histogram_le_boundary_is_inclusive():
    reg = Registry()
    h = obs.Histogram("b_seconds", "b", reg, buckets=(1.0, 2.0))
    h.observe(1.0)  # exactly on a boundary → counted in le="1"
    fam = _family(reg.render(), "b_seconds")
    by_le = {dict(l)["le"]: v for (s, l), v in fam["samples"].items()
             if s.endswith("_bucket")}
    assert by_le["1"] == 1.0


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        obs.Histogram("x", "x", buckets=())
    with pytest.raises(ValueError):
        obs.Histogram("x", "x", buckets=(1.0, 1.0))
    with pytest.raises(ValueError):
        obs.Histogram("x", "x", buckets=(2.0, 1.0))


def test_get_or_create_histogram_idempotent():
    reg = Registry()
    a = obs.get_or_create_histogram(reg, "h_seconds", "h")
    b = obs.get_or_create_histogram(reg, "h_seconds", "h")
    assert a is b
    Counter("c_total", "c", reg)
    with pytest.raises(ValueError):
        obs.get_or_create_histogram(reg, "c_total", "not a counter")


def test_label_value_escaping_round_trip():
    reg = Registry()
    c = Counter("esc_total", "escapes", reg)
    nasty = 'back\\slash "quoted"\nnewline'
    c.inc(path=nasty)
    text = reg.render()
    fam = _family(text, "esc_total")
    ((_, labels),) = fam["samples"].keys()
    assert dict(labels)["path"] == nasty  # escape → unescape round-trips


def test_render_under_concurrent_inc():
    reg = Registry()
    c = Counter("busy_total", "busy", reg)
    stop = threading.Event()
    n_workers, per_worker = 4, 2000

    def work():
        for i in range(per_worker):
            c.inc(worker="w")  # same series: max contention

    threads = [threading.Thread(target=work) for _ in range(n_workers)]
    for t in threads:
        t.start()
    # every mid-flight render must strict-parse
    while any(t.is_alive() for t in threads):
        parse_exposition(reg.render())
    for t in threads:
        t.join()
    assert c.value(worker="w") == n_workers * per_worker


def test_a_collector_that_returns_false_is_dropped_after_that_render():
    reg = Registry()
    g = Counter("seen_total", "renders each collector saw", reg)
    left = [2]

    def twice():
        g.inc(who="twice")
        left[0] -= 1
        return left[0] > 0

    reg.register_collector(twice)
    reg.register_collector(lambda: g.inc(who="always"))
    for _ in range(4):
        reg.render()
    assert g.value(who="twice") == 2 and g.value(who="always") == 4


def test_strict_parser_catches_render_bugs():
    with pytest.raises(ExpositionError):
        parse_exposition("no_type_decl 1\n")
    with pytest.raises(ExpositionError):  # missing +Inf
        parse_exposition(
            "# HELP h x\n# TYPE h histogram\n"
            'h_bucket{le="1"} 1\nh_sum 1\nh_count 1\n')
    with pytest.raises(ExpositionError):  # non-cumulative
        parse_exposition(
            "# HELP h x\n# TYPE h histogram\n"
            'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
            "h_sum 1\nh_count 3\n")
    with pytest.raises(ExpositionError):  # duplicate series
        parse_exposition(
            "# HELP c x\n# TYPE c counter\nc 1\nc 2\n")


def test_metrics_history_live_shape_validated():
    from kubeflow_tpu.controlplane.store import Store

    hist = MetricsHistory(Store())
    hist.sample()
    assert hist.series(5, live=True) != []
    assert hist.series(5, live=({}, {})) is not None
    with pytest.raises(ValueError, match="tpu_by_namespace"):
        hist.series(5, live=(1, 2))
    with pytest.raises(ValueError, match="pair of dicts"):
        hist.series(5, live=({},))


# -- tracer --------------------------------------------------------------


def test_nested_spans_share_trace_id():
    tr = obs.Tracer()
    with tr.span("root") as root:
        with tr.span("child") as child:
            with tr.span("grandchild") as gc:
                assert gc.trace_id == root.trace_id
                assert gc.parent_id == child.span_id
            assert child.parent_id == root.span_id
        assert tr.current_span() is root
    assert tr.current_span() is None
    (trace,) = tr.traces()
    assert trace["name"] == "root"
    names = {s["name"] for s in trace["spans"]}
    assert names == {"root", "child", "grandchild"}
    assert len({s["traceId"] for s in trace["spans"]}) == 1


def test_span_name_attr_does_not_collide():
    tr = obs.Tracer()
    with tr.span("reconcile", name="nb1", kind="Notebook") as s:
        assert s.attrs["name"] == "nb1"
    assert tr.traces()[0]["name"] == "reconcile"


def test_ring_evicts_oldest_first():
    tr = obs.Tracer(max_traces=3)
    for i in range(5):
        with tr.span(f"op{i}"):
            pass
    got = [t["name"] for t in tr.traces()]
    assert got == ["op4", "op3", "op2"]  # newest first, 0/1 evicted


def test_span_error_attr_and_commit():
    tr = obs.Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("nope")
    (trace,) = tr.traces()
    assert trace["spans"][0]["attrs"]["error"] == "RuntimeError"


def test_chrome_trace_export_shape():
    tr = obs.Tracer()
    with tr.span("outer", label="x"):
        with tr.span("inner"):
            pass
    doc = tr.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == 2
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        assert ev["args"]["trace_id"]
    json.dumps(doc)  # must be JSON-serializable as-is


def test_wrap_propagates_context_into_threads():
    from concurrent.futures import ThreadPoolExecutor

    tr = obs.Tracer()
    with ThreadPoolExecutor(1) as pool:
        with tr.span("request") as root:
            fut = pool.submit(tr.wrap(lambda: 42, "device.work"))
            assert fut.result() == 42
    (trace,) = tr.traces()
    device = [s for s in trace["spans"] if s["name"] == "device.work"]
    assert device and device[0]["traceId"] == root.trace_id
    assert device[0]["parentId"] == root.span_id


def test_traces_response_payload_query_handling():
    tr = obs.Tracer()
    with tr.span("a"):
        pass
    with tr.span("b"):
        pass
    assert [e["name"] for e in obs.traces_response_payload(
        tr, {"name": "a"})["traceEvents"]] == ["a"]
    summary = obs.traces_response_payload(tr, {"format": "summary"})
    assert {t["name"] for t in summary["traces"]} == {"a", "b"}
    with pytest.raises(ValueError):
        obs.traces_response_payload(tr, {"limit": "nope"})


# -- control plane integration ------------------------------------------


@pytest.fixture()
def cluster():
    with Cluster(ClusterConfig(tpu_slices={"v5e-1": 2})) as c:
        yield c


def test_reconcile_metrics_and_spans(cluster):
    from kubeflow_tpu.api.core import Container, PodTemplateSpec
    from kubeflow_tpu.api.crds import Notebook

    nb = Notebook()
    nb.metadata.name = "obs-nb"
    nb.metadata.namespace = "default"
    nb.spec.template = PodTemplateSpec()
    nb.spec.template.spec.containers.append(
        Container(name="obs-nb", image="kubeflow-tpu/jupyter-jax:latest"))
    cluster.store.create(nb)
    assert cluster.wait_idle()

    fams = parse_exposition(cluster.metrics.registry.render())
    recon = fams["reconcile_duration_seconds"]
    assert any(("kind", "NotebookController") in labels
               for _, labels in recon["samples"])
    assert fams["workqueue_queue_latency_seconds"]["samples"]
    assert fams["workqueue_depth"]["samples"]  # scrape-time collector
    # no reconcile blew up on the instrumentation itself
    for (_, labels), v in fams["reconcile_total"]["samples"].items():
        if ("severity", "error") in labels:
            assert v == 0
    # reconcile spans landed in the cluster-shared tracer
    assert any(t["name"] == "reconcile"
               for t in cluster.tracer.traces())


async def test_platform_trace_header_and_endpoint(loop):
    cluster = Cluster(ClusterConfig(tpu_slices={"v5e-1": 1})).start()
    app = cluster.create_web_app(csrf=False)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        r1 = await client.get("/healthz")
        r2 = await client.get("/healthz")
        t1, t2 = r1.headers["X-Trace-Id"], r2.headers["X-Trace-Id"]
        assert t1 and t2 and t1 != t2  # per-request trace ids

        r = await client.get("/debug/traces")
        assert r.status == 200
        doc = await r.json()
        reqs = [e for e in doc["traceEvents"]
                if e["name"] == "http.request"]
        assert {e["args"]["trace_id"] for e in reqs} >= {t1, t2}

        r = await client.get("/debug/traces?format=summary&limit=1")
        assert len((await r.json())["traces"]) == 1
        r = await client.get("/debug/traces?limit=zzz")
        assert r.status == 400
    finally:
        await client.close()
        cluster.stop()


# -- serving integration -------------------------------------------------


@pytest.fixture(scope="module")
def llama_engine():
    import jax

    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import (
        EngineConfig, InferenceEngine, LLAMA_FAMILY,
    )

    cfg = llama.LLAMA_TINY
    params = llama.init(jax.random.key(0), cfg)
    return InferenceEngine(params, cfg, LLAMA_FAMILY,
                           EngineConfig(max_len=64))


async def test_serving_request_traces_and_metrics(llama_engine):
    from kubeflow_tpu.serving import server as server_lib

    app = server_lib.create_serving_app({"m": llama_engine})
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        body = {"tokens": [[1, 2, 3, 4]], "max_new": 2}
        r1 = await client.post("/v1/models/m:generate", json=body)
        r2 = await client.post("/v1/models/m:generate", json=body)
        assert r1.status == 200 and r2.status == 200
        t1, t2 = r1.headers["X-Trace-Id"], r2.headers["X-Trace-Id"]
        assert t1 and t2 and t1 != t2
        # 404s carry trace ids too (middleware covers HTTPException)
        r = await client.post("/v1/models/nope:generate", json=body)
        assert r.status == 404 and r.headers["X-Trace-Id"]

        # the request trace has engine/device child spans under its root
        r = await client.get("/debug/traces")
        doc = await r.json()
        ev_by_trace = {}
        for e in doc["traceEvents"]:
            ev_by_trace.setdefault(e["args"]["trace_id"], []).append(e)
        spans = ev_by_trace[t1]
        names = {e["name"] for e in spans}
        assert "http.request" in names
        assert "engine.generate" in names
        assert "device.generate" in names  # executor-thread span nested
        root = next(e for e in spans if e["name"] == "http.request")
        child = next(e for e in spans if e["name"] == "engine.generate")
        assert child["args"]["parent_id"] == root["args"]["span_id"]

        # /metrics strict-parses; request latency + batch size observed
        text = await (await client.get("/metrics")).text()
        fams = parse_exposition(text)
        lat = fams["serving_request_duration_seconds"]
        assert any(
            ("route", "/v1/models/{name}:generate") in labels
            for _, labels in lat["samples"])
        bs = {s: v for (s, _), v in fams["serving_batch_size"]["samples"].items()}
        assert bs["serving_batch_size_count"] >= 2.0
        assert fams["serving_time_to_first_token_seconds"]["samples"]
    finally:
        await client.close()


# -- training integration ------------------------------------------------


def _tiny_trainer(registry, tracer):
    import jax

    from kubeflow_tpu.models import llama
    from kubeflow_tpu.parallel import MeshSpec, create_mesh
    from kubeflow_tpu.train import TrainConfig, Trainer

    cfg = llama.LLAMA_TINY
    return Trainer(
        mesh=create_mesh(MeshSpec(data=2, fsdp=2, tensor=2)),
        apply_fn=lambda p, t: llama.apply(p, cfg, t),
        init_fn=lambda k: llama.init(k, cfg),
        logical_axes=llama.param_logical_axes(cfg),
        train_config=TrainConfig(warmup_steps=1, total_steps=10),
        registry=registry, tracer=tracer,
    )


def test_trainer_wires_histograms_without_stepping():
    reg, tr = Registry(), obs.Tracer()
    trainer = _tiny_trainer(reg, tr)
    fams = parse_exposition(reg.render())
    assert fams["train_step_seconds"]["type"] == "histogram"
    assert fams["train_compile_seconds"]["type"] == "histogram"
    assert trainer.step_seconds.count() == 0


@pytest.mark.slow
def test_trainer_step_observes_histograms_and_spans():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models import llama

    reg, tr = Registry(), obs.Tracer()
    trainer = _tiny_trainer(reg, tr)
    state = trainer.init(jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, llama.LLAMA_TINY.vocab_size, (8, 16)), jnp.int32)
    state, _ = trainer.step(state, toks, jnp.roll(toks, -1, axis=1))
    state, _ = trainer.step(state, toks, jnp.roll(toks, -1, axis=1))

    assert trainer.step_seconds.count() == 2
    assert trainer.compile_seconds.count() == 1  # first step only
    parse_exposition(reg.render())  # histograms render validly
    steps = [t for t in tr.traces() if t["name"] == "train.step"]
    assert len(steps) == 2
    assert steps[-1]["spans"][0]["attrs"]["compile"] is True


# -- metrics federation (ISSUE 6) ----------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_federation_round_trip_sums_and_merges():
    """Two real registries -> render -> federate -> strict re-parse:
    counters sum, histogram _sum/_count add, and the merged document
    itself passes the same parser the replicas' /metrics must."""
    regs = [Registry(), Registry()]
    for i, reg in enumerate(regs):
        Counter("fed_requests_total", "reqs", reg).inc(3 + i)
        h = obs.get_or_create_histogram(reg, "fed_latency_seconds", "lat")
        h.observe(0.01 * (i + 1))
        h.observe(0.2)
    merged = parse_exposition(obs.federate(
        {"r0": regs[0].render(), "r1": regs[1].render(), "gone": None}))
    c = merged["fed_requests_total"]["samples"]
    assert c[("fed_requests_total", ())] == 7
    hs = merged["fed_latency_seconds"]["samples"]
    assert hs[("fed_latency_seconds_count", ())] == 4
    assert hs[("fed_latency_seconds_sum", ())] == pytest.approx(0.43)
    up = merged["fleet_federation_up"]["samples"]
    assert up[("fleet_federation_up", (("replica", "r0"),))] == 1
    assert up[("fleet_federation_up", (("replica", "gone"),))] == 0


def test_federation_union_grid_floor_interpolation():
    """Replicas with DIFFERENT bucket grids merge on the union grid;
    a replica contributes its cumulative count at its largest own
    boundary <= u. Hand-built texts pin the arithmetic exactly."""
    a = ("# HELP h x\n# TYPE h histogram\n"
         'h_bucket{le="0.1"} 1\nh_bucket{le="+Inf"} 2\n'
         "h_sum 0.6\nh_count 2\n")
    b = ("# HELP h x\n# TYPE h histogram\n"
         'h_bucket{le="0.5"} 3\nh_bucket{le="+Inf"} 3\n'
         "h_sum 0.9\nh_count 3\n")
    merged = parse_exposition(obs.federate({"a": a, "b": b}))
    hs = merged["h"]["samples"]
    # at 0.1: a contributes 1, b has no boundary <= 0.1 -> 0
    assert hs[("h_bucket", (("le", "0.1"),))] == 1
    # at 0.5: a floors to its 0.1 bucket (1), b contributes 3
    assert hs[("h_bucket", (("le", "0.5"),))] == 4
    assert hs[("h_bucket", (("le", "+Inf"),))] == 5
    assert hs[("h_count", ())] == 5


def test_federation_type_conflict_and_bad_replica():
    """A TYPE disagreement is a deploy bug -> ExpositionError; a
    replica whose text fails the strict parse is marked down instead
    of poisoning the merge."""
    good = "# HELP x y\n# TYPE x counter\nx 1\n"
    with pytest.raises(ExpositionError, match="TYPE conflict"):
        obs.merge_families([
            parse_exposition(good),
            parse_exposition("# HELP x y\n# TYPE x gauge\nx 1\n")])
    merged = parse_exposition(obs.federate(
        {"ok": good, "junk": "not an exposition {{{"}))
    up = merged["fleet_federation_up"]["samples"]
    assert up[("fleet_federation_up", (("replica", "ok"),))] == 1
    assert up[("fleet_federation_up", (("replica", "junk"),))] == 0
    assert merged["x"]["samples"][("x", ())] == 1


# -- cross-process trace propagation (ISSUE 6) ---------------------------


def test_span_from_remote_adopts_context():
    tr = obs.Tracer()
    with tr.span_from_remote("http.request", "ab" * 16, "cd" * 8,
                             route="/x") as s:
        assert s.trace_id == "ab" * 16
        assert s.parent_id == "cd" * 8
        with tr.span("inner") as child:
            assert child.trace_id == "ab" * 16
    t = tr.traces(trace_id="ab" * 16)[0]
    assert t["name"] == "http.request"
    assert {sp["name"] for sp in t["spans"]} == {"http.request", "inner"}


def test_span_from_remote_rejects_malformed_ids():
    """Propagation headers are attacker-controlled: malformed ids must
    fall back to a fresh local trace, not corrupt the ring."""
    tr = obs.Tracer()
    for bad_tid, bad_psid in (("", "cd" * 8), ("ab" * 16, "NOPE"),
                              ("ab" * 40, "cd" * 8), ("g" * 16, "cd" * 8)):
        with tr.span_from_remote("r", bad_tid, bad_psid) as s:
            assert s.trace_id != bad_tid or s.parent_id != bad_psid
    # an already-open local parent wins over the remote context
    with tr.span("outer") as outer:
        with tr.span_from_remote("r", "ab" * 16, "cd" * 8) as s:
            assert s.trace_id == outer.trace_id


def test_merge_chrome_traces_assigns_process_tracks():
    tr_a, tr_b = obs.Tracer(), obs.Tracer()
    with tr_a.span_from_remote("route", "ee" * 16, "ff" * 8):
        pass
    with tr_b.span_from_remote("serve", "ee" * 16, "ff" * 8):
        pass
    doc = obs.merge_chrome_traces([
        ("router", tr_a.chrome_trace(trace_id="ee" * 16)),
        ("replica-0", tr_b.chrome_trace(trace_id="ee" * 16))])
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert [m["args"]["name"] for m in meta] == ["router", "replica-0"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in spans} == {1, 2}
    assert {e["args"]["trace_id"] for e in spans} == {"ee" * 16}


# -- request timelines + SLO burn rates (ISSUE 6) ------------------------


def test_request_timeline_itl_excludes_preemption_holes():
    clk = _FakeClock()
    tl = obs.RequestTimeline("req-1", model="tiny", tenant="live",
                             clock=clk)
    tl.event("enqueue")
    clk.t = 1.0
    tl.event("admit", slot=0)
    clk.t = 1.5
    assert tl.token() is None          # first token: no predecessor
    clk.t = 1.6
    assert tl.token() == pytest.approx(0.1)
    clk.t = 2.0
    tl.event("preempt", slot=0)
    clk.t = 5.0
    tl.event("resume", slot=1)
    clk.t = 5.2
    assert tl.token() is None          # gap spans the hole: not an ITL
    clk.t = 5.3
    assert tl.token() == pytest.approx(0.1)
    tl.event("finish")
    assert tl.done
    assert tl.queue_wait_s == pytest.approx(1.0)
    assert tl.ttft_s == pytest.approx(1.5)
    assert tl.itls() == [pytest.approx(0.1), pytest.approx(0.1)]
    d = tl.to_dict()
    assert d["tokens"] == 4 and d["itl"]["count"] == 2
    assert d["events"][0]["t"] == 0.0  # times relative to enqueue
    json.dumps(d)  # endpoint shape must be JSON-serializable


def test_timeline_store_evicts_oldest():
    store = obs.TimelineStore(capacity=2)
    for rid in ("a", "b", "c"):
        store.add(obs.RequestTimeline(rid))
    assert store.get("a") is None
    assert store.get("c") is not None and len(store) == 2


def test_slo_engine_burn_rates_windowed():
    clk = _FakeClock()
    eng = obs.SloEngine(
        [obs.Slo("ttft", 0.95, threshold_s=0.5),
         obs.Slo("errors", 0.99)],
        short_window_s=60, long_window_s=600, clock=clk)
    # zero-seeded: every slo x window emitted before any traffic
    assert {(lbl["slo"], lbl["window"]) for _, lbl, _ in
            eng.expositions()} == {("ttft", "short"), ("ttft", "long"),
                                   ("errors", "short"), ("errors", "long")}
    for v in (0.1, 0.2, 0.6, 0.7):     # 2 bad of 4 -> frac 0.5
        eng.observe("ttft", v)
    eng.observe("unknown", 9.9)        # dropped silently, never raises
    rates = eng.burn_rates()
    assert rates[("ttft", "short")] == pytest.approx(0.5 / 0.05)
    # the bad samples age out of the short window but not the long one
    clk.t = 120.0
    for v in (0.1, 0.1):
        eng.observe("ttft", v)
    rates = eng.burn_rates()
    assert rates[("ttft", "short")] == 0.0
    assert rates[("ttft", "long")] == pytest.approx((2 / 6) / 0.05)
    eng.record("errors", good=False)
    assert eng.burn_rates()[("errors", "short")] == \
        pytest.approx(1.0 / 0.01)
