"""Multi-tenant QoS: ledger math, fair-share scheduling, preemption
replay, prefix isolation, and the X-Tenant plumbing through the
serving app and fleet router.

Scheduler/ledger tests run on fake clocks and fake queue items (no
jax); the batcher tests use the sharpened-head LLAMA_TINY oracle from
test_continuous (greedy argmax cannot flip between batch shapes), so
"preemption is token-identical" is checked against solo generate."""

import asyncio
import json

import pytest
from aiohttp import web

pytest_plugins = ("aiohttp.pytest_plugin",)

from kubeflow_tpu.obs import LabelGuard, OVERFLOW_LABEL
from kubeflow_tpu.serving.paged import BlockPool, RadixPrefixCache
from kubeflow_tpu.tenancy import (
    DEFAULT_TENANT,
    SERVING_TENANT_ANNOTATION,
    FairShareQueue,
    ReqMeta,
    TenancyConfig,
    TenantLedger,
    TenantSpec,
    Throttled,
    TokenBucket,
    config_from_dict,
    config_from_profiles,
    load_config,
    tenant_from_profile,
)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# -- token bucket ----------------------------------------------------------


def test_token_bucket_refill_math():
    clk = FakeClock()
    b = TokenBucket(rate=2.0, burst=4.0, clock=clk)
    assert b.try_take(4.0)          # drain the burst
    assert not b.try_take(1.0)
    clk.t = 1.0                      # +2 tokens
    assert b.delay_until(3.0) == pytest.approx(0.5)
    assert not b.try_take(3.0)
    clk.t = 1.5
    assert b.try_take(3.0)
    # unlimited bucket never throttles and never reports delay
    free = TokenBucket(rate=0.0, clock=clk)
    assert free.try_take(10**9) and free.delay_until(10**9) == 0.0


def test_token_bucket_debt_pacing():
    clk = FakeClock()
    b = TokenBucket(rate=10.0, clock=clk)  # burst defaults to rate
    b.take(15.0)                     # generated tokens: may go negative
    assert b.level == pytest.approx(-5.0)
    assert b.debt_delay() == pytest.approx(0.5)
    clk.t = 0.5
    assert b.debt_delay() == 0.0


# -- config ----------------------------------------------------------------


def test_spec_validation():
    with pytest.raises(ValueError, match="priority"):
        TenantSpec(name="x", priority="urgent")
    with pytest.raises(ValueError, match="weight"):
        TenantSpec(name="x", weight=0)
    with pytest.raises(ValueError, match="kv_block_share"):
        TenantSpec(name="x", kv_block_share=1.5)
    with pytest.raises(ValueError, match="unknown spec field"):
        config_from_dict({"tenants": {"x": {"wieght": 2}}})


def test_config_resolves_unknown_to_default():
    cfg = config_from_dict({"tenants": {"a": {"weight": 3.0}}})
    assert cfg.resolve("a").weight == 3.0
    # unknown and empty identities both land on the default spec —
    # cardinality stays bounded by CONFIG, not by traffic
    assert cfg.resolve("nobody").name == DEFAULT_TENANT
    assert cfg.resolve("").name == DEFAULT_TENANT
    assert cfg.names() == ["a", "default"]


def test_config_file_roundtrip(tmp_path):
    path = tmp_path / "tenants.json"
    path.write_text(json.dumps({
        "tenants": {"live": {"priority": "interactive",
                             "requests_per_s": 5.0}},
        "default": {"priority": "batch"},
    }))
    cfg = load_config(path)
    assert cfg.resolve("live").priority == "interactive"
    assert cfg.default.priority == "batch"


def test_profile_annotation_bridge():
    from types import SimpleNamespace as NS

    annotated = NS(metadata=NS(name="team-a", annotations={
        SERVING_TENANT_ANNOTATION:
            '{"priority": "interactive", "weight": 2.0}'}))
    plain = NS(metadata=NS(name="team-b", annotations={}))
    defaults = NS(metadata=NS(name="team-c", annotations={
        SERVING_TENANT_ANNOTATION: "true"}))
    spec = tenant_from_profile(annotated)
    assert spec.name == "team-a" and spec.priority == "interactive"
    assert tenant_from_profile(plain) is None
    assert tenant_from_profile(defaults) == TenantSpec(name="team-c")
    with pytest.raises(ValueError, match="not valid JSON"):
        tenant_from_profile(NS(metadata=NS(
            name="bad", annotations={SERVING_TENANT_ANNOTATION: "{oops"})))
    cfg = config_from_profiles([annotated, plain, defaults])
    assert cfg.names() == ["default", "team-a", "team-c"]


def test_profile_controller_gates_malformed_tenant_annotation():
    """Control-plane bridge: a Profile carrying a malformed serving-
    tenant annotation fails at RECONCILE time with the parse error on
    its status — not later inside whichever serving process loads
    tenant configs from Profiles — and recovers once fixed."""
    from kubeflow_tpu.api.crds import Profile
    from kubeflow_tpu.controlplane.controllers.profile import (
        ProfileController,
    )
    from kubeflow_tpu.controlplane.runtime import Manager
    from kubeflow_tpu.controlplane.store import Store

    store = Store()
    mgr = Manager(store)
    mgr.register(ProfileController())
    mgr.start()
    try:
        p = Profile()
        p.metadata.name = "team-x"
        p.spec.owner = "x@example.com"
        p.metadata.annotations[SERVING_TENANT_ANNOTATION] = "{not json"
        store.create(p)
        assert mgr.wait_idle()
        got = store.get("Profile", "", "team-x")
        assert got.status.phase == "Failed"
        assert "not valid JSON" in got.status.message
        got.metadata.annotations[SERVING_TENANT_ANNOTATION] = (
            '{"priority": "interactive"}')
        store.update(got)
        assert mgr.wait_idle()
        got = store.get("Profile", "", "team-x")
        assert got.status.phase == "Ready"
        assert store.get("Namespace", "", "team-x")
    finally:
        mgr.stop()


# -- ledger ----------------------------------------------------------------


def test_ledger_rate_throttle_carries_retry_after():
    clk = FakeClock()
    cfg = config_from_dict({"tenants": {
        "slow": {"requests_per_s": 0.5, "request_burst": 1.0}}})
    led = TenantLedger(cfg, clock=clk)
    led.check_request("slow")        # burst of 1: first passes
    with pytest.raises(Throttled) as ei:
        led.check_request("slow")
    assert ei.value.tenant == "slow" and ei.value.reason == "rate"
    assert ei.value.retry_after == pytest.approx(2.0)
    assert led.stats()["slow"]["throttled"]["rate"] == 1
    clk.t = 2.0
    led.check_request("slow")        # refilled
    # unknown identities bill the default tenant (unlimited here)
    led.check_request("stranger")
    assert led.stats()[DEFAULT_TENANT]["admitted"] == 1


def test_ledger_kv_share_and_usage_accounting():
    cfg = config_from_dict({"tenants": {"a": {"kv_block_share": 0.25}}})
    led = TenantLedger(cfg, clock=FakeClock())
    assert led.block_limit("a", 100) == 25
    assert led.block_limit("default", 100) is None  # share 1.0
    led.note_slot_taken("a", 5)
    assert led.blocks_held("a") == 5
    led.note_slot_released("a", 5)
    led.note_completed("a")
    u = led.stats()["a"]
    assert u["blocks_held"] == 0 and u["completed"] == 1


# -- fair-share queue ------------------------------------------------------


class _Fut:
    def done(self):
        return False


def _item(tenant, cost=8.0, priority="standard", weight=1.0):
    meta = ReqMeta(tenant=tenant, priority=priority, weight=weight,
                   cost=cost)
    return (None, None, None, _Fut(), None, None, meta)


def _mkq(tenants: dict, ledger=None):
    cfg = config_from_dict({"tenants": tenants})
    return FairShareQueue(cfg, ledger), cfg


def test_fair_share_alternates_equal_weights():
    q, _ = _mkq({"a": {}, "b": {}})
    for _ in range(10):
        q.append(_item("a"))
        q.append(_item("b"))
    order = [q.popleft()[6].tenant for _ in range(20)]
    assert order == ["a", "b"] * 10
    with pytest.raises(IndexError):
        q.popleft()


def test_fair_share_token_split_matches_weights():
    # acceptance: two equal-weight tenants at saturation split tokens
    # 50/50 (+-10%); a 2:1 weight splits 2:1
    q, _ = _mkq({"a": {}, "b": {}})
    for _ in range(40):
        q.append(_item("a", cost=8.0))
        q.append(_item("b", cost=8.0))
    tokens = {"a": 0, "b": 0}
    for _ in range(40):                  # serve half the backlog
        it = q.popleft()
        tokens[it[6].tenant] += it[6].cost
    total = sum(tokens.values())
    assert abs(tokens["a"] / total - 0.5) <= 0.10

    q2, _ = _mkq({"a": {"weight": 2.0}, "b": {"weight": 1.0}})
    for _ in range(60):
        q2.append(_item("a", weight=2.0))
        q2.append(_item("b", weight=1.0))
    tokens = {"a": 0, "b": 0}
    for _ in range(60):
        it = q2.popleft()
        tokens[it[6].tenant] += it[6].cost
    assert tokens["a"] / sum(tokens.values()) == pytest.approx(
        2 / 3, abs=0.10)


def test_idle_tenant_banks_no_credit():
    q, _ = _mkq({"a": {}, "b": {}})
    for _ in range(10):
        q.append(_item("a"))
    for _ in range(10):
        q.popleft()                      # a's virtual time advances
    # b arrives AFTER a has spent 10 requests of virtual time; start-
    # time fairness catches b up to the clock instead of letting it
    # monopolize the queue until its banked vt is spent
    for _ in range(4):
        q.append(_item("a"))
        q.append(_item("b"))
    order = [q.popleft()[6].tenant for _ in range(8)]
    assert order.count("b") == 4 and order[:2] != ["b", "b"]


def test_priority_classes_and_pacing_fallthrough():
    clk = FakeClock()
    tenants = {"live": {"priority": "interactive", "tokens_per_s": 10.0},
               "std": {},
               "bulk": {"priority": "batch"}}
    cfg = config_from_dict({"tenants": tenants})
    led = TenantLedger(cfg, clock=clk)
    q = FairShareQueue(cfg, led)
    q.append(_item("bulk", priority="batch"))
    q.append(_item("live", priority="interactive"))
    q.append(_item("std"))
    # strict class order: interactive > standard > batch
    assert [q.popleft()[6].tenant for _ in range(3)] \
        == ["live", "std", "bulk"]
    assert q.has_waiting("interactive") is False

    # a token-paced interactive tenant falls through to lower classes
    led.charge_tokens("live", 15)        # bucket 10/s -> 0.5s of debt
    q.append(_item("live", priority="interactive"))
    q.append(_item("bulk", priority="batch"))
    assert q.popleft()[6].tenant == "bulk"
    # nothing runnable at all -> None (not IndexError), with a delay
    assert q.popleft() is None
    assert len(q) == 1
    assert q.pacing_delay() == pytest.approx(0.5)
    clk.t = 0.5
    assert q.popleft()[6].tenant == "live"


def test_appendleft_refunds_virtual_time():
    q, _ = _mkq({"a": {}, "b": {}})
    q.append(_item("a"))
    q.append(_item("b"))
    it = q.popleft()                     # a charged 8 vt
    assert it[6].tenant == "a" and it[6].charged > 0
    q.appendleft(it)                     # deferral: refund the charge
    assert it[6].charged == 0.0
    # with the refund, a is still the lowest-vt tenant and pops first
    assert q.popleft()[6].tenant == "a"


# -- label-cardinality guard ----------------------------------------------


def test_label_guard_caps_cardinality():
    g = LabelGuard(max_values=2, seed=("known",))
    assert g.admit("known") == "known"
    assert g.admit("fresh") == "fresh"   # second of 2 allowed
    assert g.admit("attack-1") == OVERFLOW_LABEL
    assert g.admit("attack-2") == OVERFLOW_LABEL
    assert g.admit("known") == "known"   # seeded values keep passing
    assert g.admit("") == OVERFLOW_LABEL
    assert g.overflowed == 2
    with pytest.raises(ValueError):
        LabelGuard(max_values=0)


# -- radix namespace isolation --------------------------------------------


def test_radix_namespaces_never_cross_match():
    pool = BlockPool(num_blocks=16, block_size=4)
    radix = RadixPrefixCache(pool)
    toks = list(range(8))
    blocks = dict(enumerate(pool.alloc(2)))
    adopted, _ = radix.insert(toks, blocks, ns="tenant-a")
    assert adopted == {0, 1}
    # same tokens, different namespace: no full match, no partial
    # match (not even the timing side channel of a CoW seed)
    nodes, partial, plen = radix.match(toks, ns="tenant-b")
    assert nodes == [] and partial is None and plen == 0
    nodes, _, _ = radix.match(toks, ns="tenant-a")
    assert len(nodes) == 2
    # default-namespace matching is untouched
    assert radix.match(toks)[0] == []
    # eviction sweeps across namespaces and frees back to the pool
    free0 = pool.num_free
    assert radix.evict(2) == 2
    assert pool.num_free == free0 + 2


# -- batcher integration (real engine, greedy oracle) ---------------------


def _engine(max_len=64):
    import jax

    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        LLAMA_FAMILY,
    )

    cfg = llama.LLAMA_TINY
    params = dict(llama.init(jax.random.key(0), cfg))
    params["lm_head"] = params["lm_head"] * 50.0  # argmax can't flip
    return InferenceEngine(params, cfg, LLAMA_FAMILY,
                           EngineConfig(max_len=max_len))


def _solo(engine, prompt, max_new):
    import jax.numpy as jnp
    import numpy as np

    return np.asarray(engine.generate(
        jnp.asarray([prompt], jnp.int32), max_new=max_new))[0].tolist()


QOS = {"tenants": {"live": {"priority": "interactive"},
                   "bulk": {"priority": "batch"}}}


async def test_preemption_replay_is_token_identical():
    """Both batch-class decodes fill the slots; an interactive arrival
    preempts one mid-generation. The preempted request replays through
    the radix cache and must return EXACTLY its uninterrupted tokens."""
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    engine = _engine()
    p1, p2, p3 = [3, 5, 7, 11], [4, 6, 8, 10], [9, 2, 4, 8]
    want1, want2 = _solo(engine, p1, 24), _solo(engine, p2, 24)
    want3 = _solo(engine, p3, 8)
    b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                          tenancy=config_from_dict(QOS))
    try:
        f1 = asyncio.ensure_future(
            b.submit(p1, 24, (("tenant", "bulk"),)))
        f2 = asyncio.ensure_future(
            b.submit(p2, 24, (("tenant", "bulk"),)))
        for _ in range(400):             # wait until both slots busy
            if len(b._active) == 2:
                break
            await asyncio.sleep(0.02)
        assert len(b._active) == 2
        got3 = await b.submit(p3, 8, (("tenant", "live"),))
        got1, got2 = await f1, await f2
        assert b.preemptions >= 1
        assert got1 == want1
        assert got2 == want2
        assert got3 == want3
        stats = b.tenant_stats()
        assert stats["bulk"]["preempted"] == b.preemptions
        assert stats["live"]["completed"] == 1
        assert stats["bulk"]["tokens"] == 48
    finally:
        await b.close()


async def test_drain_completes_preempted_request():
    """Drain-vs-preemption seam: a batch-class request preempted back
    into the pending queue while the batcher is DRAINING must still be
    re-admitted and finish token-identically — drain refuses NEW
    arrivals, never work that was already accepted. (The preemption
    path re-enqueues via the scheduler directly, bypassing the
    draining door; this pins that bypass.)"""
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    engine = _engine()
    p1, p2, p3 = [3, 5, 7, 11], [4, 6, 8, 10], [9, 2, 4, 8]
    want1, want2 = _solo(engine, p1, 24), _solo(engine, p2, 24)
    want3 = _solo(engine, p3, 8)
    b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                          tenancy=config_from_dict(QOS))
    try:
        f1 = asyncio.ensure_future(
            b.submit(p1, 24, (("tenant", "bulk"),)))
        f2 = asyncio.ensure_future(
            b.submit(p2, 24, (("tenant", "bulk"),)))
        for _ in range(400):
            if len(b._active) == 2:
                break
            await asyncio.sleep(0.02)
        assert len(b._active) == 2
        f3 = asyncio.ensure_future(
            b.submit(p3, 8, (("tenant", "live"),)))
        for _ in range(400):            # wait for the preemption event
            if b.preemptions >= 1:
                break
            await asyncio.sleep(0.02)
        assert b.preemptions >= 1
        # drain NOW, with the preempted bulk request parked in pending
        assert await b.drain(timeout=60.0)
        with pytest.raises(RuntimeError, match="draining"):
            await b.submit(p3, 4, (("tenant", "live"),))
        assert await f3 == want3
        assert await f1 == want1       # the preempted one, replayed
        assert await f2 == want2
    finally:
        await b.close()


async def test_tenant_blind_batcher_is_plain_fifo():
    """No tenancy config: the pending queue stays a deque (FIFO), no
    ledger exists, and tenant_stats is empty — the tenant-blind
    deployment is behaviorally the seed batcher."""
    import collections

    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    engine = _engine()
    b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2)
    try:
        assert isinstance(b._pending, collections.deque)
        assert b.tenant_stats() == {}
        p = [3, 5, 7, 11]
        # an X-Tenant header still reaches submit as sampling metadata;
        # tenant-blind it must be inert (popped, not a group key)
        got = await b.submit(p, 8, (("tenant", "whoever"),))
        assert got == _solo(engine, p, 8)
        assert b.tenant_stats() == {}
    finally:
        await b.close()


async def test_prefix_isolation_blocks_cross_tenant_hits():
    """Two prefix-isolated tenants sending the SAME prompt: the second
    request of tenant a hits a's radix namespace; tenant b's first
    request must MISS (no cross-tenant reuse, no timing side channel),
    then hit its own namespace on repeat."""
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    engine = _engine()
    ten = config_from_dict({"tenants": {
        "a": {"prefix_isolation": True},
        "b": {"prefix_isolation": True}}})
    b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                          kv_block_size=8, tenancy=ten)
    try:
        prompt = [5, 3, 9, 2, 7, 1, 8, 6, 4, 3, 2, 9, 5, 7, 1, 2]
        want = _solo(engine, prompt, 4)
        for tenant, expect_hit in (("a", False), ("a", True),
                                   ("b", False), ("b", True)):
            h0 = b.prefix_cache_stats()["hits"]
            got = await b.submit(prompt, 4, (("tenant", tenant),))
            assert got == want
            hit = b.prefix_cache_stats()["hits"] - h0 > 0
            assert hit == expect_hit, (tenant, expect_hit)
    finally:
        await b.close()


# -- serving app plumbing --------------------------------------------------


@pytest.fixture()
def tiny_engine():
    return _engine()


async def test_server_header_routes_tenant_and_metrics(
        tiny_engine, aiohttp_client):
    from kubeflow_tpu.serving import server as server_lib

    ten = config_from_dict({"tenants": {
        "live": {"priority": "interactive"},
        "limited": {"requests_per_s": 0.001, "request_burst": 1.0}}})
    app = server_lib.create_serving_app(
        {"tiny": tiny_engine}, continuous=True, max_batch=2, tenancy=ten)
    client = await aiohttp_client(app)
    body = {"tokens": [[3, 5, 7, 11]], "max_new": 4}

    r = await client.post("/v1/models/tiny:generate", json=body,
                          headers={"X-Tenant": "live"})
    assert r.status == 200
    r = await client.post("/v1/models/tiny:generate", json=body)
    assert r.status == 200               # headerless -> default tenant

    # rate limit: burst of 1 admits once, then 429 with a REAL
    # Retry-After (the bucket's refill time, not the old constant "1")
    r = await client.post("/v1/models/tiny:generate", json=body,
                          headers={"X-Tenant": "limited"})
    assert r.status == 200
    r = await client.post("/v1/models/tiny:generate", json=body,
                          headers={"X-Tenant": "limited"})
    assert r.status == 429
    assert int(r.headers["Retry-After"]) >= 1
    assert "throttled" in (await r.json())["error"]

    m = await client.get("/v1/models")
    tstats = (await m.json())["models"][0]["tenants"]
    assert tstats["live"]["completed"] == 1
    assert tstats["default"]["completed"] == 1
    assert tstats["limited"]["throttled"]["rate"] == 1

    text = await (await client.get("/metrics")).text()
    assert 'serving_tenant_tokens_total{model="tiny",tenant="live"} 4' \
        in text
    assert ('serving_tenant_throttled_total{model="tiny",'
            'reason="rate",tenant="limited"} 1') in text
    # zero-seeded: every configured tenant has series before traffic
    assert 'serving_tenant_preemptions_total{model="tiny",' \
           'tenant="default"} 0' in text


async def test_tenant_blind_server_exports_no_tenant_series(
        tiny_engine, aiohttp_client):
    from kubeflow_tpu.serving import server as server_lib

    app = server_lib.create_serving_app(
        {"tiny": tiny_engine}, continuous=True, max_batch=2)
    client = await aiohttp_client(app)
    r = await client.post("/v1/models/tiny:generate",
                          json={"tokens": [[3, 5, 7, 11]], "max_new": 4},
                          headers={"X-Tenant": "whoever"})
    assert r.status == 200
    text = await (await client.get("/metrics")).text()
    # metric FAMILIES exist (HELP/TYPE) but carry zero samples — the
    # tenant-blind exposition is unchanged modulo those header lines
    for line in text.splitlines():
        if line.startswith("serving_tenant_"):
            pytest.fail(f"unexpected tenant sample: {line}")
    assert (await (await client.get("/v1/models")).json()
            )["models"][0].get("tenants") is None


def test_tenancy_requires_continuous(tiny_engine):
    from kubeflow_tpu.serving import server as server_lib

    with pytest.raises(ValueError, match="require continuous"):
        server_lib.create_serving_app(
            {"tiny": tiny_engine},
            tenancy=config_from_dict({"tenants": {}}))


# -- fleet router ----------------------------------------------------------


async def test_router_tenant_gate_and_forwarding(aiohttp_client):
    from kubeflow_tpu.fleet import router as router_mod

    seen: list[str | None] = []

    async def fake_gen(request):
        seen.append(request.headers.get("X-Tenant"))
        return web.json_response({"tokens": [[1, 2]]})

    rep_app = web.Application()
    rep_app.router.add_post("/v1/models/{name}:generate", fake_gen)
    rep_client = await aiohttp_client(rep_app)
    rep_url = (f"http://{rep_client.server.host}:"
               f"{rep_client.server.port}")

    ten = config_from_dict({"tenants": {
        "live": {"requests_per_s": 0.001, "request_burst": 2.0}}})
    client = await aiohttp_client(router_mod.create_router_app(
        hedge_after_s=0, tenancy=ten))
    r = await client.post("/fleet/register",
                          json={"url": rep_url, "models": ["m"]})
    assert r.status == 200

    body = {"tokens": [[1, 2, 3]], "max_new": 2}
    statuses = []
    for _ in range(4):
        r = await client.post("/v1/models/m:generate", json=body,
                              headers={"X-Tenant": "live"})
        statuses.append(r.status)
    assert statuses == [200, 200, 429, 429]
    assert int(r.headers["Retry-After"]) >= 1
    # the replica saw the tenant identity on every ADMITTED request
    assert seen == ["live", "live"]

    text = await (await client.get("/metrics")).text()
    assert 'fleet_tenant_requests_total{tenant="live"} 2' in text
    assert 'fleet_tenant_throttled_total{tenant="live"} 2' in text
    assert 'fleet_tenant_requests_total{tenant="default"} 0' in text


async def test_router_without_tenancy_guards_raw_labels(aiohttp_client):
    from kubeflow_tpu.fleet import router as router_mod

    async def fake_gen(request):
        return web.json_response({"tokens": [[1]]})

    rep_app = web.Application()
    rep_app.router.add_post("/v1/models/{name}:generate", fake_gen)
    rep_client = await aiohttp_client(rep_app)
    rep_url = (f"http://{rep_client.server.host}:"
               f"{rep_client.server.port}")

    app = router_mod.create_router_app(hedge_after_s=0)
    app[router_mod.FLEET_KEY].obs.tenant_guard = LabelGuard(max_values=2)
    client = await aiohttp_client(app)
    await client.post("/fleet/register",
                      json={"url": rep_url, "models": ["m"]})
    body = {"tokens": [[1, 2]], "max_new": 1}
    for t in ("a", "b", "scan-1", "scan-2", "scan-3"):
        r = await client.post("/v1/models/m:generate", json=body,
                              headers={"X-Tenant": t})
        assert r.status == 200
    text = await (await client.get("/metrics")).text()
    assert 'fleet_tenant_requests_total{tenant="a"} 1' in text
    # past the cap, scanner-minted values collapse into one bucket
    assert (f'fleet_tenant_requests_total{{tenant="{OVERFLOW_LABEL}"}} 3'
            in text)
    assert "scan-1" not in text
