"""Continuous batching: slot engine + host orchestrator + REST surface.

Oracle throughout: `engine.generate` batch-1 greedy (itself pinned to
full-recompute in test_serving.py). The head is sharpened (*50) so
argmax cannot flip between batch-1 and batch-S reduction orders —
the same hazard the window-Batcher tests guard against.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

pytest_plugins = ("aiohttp.pytest_plugin",)

from kubeflow_tpu.models import llama
from kubeflow_tpu.serving import EngineConfig, InferenceEngine, LLAMA_FAMILY
from kubeflow_tpu.serving import server as server_lib
from kubeflow_tpu.serving.continuous import (
    ContinuousBatcher, ContinuousEngine, bucket_pow2,
)


def _engine(eos=None, max_len=64):
    cfg = llama.LLAMA_TINY
    params = dict(llama.init(jax.random.key(0), cfg))
    params["lm_head"] = params["lm_head"] * 50.0
    return InferenceEngine(
        params, cfg, LLAMA_FAMILY,
        EngineConfig(max_len=max_len, eos_token=eos)), cfg


def _solo(engine, prompt, max_new):
    return np.asarray(engine.generate(
        jnp.asarray([prompt], jnp.int32), max_new=max_new))[0].tolist()


def _greedy(engine, slots):
    return engine._resolve_sampling(
        np.zeros(slots, np.float32), np.zeros(slots, np.int64),
        np.ones(slots, np.float32), jax.random.key(0), batch=slots)[0]


def _fill_slot(ce, st, slot, prompt, sp, rng):
    """Admit `prompt` into `slot` the way the batcher does: adopt the
    slot's own run of pool blocks, frozen, then feed the whole prompt
    as one slice. -> (state, first token, rng)."""
    mb = ce.blocks_per_slot
    table = 1 + slot * mb + np.arange(mb, dtype=np.int32)
    st = ce.adopt_slot(st, slot, table, 0, prompt[0])
    st, first, _, rng = ce.append_rows(
        st, [slot], [prompt], [len(prompt)], [True], sp, rng)
    return st, int(np.asarray(first)[0]), rng


def test_bucket_pow2():
    assert bucket_pow2(3, 64) == 16
    assert bucket_pow2(16, 64) == 16
    assert bucket_pow2(17, 64) == 32
    assert bucket_pow2(100, 64) == 64


@pytest.mark.slow
def test_slot_step_matches_generate_mixed_cursors():
    """Device-level check, no asyncio: three prompts of different
    lengths admitted into different slots decode EXACTLY their solo
    greedy continuations, in one shared step batch whose per-slot
    cursors differ (the thing DecodeState's scalar cursor cannot do)."""
    engine, cfg = _engine()
    ce = ContinuousEngine(engine, max_slots=4)
    rng = jax.random.key(7)
    gen = np.random.default_rng(3)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (4, 9, 17)]
    max_new = 6
    want = [_solo(engine, p, max_new) for p in prompts]

    st = ce.init_slots()
    sp = _greedy(engine, 4)
    got = [[] for _ in prompts]
    for i, p in enumerate(prompts):
        st, first, rng = _fill_slot(ce, st, i, p, sp, rng)
        got[i].append(first)
    for _ in range(max_new - 1):
        st, toks, _, rng = ce.step(st, sp, rng)
        toks = np.asarray(toks)       # [slots, 1]
        for i in range(len(prompts)):
            got[i].append(int(toks[i, 0]))
    assert got == want


@pytest.mark.slow
def test_chunked_steps_emit_identical_tokens():
    """steps=3 is one scanned dispatch of the SAME per-step program:
    the emitted tokens must equal three steps=1 calls."""
    engine, cfg = _engine()
    ce = ContinuousEngine(engine, max_slots=2)
    rng = jax.random.key(11)
    p = np.random.default_rng(14).integers(
        0, cfg.vocab_size, 7).tolist()
    want = _solo(engine, p, 7)
    sp = _greedy(engine, 2)
    st, first, rng = _fill_slot(ce, ce.init_slots(), 0, p, sp, rng)
    st, toks, _, rng = ce.step(st, sp, rng, steps=3)
    got = [first] + np.asarray(toks)[0].tolist()
    st, toks, _, rng = ce.step(st, sp, rng, steps=3)
    got += np.asarray(toks)[0].tolist()
    assert got == want


@pytest.mark.slow
async def test_batcher_concurrent_requests_match_solo():
    engine, cfg = _engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=4)
    gen = np.random.default_rng(4)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (4, 7, 12, 20)]
    want = [_solo(engine, p, 13) for p in prompts]
    got = await asyncio.gather(
        *(batcher.submit(p, 13, ()) for p in prompts))
    assert list(got) == want
    assert batcher.requests == 4
    # shared steps: 4 requests x 12 decoded tokens each (token #1
    # comes from prefill) start a slice apart, one dispatch of 4 steps
    # a slice: 12 + 3 x 4 steps, not 4 x 12
    assert batcher.calls <= 28, batcher.calls
    assert batcher.occupancy() > 1.0
    await batcher.close()


@pytest.mark.slow
async def test_late_arrival_joins_midflight():
    """A request submitted while another decodes joins at the next
    token boundary instead of waiting for the first to finish — total
    steps stay well under the serial sum."""
    engine, cfg = _engine()
    # chunk=1: per-token calls make the mid-decode poll precise; the
    # default chunking once let a loaded box run the whole of A between
    # poller wakeups, collapsing the test to the serial case
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=4,
                                chunk=1)
    gen = np.random.default_rng(5)
    a = gen.integers(0, cfg.vocab_size, 5).tolist()
    b = gen.integers(0, cfg.vocab_size, 8).tolist()
    want_a, want_b = _solo(engine, a, 20), _solo(engine, b, 4)

    task_a = asyncio.ensure_future(batcher.submit(a, 20, ()))
    while batcher.calls < 3:  # a is mid-decode
        await asyncio.sleep(0.005)
    if task_a.done():  # pathological event-loop starvation on a loaded
        pytest.skip("scheduler starved the poller; nothing to observe")
    got_b = await batcher.submit(b, 4, ())
    got_a = await task_a
    assert got_a == want_a and got_b == want_b
    # serial would need (20-1) + (4-1) = 22 steps; joined runs share
    assert batcher.calls < 22, batcher.calls
    await batcher.close()


async def test_eos_retires_slot_early_and_pads_result():
    engine0, cfg = _engine()
    gen = np.random.default_rng(6)
    p = gen.integers(0, cfg.vocab_size, 6).tolist()
    ref = _solo(engine0, p, 6)
    eos = ref[2]  # greedy hits this at step 3
    engine, _ = _engine(eos=eos)
    # chunk=1, depth=1: this test pins PER-TOKEN retirement; chunked
    # retirement is covered by the identity test above, and bounded
    # speculative overshoot (depth>1) by the pipelining tests below
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                chunk=1, pipeline_depth=1)
    got = await batcher.submit(p, 6, ())
    # window-Batcher parity: EOS-padded to exactly max_new
    assert got == ref[:3] + [eos] * 3
    # the slot retired after 2 decode steps, not 5
    assert batcher.calls <= 3, batcher.calls
    # slot is reusable afterwards
    q = gen.integers(0, cfg.vocab_size, 4).tolist()
    got_q = await batcher.submit(q, 4, ())
    want_q = _solo(engine, q, 4)
    assert got_q == want_q
    await batcher.close()


@pytest.mark.slow
async def test_slot_reuse_leaks_nothing():
    """More requests than slots, varied lengths: every result must
    equal its solo run even though slots are reused with stale KV,
    stale pads and saturated cursors left behind."""
    engine, cfg = _engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2)
    gen = np.random.default_rng(7)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (20, 3, 9, 17, 5, 11)]
    want = [_solo(engine, p, 4) for p in prompts]
    got = await asyncio.gather(
        *(batcher.submit(p, 4, ()) for p in prompts))
    assert list(got) == want
    await batcher.close()


@pytest.mark.slow
async def test_greedy_rows_exact_next_to_sampled_rows():
    """Per-slot sampling knobs: a temperature row in the batch must not
    perturb its greedy neighbors (the _sample cond selects per row)."""
    engine, cfg = _engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=4)
    gen = np.random.default_rng(8)
    g1 = gen.integers(0, cfg.vocab_size, 5).tolist()
    g2 = gen.integers(0, cfg.vocab_size, 9).tolist()
    s1 = gen.integers(0, cfg.vocab_size, 7).tolist()
    want1, want2 = _solo(engine, g1, 6), _solo(engine, g2, 6)
    r1, r2, rs = await asyncio.gather(
        batcher.submit(g1, 6, ()),
        batcher.submit(g2, 6, ()),
        batcher.submit(s1, 6, (("temperature", 0.9), ("top_k", 5))))
    assert r1 == want1 and r2 == want2
    assert len(rs) == 6
    assert all(0 <= t < cfg.vocab_size for t in rs)
    await batcher.close()


@pytest.mark.slow
async def test_rest_oneshot_and_models_card():
    engine, cfg = _engine()
    app = server_lib.create_serving_app(
        {"m": engine}, continuous=True, max_batch=4)
    client = TestClient(TestServer(app))
    await client.start_server()
    gen = np.random.default_rng(9)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (4, 7, 11)]
    want = [_solo(engine, p, 5) for p in prompts]

    async def one(p):
        r = await client.post("/v1/models/m:generate",
                              json={"tokens": [p], "max_new": 5})
        assert r.status == 200, await r.text()
        return (await r.json())["tokens"][0]

    got = await asyncio.gather(*(one(p) for p in prompts))
    for g, w in zip(got, want):
        assert g == w
    r = await client.get("/v1/models")
    card = (await r.json())["models"][0]
    assert card["batcher_mode"] == "continuous"
    assert card["batched_requests"] == 3
    assert card["occupancy"] > 0
    assert card["pipeline_depth"] == 1  # backend-aware default on CPU
    await client.close()


@pytest.mark.slow
async def test_rest_sse_stream_rides_the_slot_batch():
    engine, cfg = _engine()
    app = server_lib.create_serving_app(
        {"m": engine}, continuous=True, max_batch=4)
    client = TestClient(TestServer(app))
    await client.start_server()
    gen = np.random.default_rng(10)
    p = gen.integers(0, cfg.vocab_size, 6).tolist()
    want = _solo(engine, p, 7)

    resp = await client.post(
        "/v1/models/m:generate",
        json={"tokens": [p], "max_new": 7, "stream": True})
    assert resp.status == 200
    assert resp.headers["Content-Type"].startswith("text/event-stream")
    import json as _json
    toks, final = [], None
    async for line in resp.content:
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        obj = _json.loads(line[6:])
        if obj.get("done"):
            final = obj
        else:
            toks.extend(obj["tokens"][0])
    assert toks == want
    assert final is not None and final["total"] == 7
    await client.close()


@pytest.mark.slow
async def test_prefill_bucket_never_overruns_cache():
    """A legal request whose power-of-two prompt bucket + max_new
    would overrun the cache must fall back to the exact prompt length
    and still decode correctly (silent clamped-write corruption
    otherwise — the admission check never sees the bucket)."""
    engine, cfg = _engine()  # max_len = 64
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2)
    p = np.random.default_rng(11).integers(
        0, cfg.vocab_size, 5).tolist()
    # bucket(5) = 16; 16 + 55 = 71 > 64, but 5 + 55 = 60 fits
    want = _solo(engine, p, 55)
    got = await batcher.submit(p, 55, ())
    assert got == want
    await batcher.close()


@pytest.mark.slow
async def test_abandoned_stream_releases_slot():
    """A consumer that stops iterating (SSE client disconnect) must
    free its slot instead of decoding to max_new into a dead queue."""
    engine, cfg = _engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2)
    p = np.random.default_rng(12).integers(
        0, cfg.vocab_size, 6).tolist()
    agen = batcher.stream(p, 40, ())
    got = []
    async for tok in agen:
        got.append(tok)
        if len(got) == 3:
            break
    await agen.aclose()
    for _ in range(200):
        if not batcher._active:
            break
        await asyncio.sleep(0.005)
    assert not batcher._active
    # the slot retired long before the 39 decode steps max_new implies
    assert batcher.calls < 30, batcher.calls
    # and the pool still serves new work
    q = np.random.default_rng(13).integers(
        0, cfg.vocab_size, 4).tolist()
    assert await batcher.submit(q, 4, ()) == _solo(engine, q, 4)
    await batcher.close()


async def test_submit_capacity_and_shutdown():
    engine, cfg = _engine(max_len=32)
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2)
    with pytest.raises(ValueError, match="exceeds"):
        batcher._enqueue(list(range(30)), 8, (), queue=None)
    await batcher.close()
    with pytest.raises(RuntimeError, match="shut down"):
        await batcher.submit([1, 2, 3], 4, ())


@pytest.mark.slow
def test_chunked_prefill_equals_oneshot_ragged_batch():
    """generate(prefill_chunk=4) must equal plain generate on a ragged
    left-padded batch — including a row whose pads span entire early
    chunks (fully-masked slices attend nothing and sample nothing)."""
    engine, cfg = _engine()
    gen = np.random.default_rng(15)
    longest = 10
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (2, 6, longest)]  # row 0: pads cover chunk 0+
    arr = np.zeros((3, longest), np.int32)
    mask = np.zeros((3, longest), bool)
    for i, p in enumerate(prompts):
        arr[i, longest - len(p):] = p
        mask[i, longest - len(p):] = True
    want = np.asarray(engine.generate(
        jnp.asarray(arr), max_new=5, prompt_mask=jnp.asarray(mask)))
    got = np.asarray(engine.generate(
        jnp.asarray(arr), max_new=5, prompt_mask=jnp.asarray(mask),
        prefill_chunk=4))
    np.testing.assert_array_equal(got, want)


def test_chunked_prefill_width_validation():
    engine, cfg = _engine(max_len=32)
    p = jnp.asarray(np.random.default_rng(16).integers(
        0, cfg.vocab_size, (1, 8)), jnp.int32)
    with pytest.raises(ValueError, match="exceeds cache bucket"):
        engine.generate(p, max_new=24, prefill_chunk=7)  # pads to 14
    with pytest.raises(ValueError, match="multiple of"):
        engine.prefill_chunked(
            engine.params, p, engine.init_state(1), jax.random.key(0),
            engine._resolve_sampling(0.0, 0, 1.0, None, batch=1)[0],
            jnp.ones((1, 8), bool), chunk=3)


@pytest.mark.slow
async def test_continuous_long_prompt_admits_in_chunks():
    """A prompt of several slices beside one of a single slice: each
    decodes exactly its solo continuation."""
    engine, cfg = _engine(max_len=128)
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                prefill_chunk_tokens=8)
    gen = np.random.default_rng(17)
    long_p = gen.integers(0, cfg.vocab_size, 20).tolist()
    short_p = gen.integers(0, cfg.vocab_size, 5).tolist()
    want_l = _solo(engine, long_p, 6)
    want_s = _solo(engine, short_p, 6)
    got_l, got_s = await asyncio.gather(
        batcher.submit(long_p, 6, ()),
        batcher.submit(short_p, 6, ()))
    assert got_l == want_l and got_s == want_s
    await batcher.close()


@pytest.mark.slow
async def test_shared_prefix_decodes_like_full_prompt():
    """A request with a registered prefix must decode exactly what the
    full concatenated prompt decodes. Mixed admissions (prefixed and plain)
    share the slot batch."""
    engine, cfg = _engine(max_len=96)
    gen = np.random.default_rng(20)
    sys_prompt = gen.integers(0, cfg.vocab_size, 23).tolist()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=4,
                                prefixes={"sys": sys_prompt})
    p1 = gen.integers(0, cfg.vocab_size, 6).tolist()
    p2 = gen.integers(0, cfg.vocab_size, 11).tolist()
    plain = gen.integers(0, cfg.vocab_size, 5).tolist()
    want1 = _solo(engine, sys_prompt + p1, 5)
    want2 = _solo(engine, sys_prompt + p2, 5)
    want_plain = _solo(engine, plain, 5)
    got1, got2, got_plain = await asyncio.gather(
        batcher.submit(p1, 5, (("prefix", "sys"),)),
        batcher.submit(p2, 5, (("prefix", "sys"),)),
        batcher.submit(plain, 5, ()))
    assert got1 == want1
    assert got2 == want2
    assert got_plain == want_plain
    # slot reuse after a prefixed request leaks nothing
    got3 = await batcher.submit(plain, 5, (("prefix", "sys"),))
    assert got3 == _solo(engine, sys_prompt + plain, 5)
    with pytest.raises(ValueError, match="unknown prefix"):
        await batcher.submit(p1, 5, (("prefix", "nope"),))
    with pytest.raises(ValueError, match="exceeds"):
        await batcher.submit(p1, 96 - 23 - len(p1) + 1,
                             (("prefix", "sys"),))
    await batcher.close()


@pytest.mark.slow
async def test_rest_prefix_requests():
    engine, cfg = _engine(max_len=96)
    gen = np.random.default_rng(21)
    sys_prompt = gen.integers(0, cfg.vocab_size, 17).tolist()
    app = server_lib.create_serving_app(
        {"m": engine}, continuous=True, max_batch=4,
        prefixes={"sys": sys_prompt})
    client = TestClient(TestServer(app))
    await client.start_server()
    p = gen.integers(0, cfg.vocab_size, 5).tolist()
    want = _solo(engine, sys_prompt + p, 4)

    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [p], "max_new": 4,
                                "prefix": "sys"})
    assert r.status == 200, await r.text()
    assert (await r.json())["tokens"][0] == want

    r = await client.get("/v1/models")
    card = (await r.json())["models"][0]
    assert card["prefixes"] == {"sys": 17}

    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [p], "max_new": 4,
                                "prefix": "nope"})
    assert r.status == 400
    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [p], "max_new": 4,
                                "prefix": "sys", "speculative": True})
    assert r.status == 400
    await client.close()


@pytest.mark.slow
def test_continuous_engine_under_tensor_parallel_mesh():
    """Multi-chip continuous serving: the slot engine's prefill/insert/
    step compile and run with TENSOR-PARALLEL sharded params on the
    8-device mesh and emit exactly the unsharded tokens — XLA inserts
    the collectives, the engine code is mesh-oblivious (the SPMD
    contract the whole compute layer is built on)."""
    from kubeflow_tpu.parallel import (
        LLAMA_RULES, MeshSpec, create_mesh, shard_pytree_specs)

    cfg = llama.LLAMA_TINY
    params = dict(llama.init(jax.random.key(0), cfg))
    params["lm_head"] = params["lm_head"] * 50.0
    ref = InferenceEngine(params, cfg, LLAMA_FAMILY,
                          EngineConfig(max_len=64))
    gen = np.random.default_rng(22)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (5, 9)]
    max_new = 5
    want = [_solo(ref, p, max_new) for p in prompts]

    mesh = create_mesh(MeshSpec(data=1, fsdp=2, tensor=4))
    shardings = shard_pytree_specs(
        LLAMA_RULES, llama.param_logical_axes(cfg), mesh)
    sharded = jax.device_put(params, shardings)
    # the attention projections are genuinely tensor-sharded
    assert "tensor" in str(sharded["blocks"]["wq"].sharding.spec)
    engine = InferenceEngine(sharded, cfg, LLAMA_FAMILY,
                             EngineConfig(max_len=64))
    ce = ContinuousEngine(engine, max_slots=2)
    with jax.set_mesh(mesh):
        st = ce.init_slots()
        sp = _greedy(engine, 2)
        rng = jax.random.key(3)
        got = [[] for _ in prompts]
        for i, p in enumerate(prompts):
            st, first, rng = _fill_slot(ce, st, i, p, sp, rng)
            got[i].append(first)
        st, toks, _, rng = ce.step(st, sp, rng, steps=max_new - 1)
        toks = np.asarray(toks)
    for i in range(len(prompts)):
        got[i].extend(toks[i].tolist())
    assert got == want


@pytest.mark.slow
async def test_stop_sequences_retire_slots_early():
    """A completed stop sequence trims the output (OpenAI semantics)
    and frees the slot immediately — the compute win over running to
    max_new. Unmatched stops change nothing."""
    engine, cfg = _engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                chunk=1)
    p = np.random.default_rng(30).integers(0, cfg.vocab_size, 6).tolist()
    ref = _solo(engine, p, 10)
    stop = (tuple(ref[2:4]),)  # completes at emitted token #4
    got = await batcher.submit(p, 10, (("stop", stop),))
    assert got == ref[:2]
    assert batcher.calls <= 4, batcher.calls  # retired, not run to 10
    # unmatched stop: full (EOS-unpadded result equals the solo run)
    got2 = await batcher.submit(p, 10, (("stop", ((99999,),)),))
    assert got2 == ref
    await batcher.close()


@pytest.mark.slow
async def test_rest_stop_sequences_all_paths():
    engine, cfg = _engine()
    gen = np.random.default_rng(31)
    p = gen.integers(0, cfg.vocab_size, 5).tolist()
    want = _solo(engine, p, 8)
    stop = [want[3:5]]

    for app_kwargs in ({"continuous": True, "max_batch": 4},
                       {"batch_window_ms": 5.0},
                       {}):
        app = server_lib.create_serving_app({"m": engine}, **app_kwargs)
        client = TestClient(TestServer(app))
        await client.start_server()
        r = await client.post(
            "/v1/models/m:generate",
            json={"tokens": [p], "max_new": 8, "stop": stop})
        assert r.status == 200, await r.text()
        assert (await r.json())["tokens"][0] == want[:3], app_kwargs
        r = await client.post(
            "/v1/models/m:generate",
            json={"tokens": [p], "max_new": 8, "stop": stop,
                  "stream": True})
        assert r.status == 400
        r = await client.post(
            "/v1/models/m:generate",
            json={"tokens": [p], "max_new": 8, "stop": [[]]})
        assert r.status == 400
        await client.close()


@pytest.mark.slow
async def test_logprobs_over_rest_all_paths():
    """'logprobs': true returns the chosen tokens' raw-model
    log-softmax, 1:1 with tokens, identical between the continuous
    batcher and the direct path, and each entry is a valid logprob of
    the returned token."""
    import math

    engine, cfg = _engine()
    gen = np.random.default_rng(40)
    p = gen.integers(0, cfg.vocab_size, 6).tolist()

    got = {}
    for mode, kwargs in (("continuous",
                          {"continuous": True, "max_batch": 4}),
                         ("direct", {})):
        app = server_lib.create_serving_app({"m": engine}, **kwargs)
        client = TestClient(TestServer(app))
        await client.start_server()
        r = await client.post(
            "/v1/models/m:generate",
            json={"tokens": [p], "max_new": 5, "logprobs": True})
        assert r.status == 200, await r.text()
        body = await r.json()
        assert len(body["logprobs"][0]) == len(body["tokens"][0]) == 5
        assert all(lp <= 0.0 and math.isfinite(lp)
                   for lp in body["logprobs"][0])
        got[mode] = body
        r = await client.post(
            "/v1/models/m:generate",
            json={"tokens": [p], "max_new": 5, "logprobs": True,
                  "stream": True})
        assert r.status == 400
        await client.close()
    assert got["continuous"]["tokens"] == got["direct"]["tokens"]
    for a, b in zip(got["continuous"]["logprobs"][0],
                    got["direct"]["logprobs"][0]):
        assert a == pytest.approx(b, abs=1e-4)
    # oracle: greedy chosen-token logprob == max log-softmax of the
    # model's own forward at that position
    toks, lps = engine.generate(
        jnp.asarray([p], jnp.int32), max_new=5, return_logprobs=True)
    full = jnp.concatenate([jnp.asarray([p], jnp.int32), toks], axis=1)
    logits = llama.apply(engine.params, llama.LLAMA_TINY, full)
    for i in range(5):
        pos_logits = logits[0, len(p) - 1 + i] * 1.0
        want = float(jax.nn.log_softmax(pos_logits.astype(jnp.float32))[
            int(toks[0, i])])
        assert float(lps[0, i]) == pytest.approx(want, abs=1e-3)


async def test_backpressure_sheds_load():
    """Past max_pending queued requests, _enqueue raises Overloaded —
    bounded queueing instead of unbounded latency and host memory."""
    from kubeflow_tpu.serving.continuous import Overloaded

    engine, cfg = _engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                max_pending=3)
    p = [1, 2, 3]
    # stuff the pending deque directly (no worker running)
    for _ in range(3):
        batcher._pending.append((p, 4, {}, asyncio.get_event_loop()
                                 .create_future(), None, 0, None))
    with pytest.raises(Overloaded, match="max_pending=3"):
        batcher._enqueue(p, 4, (), queue=None)
    batcher._pending.clear()
    await batcher.close()


@pytest.mark.slow
async def test_block_admission_defers_until_blocks_free():
    """Admission is accounted in KV BLOCKS, not just slots: with a pool
    holding 8 usable blocks (kv_pool_blocks=9) and two 40-token prompts
    each needing ceil(48/8)=6 blocks, both slots are free but only one
    request fits — the second must defer until the first retires (and
    its refcount-0 blocks are evicted), then decode exactly."""
    engine, cfg = _engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                kv_block_size=8, kv_pool_blocks=9)
    cap = batcher.cengine.pool.capacity
    gen = np.random.default_rng(23)
    prompts = [gen.integers(0, cfg.vocab_size, 40).tolist()
               for _ in range(2)]
    want = [_solo(engine, p, 8) for p in prompts]
    got = await asyncio.gather(
        *(batcher.submit(p, 8, ()) for p in prompts))
    assert list(got) == want
    assert batcher.requests == 2
    # never over-committed, and accounting closes once both retired:
    # every in-use block is owned by the radix cache
    assert batcher.cengine.pool.in_use <= cap
    assert batcher.kv_blocks_in_use() == \
        batcher.prefix_cache_stats()["cached_blocks"]
    await batcher.close()


@pytest.mark.slow
async def test_direct_path_logprobs_stop_at_first_eos():
    """Uniform logprobs contract: entries cover tokens up to AND
    INCLUDING the first EOS on the direct path too — the padded tail's
    pre-forcing sample logprobs must never reach clients."""
    engine0, cfg = _engine()
    p = np.random.default_rng(42).integers(0, cfg.vocab_size, 6).tolist()
    ref = _solo(engine0, p, 6)
    # the construction needs EOS to FIRST appear at index 2 — a seed
    # whose continuation repeats ref[2] earlier would fire EOS at
    # token 0 and trim everything (the way this test once rotted)
    assert ref[2] not in ref[:2], ref
    engine, _ = _engine(eos=ref[2])
    app = server_lib.create_serving_app({"m": engine})
    client = TestClient(TestServer(app))
    await client.start_server()
    r = await client.post(
        "/v1/models/m:generate",
        json={"tokens": [p, p], "max_new": 6, "logprobs": True})
    assert r.status == 200, await r.text()
    body = await r.json()
    for row, lps in zip(body["tokens"], body["logprobs"]):
        assert row[3:] == [ref[2]] * 3      # EOS-padded tail
        assert len(lps) == 3                # trimmed at first EOS
    await client.close()


async def test_stream_overload_is_429_not_broken_sse():
    engine, cfg = _engine()
    app = server_lib.create_serving_app(
        {"m": engine}, continuous=True, max_batch=2, max_pending=0)
    client = TestClient(TestServer(app))
    await client.start_server()
    r = await client.post(
        "/v1/models/m:generate",
        json={"tokens": [[1, 2, 3]], "max_new": 4, "stream": True})
    assert r.status == 429
    assert r.headers["Retry-After"] == "1"
    await client.close()


@pytest.mark.slow
async def test_continuous_chaos_soak():
    """30 concurrent requests over 3 slots with mixed max_new, sampling
    knobs, stop sequences and mid-flight cancellations: every future
    must settle, the slot pool must end fully free, and the batcher
    must still serve afterwards — the no-deadlock/no-leak property the
    individual tests can't cover in combination."""
    engine, cfg = _engine(eos=None, max_len=64)
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=3,
                                chunk=2, max_pending=64)
    gen = np.random.default_rng(77)

    async def one(i: int):
        p = gen.integers(0, cfg.vocab_size,
                         int(gen.integers(2, 12))).tolist()
        max_new = int(gen.integers(1, 9))
        sampling = []
        if i % 3 == 0:
            sampling.append(("temperature", 0.8))
        if i % 5 == 0:
            sampling.append(("stop", ((int(gen.integers(0, 64)),),)))
        task = asyncio.ensure_future(
            batcher.submit(p, max_new, tuple(sampling)))
        if i % 4 == 0:
            await asyncio.sleep(float(gen.uniform(0, 0.05)))
            task.cancel()
        try:
            out = await asyncio.wait_for(task, timeout=120)
            # a stop completing on the FIRST token legitimately trims
            # the output to empty — only the upper bound is invariant
            assert len(out) <= max_new
            return "done"
        except asyncio.CancelledError:
            return "cancelled"

    results = await asyncio.gather(*(one(i) for i in range(30)))
    assert set(results) <= {"done", "cancelled"}
    assert results.count("done") >= 15  # most ran to completion
    # pool drains completely once the dust settles
    for _ in range(400):
        if not batcher._active and not batcher._pending:
            break
        await asyncio.sleep(0.01)
    assert not batcher._active and not batcher._pending
    assert sorted(batcher._free) == [0, 1, 2]
    # and the batcher still serves
    p = gen.integers(0, cfg.vocab_size, 5).tolist()
    assert await batcher.submit(p, 4, ()) == _solo(engine, p, 4)
    await batcher.close()


@pytest.mark.slow
async def test_logprobs_shape_uniform_across_paths_with_eos():
    """Response SHAPE must not depend on the server's batcher mode:
    with EOS hit early and logprobs on, both paths return max_new
    EOS-padded tokens and EOS-trimmed logprobs."""
    engine0, cfg = _engine()
    p = np.random.default_rng(42).integers(0, cfg.vocab_size, 6).tolist()
    ref = _solo(engine0, p, 6)
    bodies = {}
    for mode, kwargs in (("continuous",
                          {"continuous": True, "max_batch": 2}),
                         ("direct", {})):
        engine, _ = _engine(eos=ref[2])
        app = server_lib.create_serving_app({"m": engine}, **kwargs)
        client = TestClient(TestServer(app))
        await client.start_server()
        r = await client.post(
            "/v1/models/m:generate",
            json={"tokens": [p], "max_new": 6, "logprobs": True})
        assert r.status == 200, await r.text()
        bodies[mode] = await r.json()
        await client.close()
    for mode, body in bodies.items():
        assert len(body["tokens"][0]) == 6, (mode, body)   # EOS-padded
        assert body["tokens"][0][2:] == [ref[2]] * 4, (mode, body)
        assert len(body["logprobs"][0]) == 3, (mode, body)  # EOS-trimmed
    assert bodies["continuous"]["tokens"] == bodies["direct"]["tokens"]


@pytest.mark.slow
async def test_insert_failure_before_dispatch_spares_active_slots():
    """ADVICE r04: a host-side adopt raise (donated state NOT consumed)
    must fail only the new admission — requests already decoding keep
    their KV and finish with correct tokens."""
    engine, cfg = _engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                chunk=1)
    gen = np.random.default_rng(5)
    p1 = gen.integers(0, cfg.vocab_size, 6).tolist()
    p2 = gen.integers(0, cfg.vocab_size, 4).tolist()
    want1 = _solo(engine, p1, 6)

    t1 = asyncio.ensure_future(batcher.submit(p1, 6, ()))
    # let the first request admit and start decoding
    while not batcher._active:
        await asyncio.sleep(0.01)

    real_adopt = batcher.cengine.adopt_slot

    def boom(*a, **k):
        raise ValueError("host-side admission failure")

    batcher.cengine.adopt_slot = boom
    with pytest.raises(ValueError, match="host-side admission"):
        await batcher.submit(p2, 4, ())
    batcher.cengine.adopt_slot = real_adopt

    assert list(await t1) == want1  # survivor unharmed
    # pool healthy afterwards: a fresh request still serves
    assert list(await batcher.submit(p1, 6, ())) == want1
    await batcher.close()


@pytest.mark.slow
async def test_insert_failure_after_dispatch_fails_actives_cleanly():
    """ADVICE r04: when the donated slot state WAS consumed by a failed
    adopt, active requests must get a deterministic RuntimeError now —
    not a confusing deleted-buffer crash on the next decode step."""
    engine, cfg = _engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                chunk=1)
    gen = np.random.default_rng(6)
    p1 = gen.integers(0, cfg.vocab_size, 6).tolist()
    want1 = _solo(engine, p1, 6)

    t1 = asyncio.ensure_future(batcher.submit(p1, 20, ()))
    while not batcher._active:
        await asyncio.sleep(0.01)

    def consume_and_boom(st, *a, **k):
        for leaf in jax.tree.leaves(st):
            leaf.delete()  # what a post-dispatch donation does
        raise ValueError("mid-adopt failure")

    real_adopt = batcher.cengine.adopt_slot
    batcher.cengine.adopt_slot = consume_and_boom
    with pytest.raises(ValueError, match="mid-adopt"):
        await batcher.submit(p1, 4, ())
    batcher.cengine.adopt_slot = real_adopt

    with pytest.raises(RuntimeError, match="slot state lost"):
        await t1
    assert not batcher._active  # slots released, nothing leaked
    # batcher recovers: state re-inits on the next admission
    assert list(await batcher.submit(p1, 6, ())) == want1
    await batcher.close()


async def test_stream_worker_failure_emits_terminal_sse_error():
    """ADVICE r04: a decode-worker failure after SSE headers are sent
    must end the stream with a deterministic `data: {"error": ...}`
    record, not a bare connection abort."""
    engine, cfg = _engine()
    app = server_lib.create_serving_app(
        {"m": engine}, continuous=True, max_batch=2)
    client = TestClient(TestServer(app))
    await client.start_server()
    batcher = app[server_lib.BATCHERS_KEY]["m"]

    calls = {"n": 0}
    real_step = batcher.cengine.step

    def failing_step(*a, **k):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise RuntimeError("chip fell over")
        return real_step(*a, **k)

    batcher.cengine.step = failing_step
    p = np.random.default_rng(7).integers(0, cfg.vocab_size, 5).tolist()
    resp = await client.post(
        "/v1/models/m:generate",
        json={"tokens": [p], "max_new": 8, "stream": True})
    assert resp.status == 200
    import json as _json
    records = []
    async for line in resp.content:
        line = line.strip()
        if line.startswith(b"data: "):
            records.append(_json.loads(line[6:]))
    assert records, "stream produced no records"
    final = records[-1]
    assert "error" in final and "chip fell over" in final["error"]
    assert final.get("done") is None
    await client.close()


async def test_stream_failure_terminal_error_direct_mode_too():
    """The terminal SSE error contract must hold in BOTH batcher modes
    (review: continuous-only would make the contract mode-dependent)."""
    engine, cfg = _engine()
    app = server_lib.create_serving_app({"m": engine})  # direct mode
    client = TestClient(TestServer(app))
    await client.start_server()

    def exploding_stream(*a, **k):
        yield np.zeros((1, 1), np.int64)
        raise RuntimeError("chip fell over")

    engine.generate_stream = exploding_stream
    resp = await client.post(
        "/v1/models/m:generate",
        json={"tokens": [[1, 2, 3]], "max_new": 8, "stream": True})
    assert resp.status == 200
    import json as _json
    records = []
    async for line in resp.content:
        line = line.strip()
        if line.startswith(b"data: "):
            records.append(_json.loads(line[6:]))
    final = records[-1]
    assert "error" in final and "chip fell over" in final["error"]
    assert final.get("done") is None
    await client.close()


@pytest.mark.slow
async def test_pipelined_depth2_tokens_identical_to_depth1():
    """Dispatch-ahead must never change WHAT is emitted — only when
    the host sees it. Same prompts, same budgets, both depths."""
    engine, cfg = _engine()
    gen = np.random.default_rng(21)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (5, 9, 14)]
    want = [_solo(engine, p, 6) for p in prompts]
    for depth in (1, 2):
        batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                    chunk=2, pipeline_depth=depth)
        got = await asyncio.gather(
            *(batcher.submit(p, 6, ()) for p in prompts))
        assert list(got) == want, f"depth={depth}"
        await batcher.close()


@pytest.mark.slow
async def test_pipelined_eos_overshoot_is_bounded():
    """With depth 2, an EOS retirement may cost at most (depth-1) x
    chunk speculative steps beyond the depth-1 minimum — never an
    unbounded run-on."""
    engine0, cfg = _engine()
    gen = np.random.default_rng(22)
    p = gen.integers(0, cfg.vocab_size, 6).tolist()
    ref = _solo(engine0, p, 8)
    eos = ref[2]  # greedy hits this at decode step 2
    engine, _ = _engine(eos=eos)
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                chunk=2, pipeline_depth=2)
    got = await batcher.submit(p, 8, ())
    assert got == ref[:3] + [eos] * 5  # EOS-padded, same answer
    # minimum decode steps to see EOS with chunk=2 is 2; speculation
    # may add at most (depth-1) x chunk = 2 more
    assert batcher.calls <= 4, batcher.calls
    # pool healthy afterwards
    q = gen.integers(0, cfg.vocab_size, 4).tolist()
    assert await batcher.submit(q, 4, ()) == _solo(engine, q, 4)
    await batcher.close()


async def test_pipelined_rejects_bad_depth():
    engine, _ = _engine()
    with pytest.raises(ValueError, match="pipeline_depth"):
        ContinuousBatcher(engine, asyncio.Lock(), pipeline_depth=0)


@pytest.mark.slow
async def test_async_device_failure_in_drain_path_fails_cleanly():
    """An async-dispatched chunk that FAILED on device reports ready
    and raises at materialization (the TPU failure mode). The drain
    path must route that through _fail_all — every future settles with
    the error and the batcher recovers — never kill the worker and
    hang the streams (review finding on the pipelined loop)."""
    engine, cfg = _engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                chunk=2, pipeline_depth=2)
    gen = np.random.default_rng(31)
    p = gen.integers(0, cfg.vocab_size, 5).tolist()

    class PoisonArray:
        """Looks ready; dies on host transfer, like a failed XLA
        computation surfacing at np.asarray."""

        def is_ready(self):
            return True

        def __array__(self, *a, **k):
            raise RuntimeError("device computation failed")

    real_step = batcher.cengine.step
    calls = {"n": 0}

    def poisoned_step(st, sp, rng, steps):
        calls["n"] += 1
        if calls["n"] == 1:
            st2, toks, lps, rng2 = real_step(st, sp, rng, steps)
            return st2, PoisonArray(), PoisonArray(), rng2
        return real_step(st, sp, rng, steps)

    batcher.cengine.step = poisoned_step
    with pytest.raises(RuntimeError, match="device computation failed"):
        await asyncio.wait_for(batcher.submit(p, 6, ()), timeout=30)
    assert not batcher._active  # nothing leaked

    # the worker survived: a fresh request serves correctly
    want = _solo(engine, p, 4)
    got = await asyncio.wait_for(batcher.submit(p, 4, ()), timeout=60)
    assert got == want
    await batcher.close()


def _jit_cache_sizes(ce):
    """Signatures each admission and decode jit has met."""
    return {name: fn._cache_size()
            for name, fn in (("append_rows", ce._append_jit),
                             ("adopt_slot", ce._adopt_jit),
                             ("copy_cells", ce._copy_cells_jit),
                             ("step", ce._step_jit),
                             ("reset_slots", ce._reset_jit))}


async def test_warmup_covers_every_program_traffic_runs():
    """Ready means compiled: after `warmup()` a lone request, bursts
    of 2 and 4 and a prompt sharing a block and a half with an earlier
    one (the copy-on-write seed) meet no signature the jits and the
    compile watch have not seen."""
    engine, cfg = _engine()
    # weights put on a named device, as a restored checkpoint's are:
    # what a program returns is then committed too, so a jit meets the
    # state fresh from the host once and its own outputs ever after
    engine.params = jax.device_put(engine.params, jax.devices()[0])
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=4,
                                chunk=2, kv_block_size=8,
                                prefill_chunk_tokens=16)
    # adopt, copy, append; step x 2; reset at 1, 2, 4
    assert batcher.warmup() == 3 + 2 + 3
    warmed = _jit_cache_sizes(batcher.cengine)
    assert all(warmed.values()), warmed
    watched = batcher.compile_watch.counts()
    gen = np.random.default_rng(41)

    def prompt(n):
        return gen.integers(0, cfg.vocab_size, n).tolist()

    await batcher.submit(prompt(5), 4, ())  # decodes 2 steps, then 1
    for burst in (2, 4):
        await asyncio.gather(*(batcher.submit(prompt(20), 5, ())
                               for _ in range(burst)))
    head = prompt(24)
    await batcher.submit(head, 3, ())
    reused = batcher.tokens_reused
    await batcher.submit(head[:12] + prompt(8), 3, ())
    assert batcher.tokens_reused - reused == 12  # a block and a half
    assert _jit_cache_sizes(batcher.cengine) == warmed
    assert batcher.compile_watch.counts() == watched
    await batcher.close()


async def test_registered_prefix_rides_the_radix_cache():
    """A registered prefix is a name for tokens: the first request
    that names it computes them through the slices like any prompt,
    the second finds the prefix's whole blocks in the radix cache, and
    both decode what the concatenated prompt decodes."""
    engine, cfg = _engine(max_len=96)
    gen = np.random.default_rng(42)
    sys_prompt = gen.integers(0, cfg.vocab_size, 23).tolist()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                kv_block_size=8,
                                prefixes={"sys": sys_prompt})
    p1 = gen.integers(0, cfg.vocab_size, 6).tolist()
    p2 = gen.integers(0, cfg.vocab_size, 9).tolist()
    got1 = await batcher.submit(p1, 5, (("prefix", "sys"),))
    assert (batcher.tokens_reused, batcher.tokens_prefilled) == (0, 29)
    got2 = await batcher.submit(p2, 5, (("prefix", "sys"),))
    assert batcher.tokens_reused >= 16  # 23 tokens: two blocks of 8
    assert batcher.prefix_hits == 1
    assert got1 == _solo(engine, sys_prompt + p1, 5)
    assert got2 == _solo(engine, sys_prompt + p2, 5)
    await batcher.close()


@pytest.mark.slow
@pytest.mark.parametrize("family_name", ["gemma", "moe"])
async def test_non_llama_families_through_the_slot_engine(family_name):
    """The continuous batcher has only ever been exercised with llama;
    gemma (GQA 4:1, sliding window, scaled embeddings) and MoE (routed
    mlp injection) must decode identically to their solo engines
    through slot admission, scatter insert, and chunked stepping."""
    from kubeflow_tpu.serving import GEMMA_FAMILY, MOE_LLAMA_FAMILY

    if family_name == "gemma":
        from kubeflow_tpu.models import gemma
        cfg = gemma.GEMMA_TINY
        params = dict(gemma.init(jax.random.key(1), cfg))
        fam = GEMMA_FAMILY
    else:
        from kubeflow_tpu.models import llama_moe
        cfg = llama_moe.MIXTRAL_TINY
        params = dict(llama_moe.init(jax.random.key(1), cfg))
        fam = MOE_LLAMA_FAMILY
    engine = InferenceEngine(params, cfg, fam, EngineConfig(max_len=64))
    gen = np.random.default_rng(50)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (4, 9, 6)]
    want = [_solo(engine, p, 5) for p in prompts]

    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                chunk=2)
    got = await asyncio.gather(
        *(batcher.submit(p, 5, ()) for p in prompts))
    assert list(got) == want
    await batcher.close()


@pytest.mark.slow
async def test_pipelined_depth2_with_chunked_prefill_and_prefixes():
    """The depth-2 seam against round-4 admission features: chunked
    long-prompt prefill and shared-prefix KV, interleaved with plain
    requests, must stay token-exact while chunks dispatch ahead."""
    engine, cfg = _engine(max_len=128)
    gen = np.random.default_rng(60)
    sys_prompt = gen.integers(0, cfg.vocab_size, 17).tolist()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=3,
                                chunk=2, pipeline_depth=2,
                                prefill_chunk_tokens=8,
                                prefixes={"sys": sys_prompt})
    long_p = gen.integers(0, cfg.vocab_size, 21).tolist()
    pref_p = gen.integers(0, cfg.vocab_size, 6).tolist()
    plain = gen.integers(0, cfg.vocab_size, 5).tolist()
    want_long = _solo(engine, long_p, 6)
    want_pref = _solo(engine, sys_prompt + pref_p, 6)
    want_plain = _solo(engine, plain, 6)
    got_long, got_pref, got_plain = await asyncio.gather(
        batcher.submit(long_p, 6, ()),
        batcher.submit(pref_p, 6, (("prefix", "sys"),)),
        batcher.submit(plain, 6, ()))
    assert got_long == want_long
    assert got_pref == want_pref
    assert got_plain == want_plain
    # churn: reuse slots under depth 2 once more
    got2 = await batcher.submit(plain, 4, ())
    assert got2 == _solo(engine, plain, 4)
    await batcher.close()
