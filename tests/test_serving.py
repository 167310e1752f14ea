"""Inference engine + serving REST app + export."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

pytest_plugins = ("aiohttp.pytest_plugin",)

from kubeflow_tpu.models import gemma, llama
from kubeflow_tpu.serving import (
    EngineConfig, GEMMA_FAMILY, InferenceEngine, LLAMA_FAMILY,
)
from kubeflow_tpu.serving import export as export_lib
from kubeflow_tpu.serving import server as server_lib


@pytest.fixture(scope="module")
def llama_engine():
    cfg = llama.LLAMA_TINY
    params = llama.init(jax.random.key(0), cfg)
    return InferenceEngine(params, cfg, LLAMA_FAMILY,
                           EngineConfig(max_len=64)), cfg, params


def _naive_greedy(module, params, cfg, prompt, max_new):
    """Oracle: full-prefix recompute argmax decode."""
    toks = prompt
    out = []
    for _ in range(max_new):
        logits = module.apply(params, cfg, toks)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


@pytest.mark.slow
def test_cached_decode_matches_full_recompute(llama_engine):
    engine, cfg, params = llama_engine
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)),
        jnp.int32)
    got = engine.generate(prompt, max_new=6)
    want = _naive_greedy(llama, params, cfg, prompt, 6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gemma_cached_decode_matches():
    cfg = gemma.GEMMA_TINY
    params = gemma.init(jax.random.key(1), cfg)
    engine = InferenceEngine(params, cfg, GEMMA_FAMILY,
                             EngineConfig(max_len=32))
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 5)),
        jnp.int32)
    got = engine.generate(prompt, max_new=4)
    want = _naive_greedy(gemma, params, cfg, prompt, 4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_filter_logits_top_k():
    from kubeflow_tpu.serving import filter_logits
    logits = jnp.asarray([[3.0, 1.0, 4.0, 1.5, 5.0]])
    out = filter_logits(logits, jnp.asarray(2), jnp.asarray(1.0))
    finite = np.isfinite(np.asarray(out))[0]
    assert list(finite) == [False, False, True, False, True]  # 4.0, 5.0
    # 0 disables
    out = filter_logits(logits, jnp.asarray(0), jnp.asarray(1.0))
    assert np.isfinite(np.asarray(out)).all()


def test_filter_logits_top_p():
    from kubeflow_tpu.serving import filter_logits
    # probs ~ [0.643, 0.237, 0.087, 0.032] for logits [3, 2, 1, 0]
    logits = jnp.log(jnp.asarray([[0.643, 0.237, 0.087, 0.032]]))
    for p, want in [(0.5, [True, False, False, False]),   # first alone
                    (0.7, [True, True, False, False]),
                    (0.9, [True, True, True, False]),
                    (1.0, [True, True, True, True])]:
        out = filter_logits(logits, jnp.asarray(0), jnp.asarray(p))
        assert list(np.isfinite(np.asarray(out))[0]) == want, p


def test_filter_logits_top_p_renormalizes_after_top_k():
    """HF sequential semantics: k filters, RENORMALIZE, then nucleus.
    probs [0.4, 0.3, 0.3] with top_k=2 renormalize to [0.571, 0.429];
    top_p=0.5 must keep only the first token (raw-mass semantics would
    wrongly keep both: 0.4 < 0.5)."""
    from kubeflow_tpu.serving import filter_logits
    logits = jnp.log(jnp.asarray([[0.4, 0.3, 0.3]]))
    out = filter_logits(logits, jnp.asarray(2), jnp.asarray(0.5))
    assert list(np.isfinite(np.asarray(out))[0]) == [True, False, False]


def test_sampling_params_are_dynamic_and_respected(llama_engine):
    """top_k=1 / tiny top_p must reproduce greedy exactly, sampled runs
    stay inside the allowed set, and sweeping the knobs must NOT
    recompile the decode scan (they are traced values, not statics)."""
    engine, cfg, params = llama_engine
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 8)),
        jnp.int32)
    greedy = np.asarray(engine.generate(prompt, max_new=6))
    compiles_before = engine._generate_jit._cache_size()

    k1 = engine.generate(prompt, max_new=6, temperature=1.0, top_k=1,
                         rng=jax.random.key(7))
    np.testing.assert_array_equal(np.asarray(k1), greedy)
    p_tiny = engine.generate(prompt, max_new=6, temperature=2.5,
                             top_p=1e-6, rng=jax.random.key(8))
    np.testing.assert_array_equal(np.asarray(p_tiny), greedy)
    drawn = np.asarray(engine.generate(
        prompt, max_new=6, temperature=0.7, top_k=5, top_p=0.9,
        rng=jax.random.key(9)))
    assert engine._generate_jit._cache_size() == compiles_before
    # Every sampled token must come from that step's top-5 logits
    # (replay the emitted prefix through the dense forward as oracle).
    seq = np.concatenate([np.asarray(prompt), drawn], axis=1)
    for step in range(drawn.shape[1]):
        logits = np.asarray(llama.apply(
            params, cfg, jnp.asarray(seq[:, :prompt.shape[1] + step])))
        top5 = np.argsort(-logits[:, -1], axis=-1)[:, :5]
        for b in range(seq.shape[0]):
            assert drawn[b, step] in top5[b], (step, b)

    with pytest.raises(ValueError):
        engine.generate(prompt, max_new=6, top_p=0.0)
    with pytest.raises(ValueError):
        engine.generate(prompt, max_new=6, top_k=-1)


def test_generate_length_validation(llama_engine):
    engine, cfg, _ = llama_engine
    prompt = jnp.zeros((1, 60), jnp.int32)
    with pytest.raises(ValueError, match="exceeds cache bucket"):
        engine.generate(prompt, max_new=10)


def test_export_stablehlo_roundtrip(tmp_path, llama_engine):
    engine, cfg, params = llama_engine
    toks = jnp.zeros((1, 8), jnp.int32)
    fn = lambda t: llama.apply(params, cfg, t)
    path = str(tmp_path / "llama_tiny.shlo")
    size = export_lib.export_stablehlo(fn, (toks,), path)
    assert size > 0
    loaded = export_lib.load_stablehlo(path)
    got = loaded.call(toks)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(fn(toks)), rtol=1e-5, atol=1e-5)


def test_saved_model_export_degrades_clearly(tmp_path, llama_engine):
    engine, cfg, params = llama_engine
    try:
        import tensorflow  # noqa: F401
        pytest.skip("tensorflow present; degradation path not applicable")
    except ImportError:
        pass
    with pytest.raises(RuntimeError, match="stablehlo"):
        export_lib.export_saved_model(
            lambda t: llama.apply(params, cfg, t),
            (jnp.zeros((1, 8), jnp.int32),), str(tmp_path / "sm"))


def test_saved_model_export_roundtrip(tmp_path, llama_engine):
    """When TF is present, the reference's serving format (SavedModel via
    jax2tf — ref docs_dev/tf_serving.md) round-trips numerically."""
    tf = pytest.importorskip("tensorflow")
    engine, cfg, params = llama_engine
    toks = jnp.zeros((1, 8), jnp.int32)
    fn = lambda t: llama.apply(params, cfg, t)
    path = str(tmp_path / "sm")
    export_lib.export_saved_model(fn, (toks,), path)
    loaded = tf.saved_model.load(path)
    got = np.asarray(loaded.f(tf.constant(np.asarray(toks))))
    np.testing.assert_allclose(got, np.asarray(fn(toks)),
                               rtol=1e-4, atol=1e-4)


def test_eos_masking():
    """After EOS appears, the rest of the generation is EOS."""
    cfg = llama.LLAMA_TINY
    params = llama.init(jax.random.key(0), cfg)
    plain = InferenceEngine(params, cfg, LLAMA_FAMILY,
                            EngineConfig(max_len=64))
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8)),
        jnp.int32)
    ref = np.asarray(plain.generate(prompt, max_new=8))[0]
    # Pick the greedy second token as the "EOS" so masking must trigger.
    eos = int(ref[1])
    eng = InferenceEngine(params, cfg, LLAMA_FAMILY,
                          EngineConfig(max_len=64, eos_token=eos))
    got = np.asarray(eng.generate(prompt, max_new=8))[0]
    first_eos = int(np.argmax(got == eos))
    assert np.all(got[first_eos:] == eos)


def test_byte_tokenizer_roundtrip():
    s = "hello TPU ✓"
    assert server_lib.byte_decode(server_lib.byte_encode(s)) == s


async def test_serving_rest_api(llama_engine):
    engine, cfg, _ = llama_engine
    app = server_lib.create_serving_app({"llama-tiny": engine})
    client = TestClient(TestServer(app))
    await client.start_server()

    r = await client.get("/healthz")
    assert r.status == 200

    r = await client.get("/v1/models")
    models = (await r.json())["models"]
    assert models[0]["name"] == "llama-tiny"
    assert models[0]["family"] == "llama"

    r = await client.post("/v1/models/llama-tiny:generate",
                          json={"tokens": [[1, 2, 3, 4]], "max_new": 4})
    assert r.status == 200
    toks = (await r.json())["tokens"]
    assert len(toks) == 1 and len(toks[0]) == 4

    # validation surface
    r = await client.post("/v1/models/llama-tiny:generate",
                          json={"tokens": [[1, 2], [1, 2, 3]]})
    assert r.status == 400
    r = await client.post("/v1/models/llama-tiny:generate",
                          json={"tokens": [[99999]]})
    assert r.status == 400
    r = await client.post("/v1/models/nope:generate",
                          json={"tokens": [[1]]})
    assert r.status == 404
    r = await client.post("/v1/models/llama-tiny:generate",
                          json={"tokens": [[1] * 60], "max_new": 30})
    assert r.status == 400
    # malformed types must be 400, not 500
    r = await client.post("/v1/models/llama-tiny:generate",
                          json={"tokens": [[1, "a"]]})
    assert r.status == 400
    r = await client.post("/v1/models/llama-tiny:generate",
                          json={"text": 123})
    assert r.status == 400
    r = await client.post("/v1/models/llama-tiny:generate",
                          json={"tokens": [[1]], "max_new": "x"})
    assert r.status == 400

    # per-request sampling params: accepted and validated
    r = await client.post(
        "/v1/models/llama-tiny:generate",
        json={"tokens": [[1, 2, 3, 4]], "max_new": 4,
              "temperature": 0.8, "top_k": 5, "top_p": 0.9})
    assert r.status == 200, await r.text()
    assert len((await r.json())["tokens"][0]) == 4
    for bad in ({"temperature": -1}, {"temperature": "hot"},
                {"top_k": -2}, {"top_k": 1.5}, {"top_p": 0},
                {"top_p": 1.2}):
        r = await client.post(
            "/v1/models/llama-tiny:generate",
            json={"tokens": [[1]], "max_new": 2, **bad})
        assert r.status == 400, bad
    await client.close()


@pytest.mark.slow
def test_left_padded_prompts_decode_like_unpadded():
    """A left-padded row must generate exactly what its unpadded prompt
    would: pads are masked out of attention and rope sees logical
    positions. Sharpened head -> stable argmax despite shape-dependent
    reduction order."""
    import dataclasses as _dc
    params = dict(llama.init(jax.random.key(0), llama.LLAMA_TINY))
    params["lm_head"] = params["lm_head"] * 50.0
    cfg = llama.LLAMA_TINY
    eng = InferenceEngine(params, cfg, LLAMA_FAMILY, EngineConfig(max_len=64))

    rng = np.random.default_rng(5)
    short = rng.integers(0, cfg.vocab_size, 5)
    long = rng.integers(0, cfg.vocab_size, 9)
    want_short = np.asarray(eng.generate(
        jnp.asarray([short], jnp.int32), max_new=6))
    want_long = np.asarray(eng.generate(
        jnp.asarray([long], jnp.int32), max_new=6))

    arr = np.zeros((2, 9), np.int32)
    mask = np.zeros((2, 9), bool)
    arr[0, 4:] = short; mask[0, 4:] = True
    arr[1, :] = long;   mask[1, :] = True
    got = np.asarray(eng.generate(
        jnp.asarray(arr), max_new=6, prompt_mask=jnp.asarray(mask)))
    np.testing.assert_array_equal(got[0], want_short[0])
    np.testing.assert_array_equal(got[1], want_long[0])

    # malformed masks are rejected
    bad = mask.copy(); bad[0] = [True] * 4 + [False] + [True] * 4
    with pytest.raises(ValueError, match="LEFT-aligned"):
        eng.generate(jnp.asarray(arr), max_new=2,
                     prompt_mask=jnp.asarray(bad))
    with pytest.raises(ValueError, match="shape"):
        eng.generate(jnp.asarray(arr), max_new=2,
                     prompt_mask=jnp.ones((2, 4), bool))


@pytest.mark.slow
async def test_dynamic_batcher_coalesces_concurrent_requests():
    """N concurrent single-prompt requests with different lengths must
    run as ONE padded engine call and return what each request would
    get alone. Sharpened head: batch-1 vs batch-4 reduction order must
    not flip near-tied argmaxes (same hazard as the left-padding test)."""
    import asyncio as aio

    cfg = llama.LLAMA_TINY
    params = dict(llama.init(jax.random.key(0), cfg))
    params["lm_head"] = params["lm_head"] * 50.0
    engine = InferenceEngine(params, cfg, LLAMA_FAMILY,
                             EngineConfig(max_len=64))
    app = server_lib.create_serving_app(
        {"m": engine}, batch_window_ms=80.0)
    client = TestClient(TestServer(app))
    await client.start_server()

    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (4, 7, 7, 10)]
    want = [np.asarray(engine.generate(
        jnp.asarray([p], jnp.int32), max_new=5))[0].tolist()
        for p in prompts]

    async def one(p):
        r = await client.post("/v1/models/m:generate",
                              json={"tokens": [p], "max_new": 5})
        assert r.status == 200, await r.text()
        return (await r.json())["tokens"][0]

    batcher = app[server_lib.BATCHERS_KEY]["m"]
    got = await aio.gather(*(one(p) for p in prompts))
    assert batcher.calls == 1, batcher.calls  # coalesced, not serialized
    assert batcher.requests == len(prompts)  # success-counted: the
    # mean-effective-batch evidence /v1/models exposes
    for g, w in zip(got, want):
        assert g == w
    await client.close()


@pytest.mark.slow
async def test_batcher_mixes_sampling_params_in_one_call():
    """Per-row SamplingParams: requests with DIFFERENT knobs (greedy,
    sampled, top_k=1-forced-greedy) coalesce into a single engine call,
    and the deterministic rows still get exactly their solo outputs."""
    import asyncio as aio

    cfg = llama.LLAMA_TINY
    params = dict(llama.init(jax.random.key(0), cfg))
    params["lm_head"] = params["lm_head"] * 50.0
    engine = InferenceEngine(params, cfg, LLAMA_FAMILY,
                             EngineConfig(max_len=64))
    app = server_lib.create_serving_app(
        {"m": engine}, batch_window_ms=80.0)
    client = TestClient(TestServer(app))
    await client.start_server()

    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (4, 6, 8)]
    greedy_refs = [np.asarray(engine.generate(
        jnp.asarray([p], jnp.int32), max_new=5))[0].tolist()
        for p in prompts]
    bodies = [
        {"tokens": [prompts[0]], "max_new": 5},                 # greedy
        {"tokens": [prompts[1]], "max_new": 5,
         "temperature": 0.9, "top_p": 0.8},                     # sampled
        {"tokens": [prompts[2]], "max_new": 5,
         "temperature": 1.0, "top_k": 1},                       # =greedy
    ]

    async def one(body):
        r = await client.post("/v1/models/m:generate", json=body)
        assert r.status == 200, await r.text()
        return (await r.json())["tokens"][0]

    batcher = app[server_lib.BATCHERS_KEY]["m"]
    before = batcher.calls
    got = await aio.gather(*(one(b) for b in bodies))
    assert batcher.calls == before + 1, "mixed knobs must coalesce"
    assert got[0] == greedy_refs[0]
    assert got[2] == greedy_refs[2]           # top_k=1 is argmax
    assert all(0 <= t < cfg.vocab_size for t in got[1])
    await client.close()


async def test_speculative_decoding_over_rest():
    """A model registered with a draft serves "speculative": true —
    greedy output identical to the plain path, acceptance stats in the
    response, validation on batch/gamma/missing-draft."""
    cfg = llama.LLAMA_TINY
    params = dict(llama.init(jax.random.key(0), cfg))
    params["lm_head"] = params["lm_head"] * 50.0
    engine = InferenceEngine(params, cfg, LLAMA_FAMILY,
                             EngineConfig(max_len=64))
    app = server_lib.create_serving_app(
        {"m": engine}, drafts={"m": engine})   # self-draft: accepts all
    client = TestClient(TestServer(app))
    await client.start_server()

    prompt = np.random.default_rng(6).integers(
        0, cfg.vocab_size, 8).tolist()
    want = np.asarray(engine.generate(
        jnp.asarray([prompt], jnp.int32), max_new=10))[0].tolist()
    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [prompt], "max_new": 10,
                                "speculative": True, "gamma": 3})
    assert r.status == 200, await r.text()
    out = await r.json()
    assert out["tokens"][0] == want
    assert out["speculative"]["acceptance_rate"] == 1.0
    assert out["speculative"]["proposed"] > 0

    # client-swept gamma buckets to powers of two <= 8: a second value
    # in the same bucket must not add a compile
    spec_eng = app[server_lib.SPEC_KEY]["m"]
    before = spec_eng._jit._cache_size()
    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [prompt], "max_new": 10,
                                "speculative": True, "gamma": 2})
    assert r.status == 200
    # first request's gamma=3 bucketed to 2; same bucket -> cached
    assert spec_eng._jit._cache_size() == before

    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [prompt, prompt],
                                "max_new": 4, "speculative": True})
    assert r.status == 400  # batch-1 only
    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [prompt], "max_new": 4,
                                "speculative": True, "gamma": 0})
    assert r.status == 400
    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [prompt], "max_new": 50,
                                "speculative": True, "gamma": 8})
    assert r.status == 400  # gamma overflows the cache bucket

    app2 = server_lib.create_serving_app({"m": engine})
    client2 = TestClient(TestServer(app2))
    await client2.start_server()
    r = await client2.post("/v1/models/m:generate",
                           json={"tokens": [prompt], "max_new": 4,
                                 "speculative": True})
    assert r.status == 400  # no draft registered
    await client2.close()
    await client.close()


def test_byte_decode_drops_out_of_range_ids():
    # vocab-tail ids (>= 256+offset) and specials must not crash decode
    assert server_lib.byte_decode(
        [1, 300, ord("h") + 3, ord("i") + 3, 2, 500]) == "hi"


async def test_out_of_int32_token_ids_are_400(llama_engine):
    engine, _, _ = llama_engine
    app = server_lib.create_serving_app({"m": engine})
    client = TestClient(TestServer(app))
    await client.start_server()
    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [[2**40]], "max_new": 1})
    assert r.status == 400
    await client.close()


@pytest.mark.slow
def test_sharded_gemma_scale_vocab_decode_matches_unsharded():
    """VERDICT r2 weak #7: serving embed at Gemma vocab scale under a
    sharded mesh. The engine's embed (ops.embedding.embed_lookup) must
    switch to the one-hot MXU contraction when vocab/embed are sharded
    (a gather would force the SPMD partitioner to replicate the 256k
    table every step) and produce IDENTICAL greedy tokens."""
    import dataclasses

    from kubeflow_tpu.parallel import (
        LLAMA_RULES, MeshSpec, create_mesh, shard_pytree_specs)

    # Gemma-2B's 256k vocabulary on otherwise-tiny dims (the sharding
    # semantics depend on the table's vocab axis, not the block sizes).
    cfg = dataclasses.replace(
        llama.LLAMA_TINY, vocab_size=262144, tie_embeddings=True)
    params = jax.jit(lambda k: llama.init(k, cfg))(jax.random.key(1))
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 6)),
        jnp.int32)

    ref_engine = InferenceEngine(params, cfg, LLAMA_FAMILY,
                                 EngineConfig(max_len=32))
    want = ref_engine.generate(prompt, max_new=4)

    mesh = create_mesh(MeshSpec(data=1, fsdp=2, tensor=4))
    shardings = shard_pytree_specs(
        LLAMA_RULES, llama.param_logical_axes(cfg), mesh)
    sharded_params = jax.device_put(params, shardings)
    # vocab axis genuinely sharded over tensor
    assert sharded_params["embed"].sharding.spec[0] == "tensor"
    engine = InferenceEngine(sharded_params, cfg, LLAMA_FAMILY,
                             EngineConfig(max_len=32))
    with jax.set_mesh(mesh):
        got = engine.generate(prompt, max_new=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
async def test_direct_path_buckets_max_new_but_trims_response(llama_engine):
    """max_new is jit-static on the direct (client-batch) path: the
    server buckets it (ADVICE r3: a sweep must not mint one compile per
    value) yet the response carries exactly the requested count."""
    engine, cfg, _ = llama_engine
    app = server_lib.create_serving_app({"llama-tiny": engine})
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        for ask in (3, 5, 7):  # same power-of-two bucket (16)
            r = await client.post(
                "/v1/models/llama-tiny:generate",
                json={"tokens": [[1, 2, 3], [4, 5, 6]], "max_new": ask})
            assert r.status == 200, await r.text()
            toks = (await r.json())["tokens"]
            assert [len(t) for t in toks] == [ask, ask]
    finally:
        await client.close()


def test_top_k_overflow_rejected_in_library_api(llama_engine):
    """ADVICE r3: top_k >= 2**31 wrapped negative through the int32
    cast for direct library callers; must ValueError like the server."""
    engine, cfg, _ = llama_engine
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    with pytest.raises(ValueError, match="2\\*\\*31"):
        engine.generate(prompt, max_new=2, temperature=1.0, top_k=2**31)


@pytest.mark.slow
def test_generate_stream_equals_oneshot(llama_engine):
    """Streamed chunks concatenate to exactly generate()'s output under
    the same rng — both entry points scan the SAME step body — and the
    stream stops early once every row hits EOS."""
    engine, cfg, _ = llama_engine
    prompt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    for kwargs in ({}, {"rng": jax.random.key(7), "temperature": 0.8,
                        "top_k": 5}):
        full = np.asarray(engine.generate(prompt, max_new=13, **kwargs))
        parts = list(engine.generate_stream(
            prompt, max_new=13, chunk=4, **kwargs))
        assert [p.shape[0] for p in parts] == [2] * len(parts)
        got = np.concatenate(parts, axis=1)
        assert got.shape[1] <= 13
        assert (got == full[:, :got.shape[1]]).all()
        # anything generate() produced past an early stream stop is
        # post-EOS padding by construction
        if got.shape[1] < 13 and engine.ec.eos_token is not None:
            assert (full[:, got.shape[1]:] == engine.ec.eos_token).all()


@pytest.mark.slow
async def test_sse_streaming_over_rest(llama_engine):
    """POST {"stream": true} returns text/event-stream whose chunk
    events concatenate to the non-streaming response's tokens."""
    engine, cfg, _ = llama_engine
    app = server_lib.create_serving_app({"llama-tiny": engine})
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        body = {"tokens": [[1, 2, 3, 4]], "max_new": 11}
        r = await client.post("/v1/models/llama-tiny:generate", json=body)
        assert r.status == 200
        oneshot = (await r.json())["tokens"]

        r = await client.post("/v1/models/llama-tiny:generate",
                              json={**body, "stream": True})
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/event-stream")
        events = []
        async for line in r.content:
            line = line.strip()
            if line.startswith(b"data: "):
                import json as _json
                events.append(_json.loads(line[len(b"data: "):]))
        assert events and events[-1]["done"] is True
        streamed = [t for e in events[:-1] for t in e["tokens"][0]]
        assert events[-1]["total"] == len(streamed)
        assert streamed == oneshot[0][:len(streamed)]

        # stream + speculative is a 400, not a silent fallback
        r = await client.post(
            "/v1/models/llama-tiny:generate",
            json={**body, "stream": True, "speculative": True})
        assert r.status == 400
    finally:
        await client.close()


def test_generate_stream_validates_eagerly(llama_engine):
    """Review finding: bad arguments must raise at CALL time, not at
    first next() (a server would have already sent SSE headers)."""
    engine, cfg, _ = llama_engine
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    with pytest.raises(ValueError, match="exceeds cache bucket"):
        engine.generate_stream(prompt, max_new=10**6)
    with pytest.raises(ValueError, match="chunk"):
        engine.generate_stream(prompt, max_new=4, chunk=0)


def test_moe_cached_decode_matches_full_recompute():
    """MoE serving: the engine's injected-FFN family (dropless routing)
    must match a full-prefix recompute through llama_moe.apply with the
    same dropless capacity (training's capacity_factor drops tokens by
    design; serving never may — both sides pinned dropless here so any
    mismatch is a cache/routing bug, not a drop)."""
    import dataclasses

    from kubeflow_tpu.models import llama_moe
    from kubeflow_tpu.serving import MOE_LLAMA_FAMILY

    cfg = dataclasses.replace(
        llama_moe.MIXTRAL_TINY,
        capacity_factor=(llama_moe.MIXTRAL_TINY.num_experts
                         / llama_moe.MIXTRAL_TINY.top_k))
    params = dict(llama_moe.init(jax.random.key(2), cfg))
    params["lm_head"] = params["lm_head"] * 50.0
    engine = InferenceEngine(params, cfg, MOE_LLAMA_FAMILY,
                             EngineConfig(max_len=32))
    prompt = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 6)),
        jnp.int32)
    got = engine.generate(prompt, max_new=4)

    toks = prompt
    want = []
    for _ in range(4):
        logits, _aux = llama_moe.apply(params, cfg, toks)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        want.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.stack(want, axis=1)))


@pytest.mark.slow
async def test_moe_serves_through_continuous_batcher():
    """Composition: the MoE engine rides the continuous batcher (slot
    KV scatter + injected-FFN step) unchanged."""
    import asyncio as aio
    import dataclasses

    from kubeflow_tpu.models import llama_moe
    from kubeflow_tpu.serving import MOE_LLAMA_FAMILY
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    cfg = dataclasses.replace(
        llama_moe.MIXTRAL_TINY,
        capacity_factor=(llama_moe.MIXTRAL_TINY.num_experts
                         / llama_moe.MIXTRAL_TINY.top_k))
    params = dict(llama_moe.init(jax.random.key(2), cfg))
    params["lm_head"] = params["lm_head"] * 50.0
    engine = InferenceEngine(params, cfg, MOE_LLAMA_FAMILY,
                             EngineConfig(max_len=64))
    batcher = ContinuousBatcher(engine, aio.Lock(), max_slots=2)
    gen = np.random.default_rng(4)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (5, 9)]
    want = [np.asarray(engine.generate(
        jnp.asarray([p], jnp.int32), max_new=5))[0].tolist()
        for p in prompts]
    got = await aio.gather(
        *(batcher.submit(p, 5, ()) for p in prompts))
    assert list(got) == want
    await batcher.close()


def test_continuous_only_knobs_rejected_without_continuous(llama_engine):
    engine, _, _ = llama_engine
    with pytest.raises(ValueError, match="require continuous"):
        server_lib.create_serving_app({"m": engine}, warmup=True)
    with pytest.raises(ValueError, match="require continuous"):
        server_lib.create_serving_app({"m": engine},
                                      prefixes={"sys": [1, 2]})


@pytest.mark.slow
async def test_score_endpoint_matches_full_forward(llama_engine):
    """Teacher-forced scoring: engine.score and the :score door match
    a direct log-softmax over llama.apply logits, and total/count give
    perplexity directly."""
    import math

    engine, cfg, params = llama_engine
    seq = np.random.default_rng(50).integers(
        0, cfg.vocab_size, (2, 9)).tolist()
    lps = np.asarray(engine.score(jnp.asarray(seq, jnp.int32)))
    logits = llama.apply(params, cfg, jnp.asarray(seq, jnp.int32))
    want = np.asarray(
        jnp.take_along_axis(
            jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32),
                               axis=-1),
            jnp.asarray(seq, jnp.int32)[:, 1:, None], axis=-1)[:, :, 0])
    np.testing.assert_allclose(lps, want, atol=1e-4)

    app = server_lib.create_serving_app({"m": engine})
    client = TestClient(TestServer(app))
    await client.start_server()
    r = await client.post("/v1/models/m:score", json={"tokens": seq})
    assert r.status == 200, await r.text()
    body = await r.json()
    assert body["count"] == 8
    assert len(body["logprobs"][0]) == 8
    for row, tot in zip(body["logprobs"], body["total"]):
        assert tot == pytest.approx(sum(row), abs=1e-3)
        assert all(lp <= 0.0 and math.isfinite(lp) for lp in row)
    r = await client.post("/v1/models/m:score", json={"tokens": [[5]]})
    assert r.status == 400
    await client.close()


async def test_score_text_mode_short_input_is_400(llama_engine):
    engine, _, _ = llama_engine
    app = server_lib.create_serving_app({"m": engine})
    client = TestClient(TestServer(app))
    await client.start_server()
    r = await client.post("/v1/models/m:score", json={"text": ""})
    assert r.status == 400
    assert "at least 2" in (await r.json())["error"]
    await client.close()
