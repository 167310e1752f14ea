"""Multi-LoRA serving: N adapters resident over one base model.

Oracle: an engine built from `merge_lora`-folded params — the unmerged
low-rank path (base matmul + per-row delta) must produce the same
greedy tokens. Head sharpened (*50) for argmax stability across batch
compositions, as everywhere in the serving tests.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

pytest_plugins = ("aiohttp.pytest_plugin",)

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.rotary import apply_rope
from kubeflow_tpu.serving import (
    EngineConfig, InferenceEngine, LLAMA_FAMILY, build_pack,
)
from kubeflow_tpu.serving import engine as engine_lib
from kubeflow_tpu.serving import server as server_lib
from kubeflow_tpu.serving.continuous import ContinuousBatcher
from kubeflow_tpu.serving.quant import qdot, quantize_blocks
from kubeflow_tpu.train.lora import LoraConfig, init_lora, merge_lora

CFG = llama.LLAMA_TINY
LCFG = LoraConfig(rank=4)


def _adapter(seed: int):
    """A LoRA tree with non-zero B (fresh init has B=0 = identity)."""
    ad = init_lora(jax.random.key(seed), CFG, LCFG)
    ad["blocks"] = {
        t: {"A": ab["A"],
            "B": jax.random.normal(
                jax.random.key(seed + 99), ab["B"].shape) * 0.05}
        for t, ab in ad["blocks"].items()}
    return ad


@pytest.fixture(scope="module")
def setup():
    params = dict(llama.init(jax.random.key(0), CFG))
    params["lm_head"] = params["lm_head"] * 50.0
    adapters = {"alice": _adapter(1), "bob": _adapter(2)}
    pack = build_pack(CFG, LCFG, adapters)
    engine = InferenceEngine(params, CFG, LLAMA_FAMILY,
                             EngineConfig(max_len=64), adapter_pack=pack)
    return engine, params, adapters


def _merged_solo(params, adapters, name, prompt, max_new):
    merged = InferenceEngine(
        merge_lora(params, adapters[name], LCFG), CFG, LLAMA_FAMILY,
        EngineConfig(max_len=64))
    return np.asarray(merged.generate(
        jnp.asarray([prompt], jnp.int32), max_new=max_new))[0].tolist()


def test_adapter_generate_matches_merged_oracle(setup):
    engine, params, adapters = setup
    p = np.random.default_rng(0).integers(0, CFG.vocab_size, 6).tolist()
    arr = jnp.asarray([p], jnp.int32)
    base = np.asarray(engine.generate(arr, max_new=5))[0].tolist()
    for name in ("alice", "bob"):
        got = np.asarray(engine.generate(
            arr, max_new=5, adapter=name))[0].tolist()
        assert got == _merged_solo(params, adapters, name, p, 5)
        assert got != base  # the adapters actually change the model
    # '' selects the reserved zero adapter == plain base, same program
    assert np.asarray(engine.generate(
        arr, max_new=5, adapter=""))[0].tolist() == base


@pytest.mark.slow
def test_mixed_adapter_rows_in_one_batch(setup):
    engine, params, adapters = setup
    p = np.random.default_rng(1).integers(0, CFG.vocab_size, 5).tolist()
    arr = jnp.asarray([p, p, p], jnp.int32)
    got = np.asarray(engine.generate(
        arr, max_new=5, adapter=["", "alice", "bob"]))
    base = np.asarray(engine.generate(
        jnp.asarray([p], jnp.int32), max_new=5))[0]
    np.testing.assert_array_equal(got[0], base)
    assert got[1].tolist() == _merged_solo(params, adapters, "alice", p, 5)
    assert got[2].tolist() == _merged_solo(params, adapters, "bob", p, 5)


def _block_with_unguarded_head_split(cfg, fam, p, x, rope_positions,
                                     inv_freq, write_kv, attn, proj=None):
    """`engine.transformer_block` as it stood while the head split sat
    on the product itself, `(h @ w).reshape(heads)`, written out: the
    yardstick for what the block computes whichever way a projection
    is made."""
    if proj is None:
        def proj(name, h, w):
            return qdot(h, w, cfg.dtype)
    b, s = x.shape[:2]
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q = proj("wq", h, p["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = proj("wk", h, p["wk"]).reshape(
        b, s, cfg.num_kv_heads, cfg.head_dim)
    v = proj("wv", h, p["wv"]).reshape(
        b, s, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, rope_positions, inv_freq)
    k = apply_rope(k, rope_positions, inv_freq)
    k_cache, v_cache = write_kv(k, v)
    out = attn(q, k_cache, v_cache)
    x = x + proj("wo", out.reshape(b, s, cfg.q_dim), p["wo"])
    h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    gate = fam.gate_act(proj("w_gate", h, p["w_gate"]))
    x = x + proj("w_down", gate * proj("w_up", h, p["w_up"]), p["w_down"])
    return x, (k_cache, v_cache)


@pytest.mark.parametrize("how", ["plain", "lora-base-row",
                                 "lora-adapter-row", "int8"])
def test_logits_are_the_unguarded_head_splits_bit_for_bit(
        setup, monkeypatch, how):
    """The block keeps the head split's layout off the stacked weights
    (a barrier between product and reshape, tests/test_tpu_compile.py
    reads the effect): that must change no bit of the logits, for a
    plain product, `lora_proj`'s base plus delta with the reserved id
    0 and with an adapter's, and a `QTensor`'s product times its
    scale."""
    engine, params, _ = setup
    kwargs = {}
    if how == "int8":
        params = quantize_blocks(params)
    elif how != "plain":
        aid = 1 if how == "lora-adapter-row" else 0
        kwargs = dict(adapters=engine.adapter_pack.blocks,
                      adapter_ids=jnp.full((2,), aid, jnp.int32))
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, CFG.vocab_size, (2, 7)), jnp.int32)

    def logits():
        return np.asarray(jax.jit(lambda p, t: engine._forward_cached(
            p, t, engine.init_state(2), return_all=True,
            **kwargs)[0])(params, tokens))

    got = logits()
    monkeypatch.setattr(engine_lib, "transformer_block",
                        _block_with_unguarded_head_split)
    np.testing.assert_array_equal(got, logits())


def test_adapter_validation(setup):
    engine, _, _ = setup
    p = jnp.asarray([[1, 2, 3]], jnp.int32)
    with pytest.raises(ValueError, match="unknown adapter"):
        engine.generate(p, max_new=2, adapter="carol")
    with pytest.raises(ValueError, match="3 adapter names"):
        engine.generate(p, max_new=2, adapter=["a", "b", "c"])
    bare = InferenceEngine(engine.params, CFG, LLAMA_FAMILY,
                           EngineConfig(max_len=64))
    with pytest.raises(ValueError, match="no adapter_pack"):
        bare.generate(p, max_new=2, adapter="alice")


def test_pack_shape_mismatch_rejected():
    a = _adapter(1)
    b = _adapter(2)
    b["blocks"]["wq"]["A"] = b["blocks"]["wq"]["A"][:, :, :2]  # rank 2
    with pytest.raises(ValueError, match="same rank"):
        build_pack(CFG, LCFG, {"a": a, "b": b})


@pytest.mark.slow
async def test_continuous_batcher_mixes_adapters_per_slot(setup):
    """The headline behavior: concurrent requests for DIFFERENT
    fine-tunes (and the plain base) share one slot batch, each decoding
    its own adapter's tokens at its own cursor."""
    engine, params, adapters = setup
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=4)
    gen = np.random.default_rng(2)
    pa = gen.integers(0, CFG.vocab_size, 4).tolist()
    pb = gen.integers(0, CFG.vocab_size, 9).tolist()
    pc = gen.integers(0, CFG.vocab_size, 6).tolist()
    want_a = _merged_solo(params, adapters, "alice", pa, 5)
    want_b = _merged_solo(params, adapters, "bob", pb, 5)
    want_c = np.asarray(engine.generate(
        jnp.asarray([pc], jnp.int32), max_new=5))[0].tolist()
    got_a, got_b, got_c = await asyncio.gather(
        batcher.submit(pa, 5, (("adapter", "alice"),)),
        batcher.submit(pb, 5, (("adapter", "bob"),)),
        batcher.submit(pc, 5, ()))
    assert got_a == want_a
    assert got_b == want_b
    assert got_c == want_c
    # slot reuse across adapters leaks nothing
    got_a2 = await batcher.submit(pb, 5, (("adapter", "alice"),))
    assert got_a2 == _merged_solo(params, adapters, "alice", pb, 5)
    with pytest.raises(ValueError, match="unknown adapter"):
        await batcher.submit(pa, 5, (("adapter", "carol"),))
    await batcher.close()


@pytest.mark.slow
async def test_rest_adapter_requests(setup):
    engine, params, adapters = setup
    app = server_lib.create_serving_app(
        {"m": engine}, continuous=True, max_batch=4)
    client = TestClient(TestServer(app))
    await client.start_server()
    p = np.random.default_rng(3).integers(0, CFG.vocab_size, 5).tolist()

    r = await client.get("/v1/models")
    card = (await r.json())["models"][0]
    assert card["adapters"] == ["alice", "bob"]

    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [p], "max_new": 4,
                                "adapter": "alice"})
    assert r.status == 200, await r.text()
    assert (await r.json())["tokens"][0] == _merged_solo(
        params, adapters, "alice", p, 4)

    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [p], "max_new": 4,
                                "adapter": "carol"})
    assert r.status == 400
    assert "unknown adapter" in (await r.json())["error"]

    r = await client.post("/v1/models/m:generate",
                          json={"tokens": [p], "max_new": 4,
                                "adapter": "bob", "speculative": True})
    assert r.status == 400
    await client.close()


@pytest.mark.slow
async def test_adapters_under_pipelined_depth2(setup):
    """Per-slot adapter ids must survive dispatch-ahead slot reuse: a
    freed slot re-admitted with a DIFFERENT adapter while a chunk is
    in flight must decode its own fine-tune, not its predecessor's."""
    engine, params, adapters = setup
    gen = np.random.default_rng(70)
    p1 = gen.integers(0, CFG.vocab_size, 5).tolist()
    p2 = gen.integers(0, CFG.vocab_size, 8).tolist()
    want_alice = _merged_solo(params, adapters, "alice", p1, 5)
    want_bob = _merged_solo(params, adapters, "bob", p2, 5)

    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=1,
                                chunk=2, pipeline_depth=2)
    # max_slots=1 forces serial slot reuse with chunks in flight
    got_alice = await batcher.submit(
        p1, 5, (("adapter", "alice"),))
    got_bob = await batcher.submit(
        p2, 5, (("adapter", "bob"),))
    assert got_alice == want_alice
    assert got_bob == want_bob
    await batcher.close()
