"""Chunked prefill: token parity and composition with every
continuous-path feature.

`prefill_chunk_tokens=N` changes WHEN prompt tokens are fed (budget
slices interleaved with decode chunks, through the fused append path)
but must never change WHAT any request receives: every test here pins
bit-exact parity against the solo-generate oracle — across budgets (1 token per iteration up to >= the whole
prompt in one slice), model families, radix prefix reuse, tenancy
preemption, and mid-flight migration export.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest_plugins = ("aiohttp.pytest_plugin",)

from kubeflow_tpu.models import gemma, llama
from kubeflow_tpu.serving import (
    EngineConfig,
    GEMMA_FAMILY,
    InferenceEngine,
    LLAMA_FAMILY,
)
from kubeflow_tpu.serving.continuous import ContinuousBatcher, MigratedAway
from kubeflow_tpu.tenancy import config_from_dict

BS = 8


def _build_engine(family="llama", max_len=96):
    if family == "llama":
        cfg = llama.LLAMA_TINY
        params = dict(llama.init(jax.random.key(0), cfg))
        params["lm_head"] = params["lm_head"] * 50.0  # argmax can't flip
        return InferenceEngine(params, cfg, LLAMA_FAMILY,
                               EngineConfig(max_len=max_len)), cfg
    cfg = gemma.GEMMA_TINY
    params = dict(gemma.init(jax.random.key(1), cfg))
    if "lm_head" in params:  # gemma ties its embeddings
        params["lm_head"] = params["lm_head"] * 50.0
    return InferenceEngine(params, cfg, GEMMA_FAMILY,
                           EngineConfig(max_len=max_len)), cfg


@pytest.fixture(scope="module")
def llama_engine():
    return _build_engine("llama")


def _solo(engine, prompt, max_new):
    return np.asarray(engine.generate(
        jnp.asarray([prompt], jnp.int32), max_new=max_new))[0].tolist()


def _batcher(engine, budget=None, **kw):
    if budget is not None:
        kw["prefill_chunk_tokens"] = budget
    return ContinuousBatcher(engine, asyncio.Lock(), max_slots=4,
                             kv_block_size=BS, **kw)


async def _run_all(batcher, prompts, max_new):
    try:
        out = await asyncio.gather(
            *(batcher.submit(p, max_new, ()) for p in prompts))
        return [list(o) for o in out]
    finally:
        await batcher.close()


@pytest.mark.parametrize("budget", [1, 3, 64, None])
async def test_chunked_parity_across_budgets_llama(llama_engine, budget):
    """Budget 1 (one token per worker iteration — the most interleaved
    schedule possible), a mid-size budget that straddles block
    boundaries, a budget >= every prompt (one slice) and the budget
    left unset (the default, clamped to this engine's cache width) all
    emit the oracle's exact tokens."""
    engine, cfg = llama_engine
    gen = np.random.default_rng(4)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (4, 7, 12, 20)]
    want = [_solo(engine, p, 5) for p in prompts]
    b = _batcher(engine, budget)
    if budget is None:
        assert b.prefill_chunk_tokens == b.cengine.kv_width == 96
    assert await _run_all(b, prompts, 5) == want


@pytest.mark.slow
async def test_chunked_parity_gemma():
    """The other family: GQA 4:1, different norm/rope plumbing — the
    fused append path must track it through the same config."""
    engine, cfg = _build_engine("gemma")
    gen = np.random.default_rng(9)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (7, 11, 17)]
    want = [_solo(engine, p, 5) for p in prompts]
    for budget in (1, 4, 32):
        got = await _run_all(_batcher(engine, budget), prompts, 5)
        assert got == want, f"budget={budget}"


async def test_chunked_radix_reuse(llama_engine):
    """A request seeds from the radix cache: the second identical
    prompt re-prefills only the
    uncached tail, token-identically."""
    engine, cfg = llama_engine
    prompt = list(range(2, 2 + 21))
    want = _solo(engine, prompt, 5)
    b = _batcher(engine, budget=4)
    try:
        assert await b.submit(prompt, 5, ()) == want
        fed_first = b.tokens_prefilled
        assert await b.submit(prompt, 5, ()) == want
        assert b.prefix_hits == 1
        # blocks donated at retirement cover the prompt's full blocks;
        # the rerun computes at most the partial tail + 1
        assert b.tokens_reused >= (len(prompt) // BS) * BS
        assert b.tokens_prefilled - fed_first < fed_first
    finally:
        await b.close()


async def test_chunked_interleaves_decode_with_prefill(llama_engine):
    """The throughput mechanism itself: while a LONG prompt trickles
    in at budget 1, a short already-running request keeps emitting —
    its stream finishes well before the long prompt's first token.
    (A whole-prompt prefill would stall the short request for its
    length.)"""
    engine, cfg = llama_engine
    gen = np.random.default_rng(11)
    short = gen.integers(0, cfg.vocab_size, 4).tolist()
    long = gen.integers(0, cfg.vocab_size, 60).tolist()
    want_s, want_l = _solo(engine, short, 8), _solo(engine, long, 4)
    b = _batcher(engine, budget=1)
    try:
        fut_s, q = b.open_stream(short, 8, ())
        # wait until the short request is admitted and decoding
        first = await asyncio.wait_for(q.get(), 30)
        assert first is not None
        fut_l = asyncio.ensure_future(b.submit(long, 4, ()))
        # the short request's remaining tokens arrive while the long
        # prompt is still mid-prefill (60 iterations at budget 1)
        got_s = [first]
        while True:
            tok = await asyncio.wait_for(q.get(), 30)
            if tok is None:
                break
            got_s.append(tok)
        assert got_s == want_s
        assert any(r.prefilling is not None
                   for r in b._active.values()), \
            "long prompt should still be mid-prefill"
        assert await fut_l == want_l
        await fut_s
    finally:
        await b.close()


async def test_chunked_preemption_replay(llama_engine):
    """Tenancy preemption composes: bulk requests admitted through the
    chunked path preempt and replay token-identically."""
    engine, _ = llama_engine
    qos = {"tenants": {"live": {"priority": "interactive"},
                       "bulk": {"priority": "batch"}}}
    p1, p2, p3 = [3, 5, 7, 11], [4, 6, 8, 10], [9, 2, 4, 8]
    want1, want2 = _solo(engine, p1, 80), _solo(engine, p2, 80)
    want3 = _solo(engine, p3, 8)
    b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                          kv_block_size=BS, prefill_chunk_tokens=2,
                          tenancy=config_from_dict(qos))
    try:
        # long budgets keep both bulks busy well past the live
        # arrival — the preemption window cannot close underneath the
        # test (a victim mid-chunked-prefill is skipped; one that has
        # finished prefilling is fair game)
        f1 = asyncio.ensure_future(
            b.submit(p1, 80, (("tenant", "bulk"),)))
        f2 = asyncio.ensure_future(
            b.submit(p2, 80, (("tenant", "bulk"),)))
        for _ in range(400):
            if len(b._active) == 2 and all(
                    r.prefilling is None for r in b._active.values()):
                break
            await asyncio.sleep(0.02)
        assert len(b._active) == 2
        got3 = await b.submit(p3, 8, (("tenant", "live"),))
        assert b.preemptions >= 1
        assert await f1 == want1
        assert await f2 == want2
        assert got3 == want3
    finally:
        await b.close()


async def test_chunked_migration_export_mid_prefill(llama_engine):
    """Export while a request is STILL mid-chunked-prefill: its blocks
    past the fed frontier are unwritten, so the record must go out
    tokens-only and replay from scratch on the peer, token-exactly."""
    engine, cfg = llama_engine
    gen = np.random.default_rng(13)
    prompt = gen.integers(0, cfg.vocab_size, 40).tolist()
    want = _solo(engine, prompt, 6)
    a = _batcher(engine, budget=1)
    fut = asyncio.ensure_future(a.submit(prompt, 6, ()))
    try:
        for _ in range(400):  # wait for mid-prefill adoption
            if any(r.prefilling is not None
                   for r in a._active.values()):
                break
            await asyncio.sleep(0.01)
        records = await a.export_sequences()
        with pytest.raises(MigratedAway):
            await fut
    finally:
        await a.close()
    assert len(records) == 1
    rec = records[0]
    assert rec["kv"] is None and rec["out"] == []
    bb = _batcher(engine, budget=4)
    try:
        await bb.import_sequence(rec)
        got = await bb.submit(rec["tokens"], rec["max_new"], ())
        assert got == want
    finally:
        await bb.close()


async def test_chunked_migration_mid_generation(llama_engine):
    """The standard migrate point — mid-generation, past a block
    boundary — with chunked admission on BOTH replicas."""
    engine, _ = llama_engine
    prompt = [3, 5, 7, 11, 13, 17]
    want = _solo(engine, prompt, 24)
    a = _batcher(engine, budget=3)
    fut, q = a.open_stream(prompt, 24, ())
    try:
        for _ in range(11):
            tok = await asyncio.wait_for(q.get(), 30)
            assert tok is not None
        records = await a.export_sequences()
        with pytest.raises(MigratedAway):
            await fut
    finally:
        await a.close()
    (rec,) = records
    assert rec["kv"] is not None and rec["kv"]["n_full"] >= 2
    bb = _batcher(engine, budget=3)
    try:
        assert await bb.import_sequence(rec) == rec["kv"]["n_full"]
        out = await bb.submit(rec["tokens"],
                              rec["max_new"] - len(rec["out"]), ())
        assert rec["out"] + out == want
        assert bb.prefix_hits >= 1  # the resume seeded from the import
    finally:
        await bb.close()


def test_knob_validation(llama_engine):
    engine, _ = llama_engine
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                          prefill_chunk_tokens=0)
    from kubeflow_tpu.serving.server import create_serving_app
    with pytest.raises(ValueError, match="require continuous"):
        create_serving_app({"m": engine}, prefill_chunk_tokens=4)
    with pytest.raises(TypeError, match="prefill_chunk"):
        ContinuousBatcher(engine, asyncio.Lock(), prefill_chunk=8)
    with pytest.raises(TypeError, match="prefill_chunk"):
        create_serving_app({"m": engine}, continuous=True,
                           prefill_chunk=8)
