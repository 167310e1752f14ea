"""Paged KV cache + radix prefix reuse.

Host-side bookkeeping (BlockPool / RadixPrefixCache) is unit-tested
directly; the device path is held to the same oracle as the rest of the
serving tier: `engine.generate` batch-1 greedy must match the paged
continuous path TOKEN-EXACTLY, for llama AND gemma, with the prefix
cache hitting, evicting under pool pressure, and copy-on-write
diverging — reuse is only a win if it is invisible in the tokens.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest_plugins = ("aiohttp.pytest_plugin",)

from kubeflow_tpu.models import gemma, llama
from kubeflow_tpu.ops import (
    dot_product_attention, paged_attention, paged_prefill_attention,
    pool_cell_shape,
)
from kubeflow_tpu.serving import (
    EngineConfig, GEMMA_FAMILY, InferenceEngine, LLAMA_FAMILY,
)
from kubeflow_tpu.serving.continuous import ContinuousBatcher, ContinuousEngine
from kubeflow_tpu.serving.paged import TRASH_BLOCK, BlockPool, RadixPrefixCache


# -- host-side bookkeeping (no jax) ----------------------------------------


def test_block_pool_alloc_free():
    pool = BlockPool(num_blocks=5, block_size=4)
    assert pool.capacity == 4 and pool.num_free == 4 and pool.in_use == 0
    got = pool.alloc(2)
    assert got == [1, 2]          # trash block 0 never handed out
    assert TRASH_BLOCK not in got
    assert pool.in_use == 2
    # over-ask is atomic: nothing taken, nothing lost
    assert pool.alloc(3) is None
    assert pool.num_free == 2
    pool.free(got)
    assert pool.num_free == 4
    assert pool.alloc(0) == []
    with pytest.raises(ValueError):
        pool.free([TRASH_BLOCK])
    with pytest.raises(ValueError):
        pool.free([5])
    with pytest.raises(ValueError):
        pool.alloc(-1)
    with pytest.raises(ValueError):
        BlockPool(num_blocks=1, block_size=4)


def test_radix_match_insert_partial_and_refs():
    pool = BlockPool(num_blocks=10, block_size=4)
    cache = RadixPrefixCache(pool)
    toks = list(range(8))
    b0, b1 = pool.alloc(2)
    adopted, held = cache.insert(toks, {0: b0, 1: b1})
    assert adopted == {0, 1} and held == []
    assert cache.cached_blocks == 2

    nodes, pnode, plen = cache.match(toks + [99])
    assert [n.block for n in nodes] == [b0, b1]
    assert pnode is None and plen == 0
    # diverging inside the second block: one full edge + a partial
    nodes, pnode, plen = cache.match([0, 1, 2, 3, 4, 5, 77, 88])
    assert [n.block for n in nodes] == [b0]
    assert pnode is not None and pnode.block == b1 and plen == 2
    # no match at all
    nodes, pnode, plen = cache.match([42, 43, 44, 45])
    assert nodes == [] and pnode is None

    # re-inserting the same path adopts nothing (duplicate blocks stay
    # with the caller, who must free them)
    dup = pool.alloc(2)
    adopted, _ = cache.insert(toks, dict(enumerate(dup)))
    assert adopted == set()
    pool.free(dup)

    # referenced nodes are eviction-proof
    nodes, _, _ = cache.match(toks)
    cache.ref(nodes)
    assert cache.evict(2) == 0
    cache.unref(nodes)
    # leaves only: one evict() pass can reach both (leaf, then its
    # newly-leafed parent)
    assert cache.evict(2) == 2
    assert cache.cached_blocks == 0
    assert pool.in_use == 0


def test_radix_lru_eviction_order_and_clear():
    pool = BlockPool(num_blocks=10, block_size=2)
    cache = RadixPrefixCache(pool)
    (a,) = pool.alloc(1)
    (b,) = pool.alloc(1)
    cache.insert([1, 2], {0: a})
    cache.insert([3, 4], {0: b})
    cache.match([1, 2])  # touch a: b becomes LRU
    assert cache.evict(1) == 1
    nodes, _, _ = cache.match([1, 2])
    assert [n.block for n in nodes] == [a]  # a survived
    assert cache.match([3, 4])[0] == []     # b evicted

    (c,) = pool.alloc(1)
    cache.insert([1, 2, 5, 6], {1: c})
    assert cache.cached_blocks == 2
    cache.clear()
    assert cache.cached_blocks == 0 and pool.in_use == 0
    assert cache.match([1, 2])[0] == []


def test_insert_hold_protects_inflight_blocks():
    pool = BlockPool(num_blocks=6, block_size=2)
    cache = RadixPrefixCache(pool)
    (a,) = pool.alloc(1)
    _, held = cache.insert([7, 8], {0: a}, hold=True)
    assert len(held) == 1 and held[0].refs == 1
    assert cache.evict(1) == 0   # held by the admitting request
    cache.unref(held)
    assert cache.evict(1) == 1


# -- ops-level: paged gather is bit-identical to the dense layout ----------


def test_paged_attention_matches_dense_layout():
    """Same tokens, same logical cells — the paged pool scatters the
    blocks physically (shuffled ids), the dense cache is contiguous.
    The attention outputs must be BITWISE equal."""
    rng = np.random.default_rng(0)
    b, n_q, n_kv, hd, bs, mb = 2, 4, 2, 8, 4, 3
    width = mb * bs
    lens = [9, 5]
    q = jnp.asarray(rng.standard_normal((b, 1, n_q, hd)), jnp.float32)
    dense_k = np.zeros((b, width, n_kv, hd), np.float32)
    dense_v = np.zeros((b, width, n_kv, hd), np.float32)
    num_blocks = 1 + b * mb
    k_pool = np.asarray(rng.standard_normal(
        (num_blocks, bs, n_kv, hd)), np.float32)  # trash holds garbage
    v_pool = np.asarray(rng.standard_normal(
        (num_blocks, bs, n_kv, hd)), np.float32)
    phys = rng.permutation(np.arange(1, num_blocks))
    table = phys.reshape(b, mb)
    for r in range(b):
        for j in range(mb):
            dense_k[r, j * bs:(j + 1) * bs] = k_pool[table[r, j]]
            dense_v[r, j * bs:(j + 1) * bs] = v_pool[table[r, j]]
    q_pos = jnp.asarray([[n - 1] for n in lens], jnp.int32)
    kv_pos = jnp.tile(jnp.arange(width, dtype=jnp.int32)[None], (b, 1))
    kv_mask = kv_pos < jnp.asarray([[n] for n in lens], jnp.int32)

    want = dot_product_attention(
        q, jnp.asarray(dense_k), jnp.asarray(dense_v), q_pos, kv_pos,
        causal=True, kv_mask=kv_mask)
    got = paged_attention(
        q, jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(table, jnp.int32), q_pos, kv_pos,
        causal=True, kv_mask=kv_mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_pool_cell_is_whole_lane_tiles():
    """Heads the paged kernels copy lie a head a row; any other size
    lies side by side in one row, so no 128-lane tile is part empty."""
    assert pool_cell_shape(8, 128) == (8, 128)      # Mistral
    assert pool_cell_shape(8, 256) == (8, 256)
    assert pool_cell_shape(8, 64) == (1, 512)       # granite
    assert pool_cell_shape(2, 32) == (1, 64)        # the tiny models
    assert pool_cell_shape(1, 192) == (1, 192)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("hd", [16, 64])
@pytest.mark.parametrize("entry", ["decode", "prefill"])
def test_paged_entries_take_either_form_of_a_cell(entry, hd, impl):
    """A pool `[L, nb, bs, n_kv, hd]` and the same bytes as
    `[L, nb, bs, 1, n_kv * hd]` are one pool to both paged entries: the
    outputs are bit-identical, and so are the pools an append returns
    (each in the form it was given)."""
    rng = np.random.default_rng(hd)
    layers, b, n_q, n_kv, bs, mb, s = 2, 2, 4, 2, 4, 3, 3
    width, num_blocks = mb * bs, 1 + b * mb
    split = (layers, num_blocks, bs, n_kv, hd)
    merged = (layers, num_blocks, bs, 1, n_kv * hd)
    k_pool = jnp.asarray(rng.standard_normal(split), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal(split), jnp.float32)
    table = jnp.asarray(
        rng.permutation(np.arange(1, num_blocks)).reshape(b, mb), jnp.int32)
    cursors = jnp.asarray([7, 2], jnp.int32)
    layer = jnp.int32(1)
    q, kn, vn = (jnp.asarray(rng.standard_normal(
        (b, 1 if entry == "decode" else s, n, hd)), jnp.float32)
        for n in (n_q, n_kv, n_kv))
    kv_pos = jnp.tile(jnp.arange(width, dtype=jnp.int32)[None], (b, 1))

    def run(form):
        kp, vp = k_pool.reshape(form), v_pool.reshape(form)
        if entry == "decode":
            return (paged_attention(
                q, kp, vp, table, cursors[:, None], kv_pos, causal=True,
                layer=layer, impl=impl, interpret=True),)
        return paged_prefill_attention(
            q, kn, vn, kp, vp, table, cursors, jnp.asarray([s, 1]),
            layer=layer, impl=impl, interpret=True)

    want, got = run(split), run(merged)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w in zip(got[1:], want[1:]):
        assert g.shape == merged and w.shape == split
        np.testing.assert_array_equal(
            np.asarray(g).reshape(split), np.asarray(w))
    if entry == "prefill":
        # and the append did write: layer 1 differs from what it was
        assert not np.array_equal(np.asarray(want[1][1]),
                                  np.asarray(k_pool[1]))
        np.testing.assert_array_equal(np.asarray(want[1][0]),
                                      np.asarray(k_pool[0]))


def test_a_cell_that_holds_no_whole_heads_is_refused():
    q = jnp.zeros((1, 1, 4, 16), jnp.float32)
    pool = jnp.zeros((1, 3, 4, 1, 24), jnp.float32)
    with pytest.raises(ValueError, match="whole heads of 16"):
        paged_attention(q, pool, pool, jnp.zeros((1, 2), jnp.int32),
                        jnp.zeros((1, 1), jnp.int32),
                        jnp.zeros((1, 8), jnp.int32), layer=0)


def test_continuous_engine_block_validation():
    engine, _ = _llama_engine()
    with pytest.raises(ValueError):
        ContinuousEngine(engine, max_slots=2, block_size=6)  # not pow2
    with pytest.raises(ValueError):
        # pool smaller than one slot's table can never admit anything
        ContinuousEngine(engine, max_slots=2, block_size=8, num_blocks=8)


# -- device path vs the dense oracle ---------------------------------------


def _llama_engine(eos=None, max_len=64):
    cfg = llama.LLAMA_TINY
    params = dict(llama.init(jax.random.key(0), cfg))
    params["lm_head"] = params["lm_head"] * 50.0  # argmax can't flip
    return InferenceEngine(
        params, cfg, LLAMA_FAMILY,
        EngineConfig(max_len=max_len, eos_token=eos)), cfg


def _solo(engine, prompt, max_new):
    return np.asarray(engine.generate(
        jnp.asarray([prompt], jnp.int32), max_new=max_new))[0].tolist()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters
    (scan and cond bodies, calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("program", ["_decode_one", "_paged_forward"])
def test_step_programs_take_no_layer_slice_of_the_pool(program, impl):
    """The pool `[L, num_blocks, block_size, n_kv, hd]` is the layer
    scan's carry, and the attention ops take it whole with the layer's
    index. A layer's slice taken out of it (or put back into it) is a
    copy of the whole layer on the chip — 47 % of a decode step and
    59 % of a prefill slice once (PERF.md, PR 26)."""
    engine, cfg = _llama_engine()
    ce = ContinuousEngine(engine, max_slots=2, block_size=8,
                          paged_attention_impl=impl)
    st = ce.init_slots()
    rng = jax.random.key(0)
    if program == "_decode_one":
        sp = engine._resolve_sampling(
            np.zeros(2, np.float32), np.zeros(2, np.int64),
            np.ones(2, np.float32), rng, batch=2)[0]
        jaxpr = jax.make_jaxpr(lambda st: ce._decode_one(
            engine.params, None, st, sp, rng))(st)
    else:
        jaxpr = jax.make_jaxpr(lambda st: ce._paged_forward(
            engine.params, None, st, jnp.asarray([1]),
            jnp.zeros((1, 4), jnp.int32), jnp.asarray([4]),
            jnp.asarray([0])))(st)

    # a layer of the pool in the form it is kept in, or a head a row as
    # the kernel is handed it
    assert st.k.shape[1:] == (ce.num_blocks, 8, 1,
                              cfg.num_kv_heads * cfg.head_dim)
    forms = (st.k.shape[1:],
             st.k.shape[1:3] + (cfg.num_kv_heads, cfg.head_dim))

    def a_layer(var):
        shape = var.aval.shape
        return (shape[-4:] in forms
                and int(np.prod(shape[:-4])) == 1)

    names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert "scan" in names and (impl == "xla" or "pallas_call" in names)
    moved = [str(e) for e in _eqns(jaxpr.jaxpr)
             if (e.primitive.name == "dynamic_slice"
                 and a_layer(e.outvars[0]))
             or (e.primitive.name == "dynamic_update_slice"
                 and a_layer(e.invars[1]))]
    assert not moved, moved


@pytest.mark.slow
async def test_paged_parity_and_prefix_reuse_llama():
    """The tentpole contract end-to-end: repeated and prefix-sharing
    prompts through the paged batcher decode EXACTLY their solo dense
    continuations, while the radix cache demonstrably reuses blocks."""
    engine, cfg = _llama_engine()
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=4,
                                kv_block_size=8)
    gen = np.random.default_rng(5)
    a = gen.integers(0, cfg.vocab_size, 24).tolist()
    div = a[:20] + gen.integers(0, cfg.vocab_size, 4).tolist()  # CoW
    fresh = gen.integers(0, cfg.vocab_size, 12).tolist()

    assert await batcher.submit(a, 6, ()) == _solo(engine, a, 6)
    s0 = batcher.prefix_cache_stats()
    assert s0["misses"] >= 1 and s0["cached_blocks"] > 0

    # same prompt again: near-total reuse (all but the last token)
    assert await batcher.submit(a, 6, ()) == _solo(engine, a, 6)
    s1 = batcher.prefix_cache_stats()
    assert s1["hits"] == s0["hits"] + 1
    assert s1["tokens_reused"] >= s0["tokens_reused"] + 23

    # shared 20-token prefix diverging mid-block: CoW must not corrupt
    # the donor blocks — and the donor prompt must still replay clean
    assert await batcher.submit(div, 6, ()) == _solo(engine, div, 6)
    s2 = batcher.prefix_cache_stats()
    assert s2["hits"] == s1["hits"] + 1
    assert s2["tokens_reused"] >= s1["tokens_reused"] + 20
    assert await batcher.submit(a, 6, ()) == _solo(engine, a, 6)

    # unrelated prompt: a miss, not a false hit
    assert await batcher.submit(fresh, 6, ()) == _solo(engine, fresh, 6)
    s3 = batcher.prefix_cache_stats()
    assert s3["misses"] >= s0["misses"] + 1

    # accounting closes: with no active requests every in-use block is
    # owned by the radix tree, before and after shutdown (close releases
    # request-held blocks; the tree keeps its cache)
    assert batcher.kv_blocks_in_use() == s3["cached_blocks"]
    await batcher.close()
    assert batcher.cengine.pool.in_use == batcher._radix.cached_blocks
    batcher._radix.clear()
    assert batcher.cengine.pool.in_use == 0


@pytest.mark.slow
async def test_paged_parity_gemma():
    """Same contract on the second model family (GQA 8q/1kv shapes and
    sliding-window-capable attention take different code paths)."""
    cfg = gemma.GEMMA_TINY
    engine = InferenceEngine(
        gemma.init(jax.random.key(1), cfg), cfg, GEMMA_FAMILY,
        EngineConfig(max_len=64))
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                kv_block_size=8)
    gen = np.random.default_rng(9)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (7, 15)]
    want = [_solo(engine, p, 5) for p in prompts]
    got = await asyncio.gather(
        *(batcher.submit(p, 5, ()) for p in prompts))
    assert list(got) == want
    # repeat: the paged cache must hit AND stay token-exact
    assert await batcher.submit(prompts[1], 5, ()) == want[1]
    assert batcher.prefix_cache_stats()["hits"] >= 1
    await batcher.close()


@pytest.mark.slow
async def test_paged_parity_under_speculative_engine():
    """Greedy outputs must agree three ways: dense generate, the
    speculative engine over the same target, and the paged continuous
    batcher — the paged cache must be invisible to all of them."""
    from kubeflow_tpu.serving.speculative import SpeculativeEngine

    engine, cfg = _llama_engine(max_len=96)
    dcfg = dataclasses.replace(
        llama.LLAMA_TINY, num_layers=1, hidden_size=64,
        intermediate_size=192, num_heads=2, num_kv_heads=1)
    draft = InferenceEngine(
        llama.init(jax.random.key(99), dcfg), dcfg, LLAMA_FAMILY,
        EngineConfig(max_len=96))
    spec = SpeculativeEngine(engine, draft)

    prompt = np.random.default_rng(3).integers(
        0, cfg.vocab_size, 10).tolist()
    want = _solo(engine, prompt, 12)
    spec_got, _ = spec.generate(
        jnp.asarray([prompt], jnp.int32), max_new=12, gamma=3)
    assert np.asarray(spec_got)[0].tolist() == want

    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                kv_block_size=8)
    assert await batcher.submit(prompt, 12, ()) == want
    assert await batcher.submit(prompt, 12, ()) == want  # cache hit path
    assert batcher.prefix_cache_stats()["hits"] >= 1
    await batcher.close()


@pytest.mark.slow
async def test_radix_eviction_under_pool_pressure():
    """A pool sized to ONE slot's table: every admission must evict the
    previous prompt's refcount-0 blocks to make room, and the tokens
    must stay exact throughout (eviction is a memory event, never a
    correctness event)."""
    engine, cfg = _llama_engine()
    # max_len=64 / bs=8 -> 8 blocks per table; capacity 8 == one slot
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                kv_block_size=8, kv_pool_blocks=9)
    cap = batcher.cengine.pool.capacity
    gen = np.random.default_rng(11)
    prompts = [gen.integers(0, cfg.vocab_size, 40).tolist()
               for _ in range(3)]
    for p in prompts:  # serial: each needs 6 blocks, pool holds 8
        assert await batcher.submit(p, 8, ()) == _solo(engine, p, 8)
        assert batcher.cengine.pool.in_use <= cap
    stats = batcher.prefix_cache_stats()
    assert stats["cached_blocks"] <= cap
    # repeating the LAST prompt can still hit whatever survived; the
    # FIRST was necessarily evicted, so it must miss — and both decode
    # exactly
    assert await batcher.submit(prompts[0], 8, ()) == \
        _solo(engine, prompts[0], 8)
    assert await batcher.submit(prompts[0], 8, ()) == \
        _solo(engine, prompts[0], 8)
    assert batcher.prefix_cache_stats()["hits"] >= 1
    await batcher.close()
    # post-shutdown the only blocks in use are the tree's cache
    assert batcher.cengine.pool.in_use == batcher._radix.cached_blocks


# -- migration-hardening guards --------------------------------------------


def test_block_pool_double_free_guard():
    """Freeing a block twice is always an accounting bug (migration
    rollback + radix donation both free; overlapping would corrupt the
    free list into handing one block to two sequences) — the pool must
    refuse loudly, not absorb it."""
    pool = BlockPool(num_blocks=6, block_size=4)
    got = pool.alloc(3)
    pool.free(got[:1])
    with pytest.raises(ValueError, match="double-free"):
        pool.free(got[:1])
    # a duplicate id inside ONE call hits the same guard
    with pytest.raises(ValueError, match="double-free"):
        pool.free([got[1], got[1]])
    # freeing a block the pool never handed out is a double-free too
    fresh = BlockPool(num_blocks=6, block_size=4)
    with pytest.raises(ValueError, match="double-free"):
        fresh.free([2])


def test_import_blocks_geometry_guard_and_roundtrip():
    """Foreign block payloads scatter into the pool only when their
    shape matches the local geometry exactly; a mismatched import must
    raise before touching the device. Matching payloads round-trip
    export -> import -> export bitwise."""
    engine, cfg = _llama_engine()
    ce = ContinuousEngine(engine, max_slots=2, block_size=8)
    st = ce.init_slots()
    rng = np.random.default_rng(3)
    shape = (cfg.num_layers, 2, 8, cfg.num_kv_heads, cfg.head_dim)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    st = ce.import_blocks(st, [1, 2], k, v)
    got_k, got_v = ce.export_blocks(st, [1, 2])
    np.testing.assert_array_equal(got_k, k)
    np.testing.assert_array_equal(got_v, v)
    # payload from a pool with a different block size
    with pytest.raises(ValueError, match="pool block geometry"):
        ce.import_blocks(st, [1, 2], k[:, :, :4], v[:, :, :4])
    # block-count mismatch between ids and payload
    with pytest.raises(ValueError, match="pool block geometry"):
        ce.import_blocks(st, [1], k, v)


def test_blocks_leave_and_enter_a_merged_pool_in_the_wire_form():
    """The tiny model's heads of 32 keep a pool cell as one row of
    `n_kv * hd`; blocks still leave and enter as `[L, n, bs, n_kv, hd]`
    (what migration, the spill tier and a peer of another build hold),
    land in the cells the programs read, and a payload in the pool's
    own form is another geometry: refused."""
    engine, cfg = _llama_engine()
    ce = ContinuousEngine(engine, max_slots=2, block_size=8)
    n_kv, hd = cfg.num_kv_heads, cfg.head_dim
    assert ce.kv_cell == (1, n_kv * hd)
    st = ce.init_slots()
    assert st.k.shape == st.v.shape == (
        cfg.num_layers, ce.num_blocks, 8, 1, n_kv * hd)
    rng = np.random.default_rng(4)
    wire = (cfg.num_layers, 2, 8, n_kv, hd)
    k = rng.standard_normal(wire).astype(np.float32)
    v = rng.standard_normal(wire).astype(np.float32)
    st = ce.import_blocks(st, [3, 1], k, v)
    assert st.k.shape == (cfg.num_layers, ce.num_blocks, 8, 1, n_kv * hd)
    # head h of a token lies at lanes [h * hd, (h + 1) * hd) of its cell
    np.testing.assert_array_equal(
        np.asarray(st.k[:, 3, :, 0, hd:2 * hd]), k[:, 0, :, 1])
    np.testing.assert_array_equal(
        np.asarray(st.v[:, 1, :, 0, :hd]), v[:, 1, :, 0])
    assert not np.asarray(st.k[:, 2]).any()         # untouched
    got_k, got_v = ce.export_blocks(st, [3, 1])
    assert got_k.shape == got_v.shape == wire
    np.testing.assert_array_equal(got_k, k)
    np.testing.assert_array_equal(got_v, v)
    merged = (cfg.num_layers, 2, 8, 1, n_kv * hd)
    with pytest.raises(ValueError, match="pool block geometry"):
        ce.import_blocks(st, [3, 1], k.reshape(merged), v.reshape(merged))
    with pytest.raises(ValueError, match="pool block geometry"):
        ce.import_blocks(st, [3, 1], k[..., :hd // 2], v[..., :hd // 2])
