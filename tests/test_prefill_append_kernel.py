"""Fused prefill/append kernel vs the XLA scatter+gather oracle.

The kernel (ops/pallas/prefill_append.py) merges each row's s new
tokens into the paged KV pool THROUGH the block table in-kernel
(input_output_aliases) and attends them in the same pass;
`ops.paged_prefill_attention(impl="xla")` scatters the new cells with
`.at[].set` and gathers the full window. The two must agree — on the
attention output to fp32 tolerance AND on the pool contents
bit-for-bit — across GQA ratios, ragged cursors and lengths, sliding
windows, and radix-shared tables; and the continuous engine must emit
IDENTICAL tokens with either impl under chunked prefill.

Write disjointness is a precondition, not a tested behavior: each
row's write range [q_start, q_start + q_lens) must lie in blocks no
OTHER row's table references. The serving engine satisfies it by
construction (writes land in exclusively-owned fresh blocks; shared
radix blocks sit strictly below every sharer's cursor) — see
serving/paged.py.

All kernel runs here are interpret mode (CPU backend — see conftest).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops.attention import (
    impl_counts,
    paged_prefill_attention,
    resolve_paged_prefill_impl,
)
from kubeflow_tpu.ops.pallas.prefill_append import paged_prefill_append
from kubeflow_tpu.serving import EngineConfig, InferenceEngine, LLAMA_FAMILY
from kubeflow_tpu.serving.continuous import ContinuousBatcher, ContinuousEngine

TOL = dict(atol=1e-5, rtol=1e-5)


def _mk(seed, b=3, s=5, n_q=8, n_kv=2, hd=32, bs=8, nb=6,
        num_blocks=64, starts=None, lens=None):
    """Random pool + per-row table/cursor in the engine's layout:
    ragged cursors, chains of EXCLUSIVE blocks per row covering
    [0, start + s) (write-disjoint by construction), table tails
    trash-padded (block 0)."""
    rng = np.random.default_rng(seed)
    width = nb * bs
    q = jnp.asarray(rng.normal(size=(b, s, n_q, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, s, n_kv, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, s, n_kv, hd)), jnp.float32)
    kp = np.asarray(rng.normal(size=(num_blocks, bs, n_kv, hd)),
                    np.float32)
    vp = np.asarray(rng.normal(size=(num_blocks, bs, n_kv, hd)),
                    np.float32)
    kp[0] = vp[0] = 0.0  # the trash block holds no real tokens
    if starts is None:
        starts = rng.integers(0, width - s + 1, size=(b,))
    starts = np.asarray(starts, np.int32)
    if lens is None:
        lens = np.full((b,), s, np.int32)
    lens = np.asarray(lens, np.int32)
    table = np.zeros((b, nb), np.int32)
    used = {0}
    for i in range(b):
        need = -(-int(starts[i] + s) // bs) if starts[i] + s else 1
        for j in range(max(need, 1)):
            blk = int(rng.choice([x for x in range(1, num_blocks)
                                  if x not in used]))
            used.add(blk)
            table[i, j] = blk
    return (q, kn, vn, jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(starts), jnp.asarray(lens))


def _run(args, impl, window=None, mask=None, layer=None):
    q, kn, vn, kp, vp, table, starts, lens = args
    return paged_prefill_attention(
        q, kn, vn, kp, vp, table, starts, lens, kv_mask=mask,
        window=window, layer=layer, impl=impl, interpret=True)


def _visited(args):
    """Physical blocks some row's append visits (and the trash block)."""
    table, starts = np.asarray(args[5]), np.asarray(args[6])
    s, bs = args[0].shape[1], args[3].shape[1]
    visited = {0}
    for i in range(table.shape[0]):
        last = (int(starts[i]) + s - 1) // bs
        visited.update(int(b) for b in table[i, :last + 1])
    return visited


def _check_layered(args, layer, n=3):
    """`args`' pool as layer `layer` of `n` holding other numbers. In
    either implementation the layered call equals the rank-4 call on
    `pool[layer]` bit for bit (one kernel body, one scatter), and
    leaves every other layer and every unvisited block of `layer`
    byte-identical: the whole array is aliased, its other bytes stay."""
    rng = np.random.default_rng(1000 + layer)
    pools = []
    for one in args[3:5]:
        every = np.asarray(rng.normal(size=(n, *one.shape)), np.float32)
        every[layer] = np.asarray(one)
        pools.append(every)
    largs = (*args[:3], jnp.asarray(pools[0]), jnp.asarray(pools[1]),
             *args[5:])
    unvisited = sorted(set(range(args[3].shape[0])) - _visited(args))
    assert unvisited
    for impl in ("xla", "pallas"):
        wo, wk, wv = _run(args, impl)
        go, gk, gv = _run(largs, impl, layer=jnp.int32(layer))
        np.testing.assert_array_equal(np.asarray(go), np.asarray(wo))
        for got, want, before in ((gk, wk, pools[0]), (gv, wv, pools[1])):
            got = np.asarray(got)
            assert got.shape == before.shape
            np.testing.assert_array_equal(got[layer], np.asarray(want))
            np.testing.assert_array_equal(got[layer, unvisited],
                                          before[layer, unvisited])
            others = [i for i in range(n) if i != layer]
            np.testing.assert_array_equal(got[others], before[others])


def _check(args, window=None, mask=None):
    """Output parity on valid rows/tokens + pool parity on every
    non-trash block (the kernel rewrites each visited block fully, so
    untouched cells must round-trip bit-identically)."""
    wo, wk, wv = _run(args, "xla", window=window, mask=mask)
    go, gk, gv = _run(args, "pallas", window=window, mask=mask)
    lens = np.asarray(args[7])
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(go)[i, :n],
                                   np.asarray(wo)[i, :n], **TOL)
    # block 0 is the garbage sink: both impls route invalid tokens
    # there, in impl-specific order — everything else must agree
    np.testing.assert_array_equal(np.asarray(gk)[1:],
                                  np.asarray(wk)[1:])
    np.testing.assert_array_equal(np.asarray(gv)[1:],
                                  np.asarray(wv)[1:])


@pytest.mark.parametrize("layer", [None, 0, 1, 2])
@pytest.mark.parametrize("n_q,n_kv", [(8, 2), (4, 4), (8, 1)])
def test_kernel_matches_oracle_across_gqa_ratios(n_q, n_kv, layer):
    for seed in (0, 1):
        args = _mk(seed, n_q=n_q, n_kv=n_kv)
        _check(args)
        if layer is not None:
            _check_layered(args, layer)


def test_kernel_matches_oracle_ragged_cursors():
    # cursors pinned to the raggedest corners: empty pool, block
    # boundaries either side, chunk straddling a boundary, window end
    _check(_mk(2, b=5, s=5, starts=[0, 7, 8, 30, 43]))


def test_kernel_matches_oracle_ragged_lens():
    # group padding: q_lens rags from full to ZERO new tokens (a row
    # admitted in a bigger group's dispatch with nothing to feed)
    _check(_mk(3, b=4, s=6, lens=[6, 3, 1, 0]))


@pytest.mark.parametrize("window", [1, 4, 13, 100])
def test_kernel_matches_oracle_sliding_window(window):
    _check(_mk(4), window=window)


def test_kernel_matches_oracle_masked_holes():
    q, kn, vn, kp, vp, table, starts, lens = _mk(5, b=2, nb=6, bs=8)
    mask = np.ones((2, 48), bool)
    mask[:, 3] = False  # a left-pad hole, same for every row
    _check((q, kn, vn, kp, vp, table, starts, lens),
           mask=jnp.asarray(mask))


def test_kernel_shared_prefix_blocks_are_read_only():
    """Radix sharing: two rows' tables reference the SAME physical
    block strictly below both cursors. Reads must not cross-talk, and
    the shared block's content must survive both rows' visits
    bit-identically (the kernel's rewrite of a read-only block is the
    content it read)."""
    rng = np.random.default_rng(11)
    bs, n_kv, hd = 8, 2, 16
    q = jnp.asarray(rng.normal(size=(2, 4, 4, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(2, 4, n_kv, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(2, 4, n_kv, hd)), jnp.float32)
    kp = np.asarray(rng.normal(size=(8, bs, n_kv, hd)), np.float32)
    vp = np.asarray(rng.normal(size=(8, bs, n_kv, hd)), np.float32)
    kp[0] = vp[0] = 0.0
    # both rows share block 3 (cells 0..7); writes land in exclusive
    # blocks 5 and 6 — the serving invariant exactly
    table = jnp.asarray([[3, 5, 0], [3, 6, 0]], jnp.int32)
    starts = jnp.asarray([8, 10], jnp.int32)
    args = (q, kn, vn, jnp.asarray(kp), jnp.asarray(vp), table,
            starts, jnp.asarray([4, 4], jnp.int32))
    _check(args)
    _, gk, gv = _run(args, "pallas")
    np.testing.assert_array_equal(np.asarray(gk)[3], kp[3])
    np.testing.assert_array_equal(np.asarray(gv)[3], vp[3])


def test_kernel_preserves_unvisited_blocks():
    """Blocks past each row's last visited block (and blocks owned by
    nobody) must come back byte-identical — the pool is shared state;
    a stray DMA would corrupt OTHER requests' KV."""
    args = _mk(6, b=2, s=4, starts=[0, 5])
    kp_before = np.asarray(args[3]).copy()
    _, gk, gv = _run(args, "pallas")
    visited = _visited(args)
    for blk in range(kp_before.shape[0]):
        if blk not in visited:
            np.testing.assert_array_equal(np.asarray(gk)[blk],
                                          kp_before[blk])


# -- dispatcher doors -------------------------------------------------------


def test_prefill_impl_dispatch_and_counters():
    args = _mk(7)
    base = impl_counts()
    _run(args, "pallas")
    _run(args, "xla")
    now = impl_counts()
    assert now["paged_prefill"] == base["paged_prefill"] + 2
    assert now["paged_prefill_pallas"] == base["paged_prefill_pallas"] + 1
    assert now["paged_prefill_xla"] == base["paged_prefill_xla"] + 1


def test_resolve_prefill_impl():
    assert resolve_paged_prefill_impl("xla") == "xla"
    assert resolve_paged_prefill_impl("pallas") == "pallas"
    # conftest pins the CPU backend, so auto must scatter+gather
    assert resolve_paged_prefill_impl("auto") == "xla"
    with pytest.raises(ValueError, match="impl"):
        resolve_paged_prefill_impl("cuda")


def test_dispatcher_validation_doors():
    q, kn, vn, kp, vp, table, starts, lens = _mk(8)
    with pytest.raises(ValueError, match="disagree"):
        paged_prefill_attention(q, kn, vn, kp, vp[:-1], table, starts)
    with pytest.raises(ValueError, match="block_table"):
        paged_prefill_attention(q, kn, vn, kp, vp, table[0], starts)
    with pytest.raises(ValueError, match="kv_mask"):
        paged_prefill_attention(
            q, kn, vn, kp, vp, table, starts,
            kv_mask=jnp.ones((3, 40), bool))
    # a pool of every layer comes with a layer, one layer's without:
    # the message carries the shapes (dispatcher and kernel alike)
    for fn in (paged_prefill_attention, paged_prefill_append):
        with pytest.raises(ValueError,
                           match=r"\(64, 8, 2, 32\).*layer of"):
            fn(q, kn, vn, kp, vp, table, starts, lens,
               layer=jnp.int32(0))
        with pytest.raises(ValueError,
                           match=r"\(1, 64, 8, 2, 32\).*layer None"):
            fn(q, kn, vn, kp[None], vp[None], table, starts, lens)


# -- continuous engine end-to-end token parity ------------------------------


def _engine(max_len=64):
    cfg = llama.LLAMA_TINY
    params = dict(llama.init(jax.random.key(0), cfg))
    params["lm_head"] = params["lm_head"] * 50.0  # argmax can't flip
    return InferenceEngine(params, cfg, LLAMA_FAMILY,
                           EngineConfig(max_len=max_len)), cfg


def test_engine_resolves_prefill_impl():
    engine, _ = _engine()
    ce = ContinuousEngine(engine, max_slots=2,
                          paged_attention_impl="auto")
    assert ce.prefill_impl == "xla"  # CPU auto-resolution
    ce = ContinuousEngine(engine, max_slots=2,
                          paged_attention_impl="pallas")
    assert ce.prefill_impl == "pallas"


@pytest.mark.slow
def test_chunked_prefill_token_parity_across_impls():
    """The serving-level A/B: chunked prefill emits IDENTICAL tokens
    whether the append runs through the fused kernel (interpret) or
    the XLA scatter+gather — the same contract the decode kernel
    pins."""
    engine, cfg = _engine()
    gen = np.random.default_rng(5)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (9, 17)]

    def run(impl):
        async def go():
            b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                                  kv_block_size=8,
                                  prefill_chunk_tokens=4,
                                  paged_attention_impl=impl)
            assert b.cengine.prefill_impl == impl
            out = await asyncio.gather(
                *(b.submit(p, 5, ()) for p in prompts))
            await b.close()
            return [list(o) for o in out]

        return asyncio.get_event_loop().run_until_complete(go())

    assert run("xla") == run("pallas")
