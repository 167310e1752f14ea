"""Fused paged-attention kernel vs the XLA gather oracle.

The kernel (ops/pallas/paged_attention.py) walks each row's block
table in-kernel; `ops.paged_attention(impl="xla")` gathers the full
window through the same table. The two must agree to fp32 tolerance
(online-softmax merge vs single-pass softmax) across everything the
serving engine can throw at them: GQA ratios, ragged cursors, sliding
windows, CoW-shared tables, and the trash-block-0 convention — and the
continuous engine must emit IDENTICAL tokens with either impl.

All kernel runs here are interpret mode (CPU backend — see conftest).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import gemma, llama
from kubeflow_tpu.ops.attention import (
    impl_counts,
    paged_attention,
    resolve_paged_attention_impl,
)
from kubeflow_tpu.ops.pallas.paged_attention import (
    group_blocks,
    paged_decode_attention,
)
from kubeflow_tpu.serving import (
    GEMMA_FAMILY,
    LLAMA_FAMILY,
    EngineConfig,
    InferenceEngine,
)
from kubeflow_tpu.serving.continuous import ContinuousBatcher, ContinuousEngine
from kubeflow_tpu.serving.paged import BlockPool

TOL = dict(atol=1e-5, rtol=1e-5)


def _mk(seed, b=3, n_q=8, n_kv=2, hd=32, bs=8, nb=6, num_blocks=32):
    """Random pool + per-row table/cursor in the engine's layout:
    ragged cursors, live blocks allocated from the pool, table tails
    trash-padded (block 0), a pad hole punched into the mask."""
    rng = np.random.default_rng(seed)
    width = nb * bs
    q = jnp.asarray(rng.normal(size=(b, 1, n_q, hd)), jnp.float32)
    kp = np.asarray(rng.normal(size=(num_blocks, bs, n_kv, hd)),
                    np.float32)
    vp = np.asarray(rng.normal(size=(num_blocks, bs, n_kv, hd)),
                    np.float32)
    kp[0] = vp[0] = 0.0  # the trash block holds no real tokens
    pos = rng.integers(0, width, size=(b,)).astype(np.int32)
    table = np.zeros((b, nb), np.int32)
    used = {0}
    for i in range(b):
        for j in range(pos[i] // bs + 1):
            blk = int(rng.choice([x for x in range(1, num_blocks)
                                  if x not in used]))
            used.add(blk)
            table[i, j] = blk
    mask = np.ones((b, width), bool)
    mask[:, 3] = False  # a left-pad hole, same for every row
    kv_pos = np.broadcast_to(np.arange(width, dtype=np.int32), (b, width))
    return (q, jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(pos), jnp.asarray(mask),
            jnp.asarray(kv_pos))


def _oracle(q, kp, vp, table, pos, mask, kv_pos, window=None):
    return paged_attention(q, kp, vp, table, pos[:, None], kv_pos,
                           causal=True, kv_mask=mask, window=window,
                           impl="xla")


def _layers(pool, layer, seed, n=3):
    """`pool` as layer `layer` of an `n`-layer pool whose other layers
    hold other numbers: a read of the wrong layer cannot pass."""
    rng = np.random.default_rng(1000 + seed)
    out = np.asarray(rng.normal(size=(n, *pool.shape)), np.float32)
    out[layer] = np.asarray(pool)
    return jnp.asarray(out)


@pytest.mark.parametrize("layer", [None, 0, 1, 2])
@pytest.mark.parametrize("n_q,n_kv", [(8, 2), (4, 4), (8, 1)])
def test_kernel_matches_oracle_across_gqa_ratios(n_q, n_kv, layer):
    """`layer=None`: one layer's pool (rank 4). Otherwise the same pool
    as layer `layer` of three: the layered call must equal the rank-4
    call bit for bit in either implementation (one kernel body, one
    gather), and the kernel the oracle to fp32 tolerance."""
    for seed in (0, 1):
        q, kp, vp, table, pos, mask, kv_pos = _mk(
            seed, n_q=n_q, n_kv=n_kv)
        want = _oracle(q, kp, vp, table, pos, mask, kv_pos)
        got = paged_decode_attention(q, kp, vp, table, pos, mask,
                                     interpret=True)
        if layer is not None:
            kl, vl = _layers(kp, layer, seed), _layers(vp, layer, seed + 7)
            li = jnp.int32(layer)
            np.testing.assert_array_equal(
                np.asarray(paged_attention(
                    q, kl, vl, table, pos[:, None], kv_pos, causal=True,
                    kv_mask=mask, layer=li, impl="xla")),
                np.asarray(want))
            layered = paged_decode_attention(
                q, kl, vl, table, pos, mask, layer=li, interpret=True)
            np.testing.assert_array_equal(np.asarray(layered),
                                          np.asarray(got))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL)


def test_kernel_matches_oracle_ragged_cursors():
    # cursors pinned to the raggedest corners: empty-but-one, block
    # boundaries either side, full window
    q, kp, vp, table, _, mask, kv_pos = _mk(2, b=5, nb=6, bs=8)
    pos = jnp.asarray([0, 7, 8, 33, 47], jnp.int32)
    table = jnp.asarray(np.where(
        np.arange(6)[None] <= np.asarray(pos)[:, None] // 8,
        np.asarray(table), 0))
    want = _oracle(q, kp, vp, table, pos, mask, kv_pos)
    got = paged_decode_attention(q, kp, vp, table, pos, mask,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("window", [1, 4, 13, 100])
def test_kernel_matches_oracle_sliding_window(window):
    q, kp, vp, table, pos, mask, kv_pos = _mk(3)
    want = _oracle(q, kp, vp, table, pos, mask, kv_pos, window=window)
    got = paged_decode_attention(q, kp, vp, table, pos, mask,
                                 window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_kernel_matches_oracle_cow_shared_tables():
    """Two rows point at the SAME physical block (radix sharing /
    copy-on-write): the indirection must read it once per row without
    cross-talk."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 16)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(8, 4, 2, 16)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(8, 4, 2, 16)), jnp.float32)
    table = jnp.asarray([[3, 5, 0], [3, 6, 0]], jnp.int32)  # share 3
    pos = jnp.asarray([6, 7], jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32), (2, 12))
    want = paged_attention(q, kp, vp, table, pos[:, None], kv_pos,
                           causal=True, impl="xla")
    got = paged_decode_attention(q, kp, vp, table, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_kernel_never_reads_the_trash_tail():
    """Trash-block-0 convention: table tails point at block 0. The
    kernel must confine its copies to live blocks — poison the trash
    block with NaN and the output must stay finite and match the
    oracle run on a clean pool. (The oracle itself is NOT given the
    poison: its gather multiplies trash V cells by probability 0.0,
    and 0 * NaN = NaN — the full-window read the kernel exists to
    avoid.)"""
    q, kp, vp, table, pos, mask, kv_pos = _mk(4)
    want = _oracle(q, kp, vp, table, pos, mask, kv_pos)
    kp_bad = jnp.asarray(np.asarray(kp)).at[0].set(np.nan)
    vp_bad = jnp.asarray(np.asarray(vp)).at[0].set(np.nan)
    got = paged_decode_attention(q, kp_bad, vp_bad, table, pos, mask,
                                 interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# -- grid steps of several blocks --------------------------------------------
#
# The cases above fit one group (`group_blocks` stops at the table's
# size). These run at block sizes a pool is served with, where a table
# is several groups: float32 blocks of 64 cells x 4 heads x 128 are
# 128 KB, as the bf16 blocks of Mistral-7B's 8 heads, and make G = 8
# (512 cells a step); 8 heads make 256 KB and G = 4.


def _mk_groups(seed, cursors, nb, *, n_q=8, n_kv=4, hd=128, bs=64,
               spare=3, dtype=np.float32):
    """Rows at the given cursors over `nb`-block tables: each live
    block its own pool block, in scrambled order, the table's tails at
    the trash block, `spare` blocks no row owns, one pad hole a row
    (cell 1). Cursor -1: a row with no cell at all."""
    rng = np.random.default_rng(seed)
    cursors = np.asarray(cursors, np.int32)
    b, width = len(cursors), nb * bs
    live = np.where(cursors < 0, 0, cursors // bs + 1)
    num_blocks = 1 + int(live.sum()) + spare
    ids = 1 + rng.permutation(num_blocks - 1)
    table = np.zeros((b, nb), np.int32)
    for r, start in enumerate(np.cumsum(live) - live):
        table[r, :live[r]] = ids[start:start + live[r]]
    q = rng.normal(size=(b, 1, n_q, hd)).astype(dtype)
    kp, vp = (rng.normal(size=(num_blocks, bs, n_kv, hd)).astype(dtype)
              for _ in range(2))
    mask = np.ones((b, width), bool)
    mask[:, 1] = False
    kv_pos = np.broadcast_to(np.arange(width, dtype=np.int32), (b, width))
    return tuple(jnp.asarray(a) for a in
                 (q, kp, vp, table, cursors, mask, kv_pos))


def _poisoned(pool, table, cursors, bs):
    """`pool` with NaN in every block that is live for no row."""
    live = np.zeros(pool.shape[0], bool)
    for row, cur in zip(np.asarray(table), np.asarray(cursors)):
        live[row[:max(cur // bs + 1, 0)]] = True
    return jnp.where(live[:, None, None, None], pool, np.nan)


GROUP_CASES = {
    # cursors on block and group edges, G = 8: first cell, a block's
    # last and the next one's first, a group's last and the next one's
    # first, the table's last
    "edges": dict(cursors=[0, 63, 64, 511, 512, 64 * 64 - 1], nb=64),
    # G = 4 (eight heads): tables of a group and a block, of three
    # groups and a block, and shorter than one group
    "5 blocks": dict(cursors=[300, 255, 256, 319], nb=5, n_kv=8),
    "13 blocks": dict(cursors=[831, 767, 768, 10], nb=13, n_kv=8),
    "3 blocks": dict(cursors=[191, 64, 5], nb=3, n_kv=8),
    "all rows full": dict(cursors=[16 * 64 - 1] * 3, nb=16),
    # rows with no cell (cursor -1) around the one that has: the
    # first copies, and the next row's, have to skip them
    "one row live": dict(cursors=[-1, -1, 700, -1], nb=16),
    "last row live": dict(cursors=[-1, 40, -1, 1023], nb=16),
}


@pytest.mark.parametrize("case", GROUP_CASES)
def test_kernel_matches_oracle_over_groups_of_blocks(case):
    """Every pool block that is live for no row is NaN (the trash
    block, the spare ones): the kernel copies live blocks only, zeroes
    what it did not copy out of the product, and equals the oracle on
    the clean pool; a row with no cell is exact zeros."""
    kw = dict(GROUP_CASES[case])
    cursors = kw["cursors"]
    q, kp, vp, table, pos, mask, kv_pos = _mk_groups(11, **kw)
    bs, n_kv, hd = kp.shape[1:]
    g = group_blocks(kw["nb"], bs, n_kv, hd, 4)
    assert g == (8 if n_kv == 4 else 4)
    want = np.asarray(_oracle(q, kp, vp, table, jnp.maximum(pos, 0),
                              mask, kv_pos))
    got = np.asarray(paged_decode_attention(
        q, _poisoned(kp, table, cursors, bs),
        _poisoned(vp, table, cursors, bs), table, pos, mask,
        interpret=True))
    assert np.isfinite(got).all()
    has_cell = np.asarray(cursors) >= 0
    np.testing.assert_allclose(got[has_cell], want[has_cell], **TOL)
    assert not got[~has_cell].any()


@pytest.mark.parametrize("window,cursors", [
    # the window's first block in the group before the cursor's
    (700, [1500, 520, 4095]),
    # ... three groups before it, and inside the cursor's own group
    (1600, [2047, 1700, 100]),
    (100, [1500, 511, 512]),
    # one cell
    (1, [512, 0, 63]),
])
def test_kernel_matches_oracle_window_across_groups(window, cursors):
    q, kp, vp, table, pos, mask, kv_pos = _mk_groups(12, cursors, 64)
    # blocks the window has left behind are dead: give them NaN
    first = np.maximum(np.asarray(cursors) - window + 1, 0) // 64
    dead = np.concatenate([np.asarray(table)[r, :f]
                           for r, f in enumerate(first)])
    poison = jnp.zeros(kp.shape[0], bool).at[dead].set(True).at[0].set(
        True)[:, None, None, None]
    want = _oracle(q, kp, vp, table, pos, mask, kv_pos, window=window)
    got = np.asarray(paged_decode_attention(
        q, jnp.where(poison, np.nan, kp), jnp.where(poison, np.nan, vp),
        table, pos, mask, window=window, interpret=True))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), **TOL)


def test_kernel_matches_oracle_at_the_served_dtype():
    """bfloat16, Mistral-7B's heads: `q k^T` on bfloat16 operands and
    `p` in three bfloat16 parts against V. Both are exact products
    summed in float32, so the float32 oracle on the same numbers is
    met to the output's own rounding."""
    q, kp, vp, table, pos, mask, kv_pos = _mk_groups(
        13, [0, 700, 1535, 2047], 32, n_q=32, n_kv=8, dtype=np.float32)
    q, kp, vp = (a.astype(jnp.bfloat16) for a in (q, kp, vp))
    assert group_blocks(32, 64, 8, 128, 2) == 8
    want = _oracle(*(a.astype(jnp.float32) for a in (q, kp, vp)),
                   table, pos, mask, kv_pos)
    got = paged_decode_attention(q, kp, vp, table, pos, mask,
                                 interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=2.0 ** -8,
                               rtol=2.0 ** -8)


def test_group_of_blocks_is_read_off_the_shapes():
    # mistral-7b.steady's call: 64-block tables of 64 x 8 x 128 bf16
    assert group_blocks(64, 64, 8, 128, 2) == 8
    # a table of four blocks pays for four; one block, for one
    assert group_blocks(4, 64, 8, 128, 2) == 4
    assert group_blocks(1, 64, 8, 128, 2) == 1
    # twice the bytes a block, half the blocks; small blocks stop at
    # the table
    assert group_blocks(64, 64, 8, 128, 4) == 4
    assert group_blocks(6, 8, 2, 32, 4) == 8


# -- dispatcher doors -------------------------------------------------------


def test_paged_attention_impl_dispatch_and_counters():
    q, kp, vp, table, pos, mask, kv_pos = _mk(5)
    base = impl_counts()
    want = _oracle(q, kp, vp, table, pos, mask, kv_pos)
    got = paged_attention(q, kp, vp, table, pos[:, None], kv_pos,
                          causal=True, kv_mask=mask, impl="pallas",
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    now = impl_counts()
    assert now["paged_pallas"] == base["paged_pallas"] + 1
    assert now["paged_xla"] == base["paged_xla"] + 1  # the oracle call


def test_resolve_impl():
    assert resolve_paged_attention_impl("xla") == "xla"
    assert resolve_paged_attention_impl("pallas") == "pallas"
    # conftest pins the CPU backend, so auto must gather
    assert resolve_paged_attention_impl("auto") == "xla"
    with pytest.raises(ValueError, match="impl"):
        resolve_paged_attention_impl("cuda")


def test_dispatcher_validation_doors():
    q, kp, vp, table, pos, mask, kv_pos = _mk(6)
    with pytest.raises(ValueError, match="causal-only"):
        paged_attention(q, kp, vp, table, pos[:, None], kv_pos,
                        causal=False, impl="pallas", interpret=True)
    # geometry mismatches raise with the actual numbers, not an opaque
    # jit gather/reshape error
    with pytest.raises(ValueError, match="kv_positions"):
        paged_attention(q, kp, vp, table, pos[:, None],
                        kv_pos[:, :-8], causal=True)
    with pytest.raises(ValueError, match="kv_mask"):
        paged_attention(q, kp, vp, table, pos[:, None], kv_pos,
                        causal=True, kv_mask=mask[:, :-8])
    with pytest.raises(ValueError, match="disagree"):
        paged_attention(q, kp, vp[:-1], table, pos[:, None], kv_pos,
                        causal=True)
    with pytest.raises(ValueError, match="block_table"):
        paged_attention(q, kp, vp, table[0], pos[:, None], kv_pos,
                        causal=True)
    # a pool of every layer comes with a layer, one layer's without:
    # the message carries the shapes
    with pytest.raises(ValueError, match=r"\(32, 8, 2, 32\).*layer of"):
        paged_attention(q, kp, vp, table, pos[:, None], kv_pos,
                        causal=True, layer=jnp.int32(0))
    with pytest.raises(ValueError,
                       match=r"\(1, 32, 8, 2, 32\).*layer None"):
        paged_attention(q, kp[None], vp[None], table, pos[:, None],
                        kv_pos, causal=True)


def test_kernel_validation_doors():
    q, kp, vp, table, pos, mask, _ = _mk(6)
    with pytest.raises(ValueError, match="s=1"):
        paged_decode_attention(jnp.concatenate([q, q], axis=1), kp, vp,
                               table, pos, interpret=True)
    with pytest.raises(ValueError, match="q_positions"):
        paged_decode_attention(q, kp, vp, table, pos[:, None],
                               interpret=True)
    with pytest.raises(ValueError, match="kv_mask"):
        paged_decode_attention(q, kp, vp, table, pos,
                               mask[:, :-1], interpret=True)
    with pytest.raises(ValueError, match="grouped"):
        paged_decode_attention(q[:, :, :3], kp, vp, table, pos,
                               interpret=True)
    with pytest.raises(ValueError, match=r"\(32, 8, 2, 32\).*layer of"):
        paged_decode_attention(q, kp, vp, table, pos, layer=1,
                               interpret=True)
    with pytest.raises(ValueError,
                       match=r"\(1, 32, 8, 2, 32\).*layer None"):
        paged_decode_attention(q, kp[None], vp[None], table, pos,
                               interpret=True)


# -- engine construction geometry ------------------------------------------


def _llama_engine(max_len=32):
    cfg = llama.LLAMA_TINY
    params = dict(llama.init(jax.random.key(0), cfg))
    params["lm_head"] = params["lm_head"] * 50.0  # argmax can't flip
    return InferenceEngine(params, cfg, LLAMA_FAMILY,
                           EngineConfig(max_len=max_len)), cfg


def test_engine_rejects_mismatched_pool_geometry():
    engine, _ = _llama_engine()
    # matching pool: accepted and adopted
    pool = BlockPool(9, 8)
    ce = ContinuousEngine(engine, max_slots=2, block_size=8,
                          num_blocks=9, pool=pool)
    assert ce.pool is pool
    # wrong block_size: the table/mask layout would disagree with the
    # pool shape — must fail HERE, not deep inside jit
    with pytest.raises(ValueError, match="block_size=16"):
        ContinuousEngine(engine, max_slots=2, block_size=8,
                         num_blocks=9, pool=BlockPool(9, 16))
    with pytest.raises(ValueError, match="num_blocks=32"):
        ContinuousEngine(engine, max_slots=2, block_size=8,
                         num_blocks=9, pool=BlockPool(32, 8))


@pytest.mark.parametrize("window,cursors,live,fetching", [
    # block and group edges of a 64-block table in groups of 8
    (None, [0, 63, 64, 511, 512, 4095], 1 + 1 + 2 + 8 + 9 + 64,
     1 + 1 + 1 + 1 + 2 + 8),
    (None, [], 0, 0),
    # cells 801..1500: blocks 12..23, groups 1 and 2
    (700, [1500, 10], 12 + 1, 2 + 1),
])
def test_decode_kv_steps_at_the_served_shapes(window, cursors, live,
                                              fetching):
    """`sched.decode`'s stats, at mistral-7b.steady's geometry (the
    arithmetic needs no weights: the engine is a stand-in)."""
    import types

    ce = object.__new__(ContinuousEngine)
    ce.engine = types.SimpleNamespace(cfg=types.SimpleNamespace(
        num_kv_heads=8, head_dim=128, dtype=jnp.bfloat16,
        sliding_window=window))
    ce.S, ce.block_size, ce.blocks_per_slot = 16, 64, 64
    assert ce.decode_kv_steps(cursors) == {
        "kv_blocks_live": live, "kv_steps_fetching": fetching,
        "kv_steps": 16 * 8}


def test_decode_dispatch_span_carries_the_kv_steps():
    """Each `sched.decode` span of a dispatch says how many pool blocks
    its rows can see and how many of the kernel's grid steps fetch."""
    import contextlib

    spans = []

    @contextlib.contextmanager
    def annotate(name, **stats):
        spans.append((name, stats))
        yield

    async def run():
        engine, _ = _llama_engine()
        b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                              kv_block_size=8,
                              paged_attention_impl="xla")
        b.profiler._annotate = annotate
        await b.submit(list(range(3, 13)), 4, ())
        await b.close()
        return b.cengine

    ce = asyncio.get_event_loop().run_until_complete(run())
    dispatches = [st for name, st in spans
                  if name == "sched.decode" and "kv_steps" in st]
    assert dispatches
    for st in dispatches:
        # one row decoding, its cursor in the second block of 8 cells;
        # the tiny model's whole table is one group
        assert st["kv_blocks_live"] == 2
        assert st["kv_steps_fetching"] == 1
        assert st["kv_steps"] == ce.S == 2
        # two KV heads of 32 side by side: the pool's minor dimension
        assert st["kv_cell_lanes"] == ce.kv_cell[1] == 64


def test_engine_rejects_bad_impl_name():
    engine, _ = _llama_engine()
    with pytest.raises(ValueError, match="impl"):
        ContinuousEngine(engine, max_slots=2, paged_attention_impl="tpu")
    ce = ContinuousEngine(engine, max_slots=2,
                          paged_attention_impl="auto")
    assert ce.attention_impl == "xla"  # CPU backend resolves to gather


def test_server_exports_attention_impl_and_wires_tracer():
    """The observability contract: the app publishes which impl decode
    resolved to (info gauge) and hands the batcher its tracer so
    decode chunks become `decode.attention` spans."""
    from kubeflow_tpu.serving.server import (
        BATCHERS_KEY,
        OBS_KEY,
        create_serving_app,
    )

    engine, _ = _llama_engine()
    app = create_serving_app({"m": engine}, continuous=True,
                             kv_block_size=8)
    sobs = app[OBS_KEY]
    b = app[BATCHERS_KEY]["m"]
    assert b.tracer is sobs.tracer
    assert b.cengine.attention_impl == "xla"  # CPU auto-resolution
    text = sobs.registry.render()
    assert 'serving_attention_impl{impl="xla",model="m"} 1' in text
    # the form the pool keeps a cell in: two heads of 32 in one row
    assert 'serving_kv_pool_cell_lanes{model="m"} 64' in text
    # the knob is continuous-only, like the rest of the paged config
    with pytest.raises(ValueError, match="paged_attention_impl"):
        create_serving_app({"m": engine},
                           paged_attention_impl="pallas")


# -- continuous engine end-to-end token parity ------------------------------


def _decode_all(engine, prompts, max_new, impl, tracer=None):
    async def run():
        b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                              kv_block_size=8,
                              paged_attention_impl=impl)
        assert b.cengine.attention_impl == impl
        b.tracer = tracer
        out = await asyncio.gather(
            *(b.submit(p, max_new, ()) for p in prompts))
        await b.close()
        return [list(o) for o in out]

    return asyncio.get_event_loop().run_until_complete(run())


@pytest.mark.slow
def test_continuous_token_parity_llama():
    from kubeflow_tpu import obs

    engine, cfg = _llama_engine()
    gen = np.random.default_rng(5)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (9, 5)]
    tracer = obs.Tracer()
    xla = _decode_all(engine, prompts, 5, "xla", tracer=tracer)
    pallas = _decode_all(engine, prompts, 5, "pallas", tracer=tracer)
    assert xla == pallas
    # every decode chunk became a span tagged with the impl that ran it
    impls = {s["attrs"]["impl"]
             for t in tracer.traces("decode.attention")
             for s in t["spans"] if s["name"] == "decode.attention"}
    assert impls == {"xla", "pallas"}


@pytest.mark.slow
def test_continuous_token_parity_gemma():
    # gemma exercises the other family: 8q/1kv GQA and the
    # sliding-window-capable attention plumbing
    cfg = gemma.GEMMA_TINY
    engine = InferenceEngine(
        gemma.init(jax.random.key(1), cfg), cfg, GEMMA_FAMILY,
        EngineConfig(max_len=32))
    gen = np.random.default_rng(9)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (7, 11)]
    xla = _decode_all(engine, prompts, 5, "xla")
    pallas = _decode_all(engine, prompts, 5, "pallas")
    assert xla == pallas
