"""Fused decode attention over the PAGED KV pool (Pallas TPU kernel).

PR 2 moved the continuous engine's KV cache into a shared block pool
(`serving/paged.py`): each slot owns a block table of physical block
ids and a cursor. `ops.paged_attention`'s XLA path gathers every row's
FULL `[blocks_per_slot * block_size]` window through the table before
attending — correct, but it streams the dead tail (and the trash-block
padding) through HBM on every decode step, and decode MBU is the
roofline that matters (bench.py). This kernel walks the table
in-kernel instead:

- grid = (rows, blocks_per_slot); each row's CURSOR and BLOCK TABLE
  are scalar-prefetched, so the K/V BlockSpec index map can resolve
  `table[row, j]` before the body runs and DMA only that physical
  block from the pool;
- the pool is rank 5, `[L, num_blocks, block_size, n_kv, hd]`: every
  layer's, as the serving engines' layer scan carries it, with the
  LAYER a third scalar-prefetched operand. The index map returns
  `(layer, table[row, j], 0, 0, 0)` over a squeezed leading block
  dimension, so the body sees one block as before and no caller takes
  a layer's slice first (for a Pallas operand that slice is a copy of
  the whole layer: 268 MB a layer a step at Mistral-7B's pool). One
  layer's pool (rank 4, no `layer`) is lifted to `[1, ...]` at layer
  0, a bitcast, and runs the same body;
- iterations past the cursor block (and, with a sliding window, before
  the window's first block) are CLAMPED to the boundary — a repeated
  physical index means no new DMA, so HBM traffic tracks the cache
  FILL, not `blocks_per_slot * block_size` — and `pl.when` gates the
  compute;
- GQA stays at KV resolution (queries reshape to [n_kv, group] inside
  the kernel; the pool never repeats heads);
- per-block partials merge with the same online softmax as
  flash_attention.py / decode_attention.py; per-cell validity (left-pad
  holes) rides in as an int32 mask laid out `[b, blocks, 1, block_size]`
  and indexed by LOGICAL block (Mosaic takes neither an i1 VMEM operand
  nor a `(1, block_size)` block over `[b, width]`: the last two block
  dims must be (8, 128)-divisible or the array's own), causality masks
  by absolute cell index against the prefetched cursor.

Cell index == logical token position is a precondition (the pool's
insert-time compaction guarantees it — see serving/paged.py); callers
with rotated/packed layouts must use the XLA gather path, which masks
by the actual position tensors.

A row whose visible set is empty (pad holes over its whole causal
prefix) comes out as exact zeros; the XLA path gives such a row the mean
of V. Only host-masked filler rows are ever in that state, and
tools/smoke_kernels.py pins the convention on the chip.

The trash-block-0 convention costs nothing here: clamping confines j
to live blocks, so the table's trash tail is never even read.

Pinned against the XLA gather oracle (`ops.paged_attention`
impl="xla") by tests/test_paged_attention_kernel.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.attention import NEG_INF, layered_pool
from kubeflow_tpu.ops.pallas.flash_attention import resolve_interpret


def _kernel(pos_ref, tab_ref, layer_ref, q_ref, k_ref, v_ref, mask_ref,
            o_ref, acc, m_scr, l_scr, *, scale, window, block_size, nb,
            n_kv, group):
    # tab_ref and layer_ref are consumed by the BlockSpec index maps
    # (that's the whole point); the body only needs the cursor.
    del tab_ref, layer_ref
    b_i, bj = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b_i]

    @pl.when(bj == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # Relevance mirrors decode_attention: skip logical blocks past the
    # cursor AND (with a sliding window) blocks wholly older than the
    # attention band.
    relevant = bj * block_size <= pos
    if window is not None:
        relevant &= (bj * block_size + block_size - 1) >= pos - window + 1

    @pl.when(relevant)
    def _compute():
        n_q = n_kv * group
        q = q_ref[0, 0].astype(jnp.float32)           # [n_q, hd]
        k = k_ref[0].astype(jnp.float32)              # [bs, n_kv, hd]
        qg = q.reshape(n_kv, group, -1)
        kt = jnp.swapaxes(k, 0, 1)                    # [n_kv, bs, hd]
        # [n_kv, group, bs]: batch over kv heads — GQA without repeat
        logits = jax.lax.dot_general(
            qg, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        logits = logits.reshape(n_q, block_size)

        # Logical cell index == token position (pool compaction).
        idx = bj * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (n_q, block_size), 1)
        visible = (idx <= pos) & (mask_ref[0, 0] != 0)  # causal & pad holes
        if window is not None:
            visible &= (pos - idx) < window
        logits = jnp.where(visible, logits, NEG_INF)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
        p = jnp.exp(logits - m_new[:, None])
        # a fully-masked block contributes nothing, not exp(NEG_INF-m)
        p = jnp.where(visible, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            (l_scr[:, 0] * alpha + jnp.sum(p, axis=1))[:, None],
            l_scr.shape)
        v = v_ref[0].astype(jnp.float32)              # [bs, n_kv, hd]
        vg = jnp.swapaxes(v, 0, 1)                    # [n_kv, bs, hd]
        pv = jax.lax.dot_general(
            p.reshape(n_kv, group, block_size), vg,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(n_q, -1)                            # [n_q, hd]
        acc[:] = acc[:] * alpha[:, None] + pv
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)

    @pl.when(bj == nb - 1)
    def _finish():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / safe_l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,            # [b, 1, n_q, hd]
    k_pool: jnp.ndarray,       # [(L,) num_blocks, block_size, n_kv, hd]
    v_pool: jnp.ndarray,       # [(L,) num_blocks, block_size, n_kv, hd]
    block_table: jnp.ndarray,  # [b, blocks_per_slot] int32 physical ids
    q_positions: jnp.ndarray,  # [b] int32 — each row's cursor
    kv_mask: jnp.ndarray | None = None,  # [b, blocks_per_slot*block_size]
    *,
    layer=None,                # int32 scalar, with a rank-5 pool
    window: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One-token-per-row attention through each row's block table.

    HBM reads per row are `ceil((cursor+1)/block_size)` pool blocks
    (bounded below by the sliding window's first block), not the full
    `blocks_per_slot` window the XLA gather touches.
    """
    interpret = resolve_interpret(interpret)
    b, sq, n_q, hd = q.shape
    if sq != 1:
        raise ValueError(
            f"paged_decode_attention is s=1 only, got sq={sq}")
    k_pool, v_pool, layer = layered_pool(k_pool, v_pool, layer)
    block_size, n_kv, hd_kv = k_pool.shape[2:]
    if hd_kv != hd:
        raise ValueError(
            f"head dim mismatch: q has {hd}, pool has {hd_kv}")
    if n_q % n_kv:
        raise ValueError(f"{n_q} query heads not grouped by {n_kv} kv")
    group = n_q // n_kv
    if block_table.ndim != 2 or block_table.shape[0] != b:
        raise ValueError(
            f"block_table must be [b={b}, blocks_per_slot], got "
            f"{block_table.shape}")
    nb = block_table.shape[1]
    width = nb * block_size
    if q_positions.shape != (b,):
        raise ValueError(
            f"q_positions must be [b={b}], got {q_positions.shape}")
    if kv_mask is None:
        kv_mask = jnp.ones((b, width), bool)
    if kv_mask.shape != (b, width):
        raise ValueError(
            f"kv_mask must be [b={b}, blocks_per_slot*block_size="
            f"{width}], got {kv_mask.shape}")
    positions = q_positions.astype(jnp.int32)
    table = block_table.astype(jnp.int32)

    # Clamped LOGICAL block index: iterations outside a row's live
    # range re-reference a boundary block, whose PHYSICAL id then
    # repeats — consecutive equal indices skip the DMA, which is where
    # the fill-proportional saving comes from. The live range is
    # [first block the window can see, cursor block]; the table's
    # trash-block tail is never read.
    def _clamp(bj, pos):
        hi = pos // block_size
        if window is None:
            return jnp.minimum(bj, hi)
        lo = jnp.maximum((pos - window + 1) // block_size, 0)
        return jnp.clip(bj, lo, hi)

    def kv_map(b_i, bj, pos_ref, tab_ref, layer_ref):
        # The indirection: logical block -> physical pool block of the
        # prefetched layer (whose block dimension is squeezed away).
        return (layer_ref[0], tab_ref[b_i, _clamp(bj, pos_ref[b_i])],
                0, 0, 0)

    def mask_map(b_i, bj, pos_ref, tab_ref, layer_ref):
        # The mask is laid out logically, so no table lookup here.
        return (b_i, _clamp(bj, pos_ref[b_i]), 0, 0)

    def row_map(b_i, bj, pos_ref, tab_ref, layer_ref):
        return (b_i, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, 1, n_q, hd), row_map),
            pl.BlockSpec((None, 1, block_size, n_kv, hd), kv_map),
            pl.BlockSpec((None, 1, block_size, n_kv, hd), kv_map),
            pl.BlockSpec((1, 1, 1, block_size), mask_map),
        ],
        out_specs=pl.BlockSpec((1, 1, n_q, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((n_q, hd), jnp.float32),
            pltpu.VMEM((n_q, 128), jnp.float32),
            pltpu.VMEM((n_q, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=hd**-0.5, window=window, block_size=block_size,
        nb=nb, n_kv=n_kv, group=group,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(positions, table, layer.reshape(1), q, k_pool, v_pool,
      kv_mask.astype(jnp.int32).reshape(b, nb, 1, block_size))
