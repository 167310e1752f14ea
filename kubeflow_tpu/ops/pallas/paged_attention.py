"""Fused decode attention over the PAGED KV pool (Pallas TPU kernel).

PR 2 moved the continuous engine's KV cache into a shared block pool
(`serving/paged.py`): each slot owns a block table of physical block
ids and a cursor. `ops.paged_attention`'s XLA path gathers every row's
FULL `[blocks_per_slot * block_size]` window through the table before
attending — correct, but it streams the dead tail (and the trash-block
padding) through HBM on every decode step, and decode MBU is the
roofline that matters (bench.py). This kernel walks the table
in-kernel instead, and its grid steps follow the LIVE blocks:

- the pools stay in HBM (`memory_space=ANY`), whole and rank 5,
  `[L, num_blocks, block_size, n_kv, hd]`: every layer's, as the
  serving engines' layer scan carries it. Cursors, block table and the
  LAYER are scalar-prefetched. One layer's pool (rank 4, no `layer`) is
  lifted to `[1, ...]` at layer 0, a bitcast, and runs the same body.
  The call sees a block as `[block_size * n_kv, hd]` rows (row = cell
  * n_kv + head, the pool's own order): a reshape that is a bitcast
  where the heads fill the pool's tiles (8 heads of 128 in bf16; the
  compiled serving step has no copy of the pool), and that lets one
  KV head (Gemma) through Mosaic's copies, which a `[.., 1, hd]` slice
  is not. The head dim has to be a multiple of 128 on the chip;
- grid = (rows, ceil(blocks_per_slot / G)): a step owns a GROUP of `G`
  consecutive logical blocks of one row. A row's live range runs from
  the first block its sliding window can see (block 0 without one) to
  its cursor's block. A group that touches the range is a FETCHING
  step; any other starts no copy and runs no body, so a slot's empty
  tail costs `1 / G` of the steps it used to (16 x 64 = 1024 steps a
  call at Mistral-7B's serving shapes were 16 x 8 = 128 now, of which
  the ~35 that hold live blocks do anything);
- a fetching step copies ONLY its live blocks, each with its own
  `pltpu.make_async_copy` through the table (`pool[layer, table[row,
  j]]` -> block `j % G` of a VMEM buffer of `G` blocks) and its own DMA
  semaphore. Two such buffers alternate: before a fetching step waits
  for its own copies it starts those of the NEXT fetching step (the
  row's next group, or the first live group of the next row that has
  one), so they fly while this one is computed. The very first step of
  the grid starts the first fetching step's copies;
- `G` (`group_blocks`) is the largest power of two whose two K and two
  V buffers fit `VMEM_BUFFER_BYTES`, and no more than the table needs:
  8 at Mistral-7B's shapes (64 blocks of 64 x 8 x 128 bf16: 512 tokens,
  2 MB of K and V a buffer), 4 for a 4-block table. It is read off the
  call's shapes and nothing else;
- the body works on the group at once. The buffer is `[G * block_size
  * n_kv, hd]` rows, in the pool's own order (no transpose), and all
  query heads meet all rows in one `q k^T`: a query head's logits
  against another KV head's rows are masked like any invisible cell
  (GQA without repeating or regrouping the pool: the MXU's time is the
  K and V tiles it loads, which are the same either way). `q k^T`
  takes its operands in the promoted dtype of `q` and the pool with
  float32 accumulation (bf16 x bf16 is exact in float32); logits, the
  running maximum, the sum, `p` and the accumulator are float32, and
  `p` is not rounded before `p v`: against a bfloat16 pool it is split
  into three bfloat16 parts that sum to it exactly, which meet V in
  one pass (Mosaic's float32 dot rounds its operands to bfloat16
  unless told HIGHEST, which loads V's tiles six times); a float32
  pool takes HIGHEST in both products;
- masking: a column is visible if it is its query head's own KV head,
  its token is at or before the cursor, inside the window, and no pad
  hole. Everything else, the cells of blocks that were NOT fetched
  among them (a buffer holds whatever an earlier group left there, or
  nothing yet: possibly NaN bits), is masked with `where` on the
  logits AND on `p`, and the V rows outside `[window start, cursor]`
  are zeroed before `p v`, so nothing unfetched is read as a number;
- partials merge with the same online softmax as flash_attention.py /
  decode_attention.py; per-cell validity (left-pad holes) rides in as
  an int32 mask laid out `[b, groups, 1, G * block_size]` (Mosaic
  takes neither an i1 VMEM operand nor a block whose last two dims are
  not (8, 128)-divisible or the array's own), a bit a cell. A cell has
  `n_kv` columns, and repeating lanes in place is what neither XLA (a
  2 MB relayout a call, 30 us of a 110 us call when it was tried) nor
  Mosaic does well: the body multiplies the bits, 128 cells to a row,
  by a constant `[128, 128 * n_kv]` matrix of ones where column //
  n_kv is the cell (5 us a call). The mask's index map is clamped to
  the live groups, so a dead step refetches nothing.

Cell index == logical token position is a precondition (the pool's
insert-time compaction guarantees it — see serving/paged.py); callers
with rotated/packed layouts must use the XLA gather path, which masks
by the actual position tensors.

A row whose visible set is empty (pad holes over its whole causal
prefix, or a negative cursor) comes out as exact zeros; the XLA path
gives such a row the mean of V. Only host-masked filler rows are ever
in that state, and tools/smoke_kernels.py pins the convention on the
chip.

The trash-block-0 convention costs nothing here: only live blocks are
copied, so the table's trash tail is never even read.

Pinned against the XLA gather oracle (`ops.paged_attention`
impl="xla") by tests/test_paged_attention_kernel.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.attention import NEG_INF, layered_pool
from kubeflow_tpu.ops.pallas.flash_attention import resolve_interpret

# What the K and V buffers of one call may take of VMEM, both slots of
# both: `group_blocks` sizes a grid step's group of blocks against it.
VMEM_BUFFER_BYTES = 4 * 2**20


def group_blocks(blocks_per_slot: int, block_size: int, n_kv: int,
                 hd: int, itemsize: int) -> int:
    """`G`, the pool blocks one grid step owns: the largest power of
    two whose double-buffered K and V fit `VMEM_BUFFER_BYTES`, stopped
    at the first that covers the whole table."""
    block_bytes = block_size * n_kv * hd * itemsize
    g = 1
    while g < blocks_per_slot and 8 * g * block_bytes <= VMEM_BUFFER_BYTES:
        g *= 2
    return g


def _div(x, n: int):
    """x // n for non-negative int32 `x`: a shift where `n` is a power
    of two (the VPU has no integer divide)."""
    if n & (n - 1) == 0:
        return jax.lax.shift_right_logical(x, n.bit_length() - 1)
    return jax.lax.div(x, n)


def _rem(x, n: int):
    """x % n for non-negative int32 `x`, as `_div`."""
    return x & (n - 1) if n & (n - 1) == 0 else jax.lax.rem(x, n)


def _kernel(pos_ref, tab_ref, layer_ref, q_ref, k_hbm, v_hbm, mask_ref,
            spread_ref, o_ref, k_buf, v_buf, sems, slot_ref, acc, m_scr,
            l_scr, *,
            scale, window, block_size, nb, g_blocks, n_kv, group, rows):
    r, g = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    tokens = g_blocks * block_size        # a group's cells
    cols = tokens * n_kv                  # ... and its K/V rows

    def live_blocks(row):
        """[lo, hi], the logical blocks `row` can see; hi < lo: none."""
        pos = pos_ref[row]
        hi = jnp.where(pos < 0, -1,
                       jnp.minimum(jax.lax.div(pos, block_size), nb - 1))
        if window is None:
            return 0, hi
        first = jnp.maximum(pos - window + 1, 0)
        return jax.lax.div(first, block_size), hi

    def each_live_copy(row, grp, slot, do):
        """`do(copy)` for the K and V copy of every live block of group
        `grp` of `row`, into (or out of the semaphores of) `slot`."""
        lo, hi = live_blocks(row)

        def block(i, _):
            blk = grp * g_blocks + i

            @pl.when((blk >= lo) & (blk <= hi))
            def _():
                phys = tab_ref[row, blk]
                for kv, (pool, buf) in enumerate(
                        ((k_hbm, k_buf), (v_hbm, v_buf))):
                    do(pltpu.make_async_copy(
                        pool.at[layer, phys], buf.at[slot, i],
                        sems.at[kv, slot, i]))

        # a loop, not `for i in range(g_blocks)`: unrolled, the three
        # uses of this traced 48 copies a kernel and set-up took 6 s
        # longer, for 1.5 us a call (PERF.md, PR 29)
        jax.lax.fori_loop(0, g_blocks, block, None)

    def first_live_row(start):
        """The first row >= start with a live block, or `rows`."""
        def dead(row):
            lo, hi = live_blocks(jnp.minimum(row, rows - 1))
            return (row < rows) & (hi < lo)
        return jax.lax.while_loop(dead, lambda row: row + 1, start)

    def first_group(row):
        return jax.lax.div(live_blocks(jnp.minimum(row, rows - 1))[0],
                           g_blocks)

    @pl.when((r == 0) & (g == 0))
    def _first_copies():
        first = first_live_row(0)
        slot_ref[0] = 0

        @pl.when(first < rows)
        def _():
            each_live_copy(first, first_group(first), 0,
                           lambda copy: copy.start())

    @pl.when(g == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    pos = jnp.minimum(pos_ref[r], nb * block_size - 1)
    lo, hi = live_blocks(r)
    fetching = (g * g_blocks <= hi) & ((g + 1) * g_blocks - 1 >= lo)

    @pl.when(fetching)
    def _compute():
        slot = slot_ref[0]
        # the next fetching step's copies fly under this one's body
        more = (g + 1) * g_blocks <= hi
        nxt = first_live_row(jnp.where(more, r, r + 1))

        @pl.when(nxt < rows)
        def _():
            each_live_copy(nxt, jnp.where(more, g + 1, first_group(nxt)),
                           1 - slot, lambda copy: copy.start())

        each_live_copy(r, g, slot, lambda copy: copy.wait())
        slot_ref[0] = 1 - slot

        # Logical cell index == token position (pool compaction): the
        # group's K/V rows [first, last) are the cells the cursor and
        # the window let through. Rows outside hold cells past the
        # cursor, before the window, or nothing that was ever copied.
        last = (pos - g * tokens + 1) * n_kv
        first = (0 if window is None
                 else (pos - window + 1 - g * tokens) * n_kv)

        def seen(row):
            return (row < last) & (row >= first)

        n_q = n_kv * group
        q = q_ref[0, 0]                                # [n_q, hd]
        k = k_buf[slot].reshape(cols, -1)              # [cols, hd]
        mm = jnp.promote_types(q.dtype, k.dtype)
        exact = (jax.lax.Precision.HIGHEST if mm == jnp.float32
                 else None)     # bf16 x bf16 is exact in float32
        logits = jax.lax.dot_general(
            q.astype(mm), k.astype(mm), (((1,), (1,)), ((), ())),
            precision=exact, preferred_element_type=jnp.float32,
        ) * scale                                      # [n_q, cols]

        # The mask holds a bit a cell and a cell has n_kv columns: the
        # cells, `span` to a row, times `spread` ([span, span * n_kv],
        # a one where column // n_kv is the cell) give each column its
        # cell's bit: lanes are not repeated in place on this chip
        span = spread_ref.shape[0]
        cells = jnp.concatenate(
            [mask_ref[0, 0, :, i:i + span]
             for i in range(0, tokens, span)])      # [tokens / span, span]
        bits = jax.lax.dot_general(
            cells.astype(jnp.float32).astype(spread_ref.dtype),
            spread_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        holes = jnp.concatenate(
            [bits[i:i + 1] for i in range(tokens // span)],
            axis=1) == 0.0                             # [1, cols]

        col = jax.lax.broadcasted_iota(jnp.int32, (n_q, cols), 1)
        q_head = jax.lax.broadcasted_iota(jnp.int32, (n_q, cols), 0)
        visible = (seen(col)
                   & (_rem(col, n_kv) == _div(q_head, group))  # GQA
                   & ~holes)                                   # pads
        logits = jnp.where(visible, logits, NEG_INF)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
        p = jnp.exp(logits - m_new[:, None])
        # a fully-masked group contributes nothing, not exp(NEG_INF-m)
        p = jnp.where(visible, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            (l_scr[:, 0] * alpha + jnp.sum(p, axis=1))[:, None],
            l_scr.shape)
        # 0 * NaN is NaN: an unseen row leaves the product as a row of
        # zeros, not as the zeros of p
        v = v_buf[slot].reshape(cols, -1).astype(jnp.float32)
        v = jnp.where(
            seen(jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)), v, 0.0)
        if v_buf.dtype == jnp.bfloat16:
            # p = p1 + p2 + p3 to the last bit, each a bfloat16: three
            # exact products in one pass over V's tiles, where a
            # float32 dot would round p (one pass) or load V's tiles
            # six times (HIGHEST)
            p1 = p.astype(jnp.bfloat16)
            rest = p - p1.astype(jnp.float32)
            p2 = rest.astype(jnp.bfloat16)
            p3 = (rest - p2.astype(jnp.float32)).astype(jnp.bfloat16)
            pv = jax.lax.dot_general(
                jnp.concatenate([p1, p2, p3]), v.astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)    # [3 n_q, hd]
            pv = pv[:n_q] + pv[n_q:2 * n_q] + pv[2 * n_q:]
        else:
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)    # [n_q, hd]
        acc[:] = acc[:] * alpha[:, None] + pv
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)

    @pl.when(g == pl.num_programs(1) - 1)
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
    if hd % 128 and not interpret:
        # a block's copy moves whole 128-lane tiles of the pool
        raise ValueError(
            f"the compiled kernel copies pool blocks whose head dim is "
            f"a multiple of 128, got {hd}: use impl='xla'")
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

    # A block as the body takes it: [block_size * n_kv, hd] rows, row =
    # cell * n_kv + head, the pool's own order (a bitcast where the
    # heads fill the pool's tiles, as 8 heads of 128 do)
    n_layers, num_blocks = k_pool.shape[:2]
    block_rows = block_size * n_kv
    k_pool = k_pool.reshape(n_layers, num_blocks, block_rows, hd)
    v_pool = v_pool.reshape(n_layers, num_blocks, block_rows, hd)
    g_blocks = group_blocks(nb, block_size, n_kv, hd,
                            k_pool.dtype.itemsize)
    groups = pl.cdiv(nb, g_blocks)
    tokens = g_blocks * block_size
    # the mask by group; the last group's cells past the table are
    # holes. `spread` takes a cell's bit to its n_kv columns (_kernel)
    mask = jnp.pad(kv_mask.astype(jnp.int32),
                   ((0, 0), (0, groups * tokens - width))).reshape(
        b, groups, 1, tokens)
    span = math.gcd(tokens, 128)
    spread = jnp.asarray(np.repeat(np.eye(span), n_kv, axis=1),
                         jnp.bfloat16)

    def mask_map(b_i, g, pos_ref, tab_ref, layer_ref):
        # Clamped to the row's live groups: a step outside them asks
        # for the block it already has, which is no new DMA.
        pos = pos_ref[b_i]
        hi = jnp.clip(pos, 0, width - 1) // tokens
        if window is None:
            return (b_i, jnp.minimum(g, hi), 0, 0)
        lo = jnp.clip(pos - window + 1, 0, width - 1) // tokens
        return (b_i, jnp.clip(g, lo, hi), 0, 0)

    def row_map(b_i, g, pos_ref, tab_ref, layer_ref):
        return (b_i, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, groups),
        in_specs=[
            pl.BlockSpec((1, 1, n_q, hd), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, 1, tokens), mask_map),
            pl.BlockSpec(spread.shape, lambda *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, n_q, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, g_blocks, block_rows, hd), k_pool.dtype),
            pltpu.VMEM((2, g_blocks, block_rows, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, g_blocks)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n_q, hd), jnp.float32),
            pltpu.VMEM((n_q, 128), jnp.float32),
            pltpu.VMEM((n_q, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=hd**-0.5, window=window, block_size=block_size,
        nb=nb, g_blocks=g_blocks, n_kv=n_kv, group=group, rows=b,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(positions, table, layer.reshape(1), q, k_pool, v_pool, mask, spread)
