"""Fused prefill/append attention over the PAGED KV pool (Pallas TPU).

The chunked-prefill and speculative-verify paths both feed s >= 1 NEW
tokens per row into a paged cache and attend them against everything
written so far (prefix blocks + the new tokens themselves). The XLA
route is scatter-then-gather: write the s new K/V cells through the
block table, then re-read the row's FULL `[blocks_per_slot *
block_size]` window for attention — the new cells make a round trip
through HBM and the dead tail streams through on every chunk. This
kernel fuses the two:

- grid = (rows, blocks_per_slot); each row's APPEND CURSOR (`q_start`),
  valid-token count (`q_lens`) and BLOCK TABLE are scalar-prefetched,
  so the K/V BlockSpec index maps resolve `table[row, j]` before the
  body runs and DMA only live physical blocks (iterations outside
  [window lo, append hi] are clamped — a repeated physical index skips
  the DMA, as in paged_attention.py);
- the pool is rank 5, `[L, num_blocks, block_size, n_kv, hd]`, and the
  LAYER a fourth scalar-prefetched operand, as in paged_attention.py:
  the index maps return `(layer, table[row, j], 0, 0, 0)` over a
  squeezed leading block dimension. `input_output_aliases` covers the
  WHOLE array, so a call rewrites the visited blocks of layer `layer`
  and every other byte (the other layers, the unvisited blocks) stays
  where it is; a caller whose layers share one array (the engines'
  scan carry) neither slices a layer out nor writes it back. One
  layer's pool (rank 4, no `layer`) is lifted to `[1, ...]` at layer
  0 and comes back in the rank it was given;
- per visited block the body MERGES the new tokens in-register (a
  one-hot [block_size, s] matmul scatters token t to cell
  `q_start + t`), writes the merged block back to the pool via
  `input_output_aliases` (in place — the pool is never copied), and
  attends all s queries against the merged block with the shared
  online-softmax merge, masking causally by absolute cell index
  (`idx <= q_start + t`);
- every VISITED block is fully rewritten (blocks without new cells are
  rewritten with their own content): Pallas flushes the output buffer
  whenever its index map moves, so a visited-but-unwritten block would
  flush garbage. Unvisited blocks keep their pool content through the
  aliasing. Shared radix-chain blocks are rewritten with identical
  bytes (new cells land only at `idx >= q_start`, past any shared
  prefix), so cross-row revisits are benign; clamped revisits recompute
  the same merged content, so they are idempotent.

Cell index == logical token position is a precondition, as for the
decode kernel (insert-time compaction guarantees it). A second
precondition: each row's WRITE range `[q_start, q_start + q_lens)`
must lie in blocks no other row's table references (exclusively owned
generation-region blocks) — a write into a block another row reads or
writes in the same call races, because each row's input DMA sees the
pre-call pool, not earlier rows' merges. The serving layers satisfy
both by construction (radix sharing covers only the read-only seed
region below every sharer's cursor). Rows with `q_lens == 0` (group
padding) write nothing and produce garbage attention output the
caller discards.

Pinned against the XLA scatter+gather oracle
(`ops.paged_prefill_attention` impl="xla") by
tests/test_prefill_append_kernel.py in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.attention import NEG_INF, layered_pool
from kubeflow_tpu.ops.pallas.flash_attention import resolve_interpret


def _kernel(qs_ref, ql_ref, tab_ref, layer_ref, q_ref, kn_ref, vn_ref,
            kp_ref, vp_ref, mask_ref, o_ref, ko_ref, vo_ref, acc, m_scr,
            l_scr, *, scale, window, block_size, s, nb, n_kv, group, hd):
    # tab_ref and layer_ref feed the BlockSpec index maps; the body
    # needs cursors.
    del tab_ref, layer_ref
    b_i, bj = pl.program_id(0), pl.program_id(1)
    start = qs_ref[b_i]
    n_new = ql_ref[b_i]
    n_q = n_kv * group

    @pl.when(bj == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # Live range: the append's last cell bounds above; a sliding window
    # bounds below (blocks wholly older than the OLDEST query's band
    # are invisible to every query — and writes land at idx >= start,
    # always inside the band).
    relevant = bj * block_size <= start + s - 1
    if window is not None:
        relevant &= (bj * block_size + block_size - 1
                     >= start - window + 1)

    @pl.when(relevant)
    def _compute():
        # --- merge the new tokens into this block, in-register -------
        # cell i of logical block bj holds new token t iff its absolute
        # index equals the token's append position (and t is valid).
        idx_i = bj * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_size, s), 0)
        t_i = jax.lax.broadcasted_iota(jnp.int32, (block_size, s), 1)
        sel = (idx_i == start + t_i) & (t_i < n_new)     # [bs, s]
        written = jnp.any(sel, axis=1)                   # [bs]
        selv = sel.astype(jnp.float32)
        kn = kn_ref[0].astype(jnp.float32).reshape(s, n_kv * hd)
        vn = vn_ref[0].astype(jnp.float32).reshape(s, n_kv * hd)
        k_scat = jax.lax.dot_general(
            selv, kn, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(block_size, n_kv, hd)
        v_scat = jax.lax.dot_general(
            selv, vn, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(block_size, n_kv, hd)
        k_blk = jnp.where(written[:, None, None], k_scat,
                          kp_ref[0].astype(jnp.float32))
        v_blk = jnp.where(written[:, None, None], v_scat,
                          vp_ref[0].astype(jnp.float32))
        # full-block writeback (cast to pool dtype FIRST, then attend
        # the cast values — semantics are "attend what the pool holds",
        # matching the XLA scatter-then-gather oracle bit for bit when
        # pool dtype narrows)
        ko_ref[0] = k_blk.astype(ko_ref.dtype)
        vo_ref[0] = v_blk.astype(vo_ref.dtype)
        k_att = ko_ref[0].astype(jnp.float32)
        v_att = vo_ref[0].astype(jnp.float32)

        # --- online-softmax attention of all s queries ---------------
        q = q_ref[0].astype(jnp.float32)                 # [s, n_q, hd]
        qg = q.reshape(s, n_kv, group, hd).transpose(1, 0, 2, 3)
        qg = qg.reshape(n_kv, s * group, hd)
        kt = jnp.swapaxes(k_att, 0, 1)                   # [n_kv, bs, hd]
        logits = jax.lax.dot_general(
            qg, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                  # [n_kv, s*group, bs]
        idx = bj * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (s, block_size), 1)
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (s, block_size), 0)
        visible = (idx <= qpos) & (mask_ref[0, 0] != 0)  # causal, pads
        if window is not None:
            visible &= (qpos - idx) < window
        vis = jnp.broadcast_to(
            visible[:, None, :], (s, group, block_size)
        ).reshape(1, s * group, block_size)
        logits = jnp.where(vis, logits, NEG_INF).reshape(
            n_kv * s * group, block_size)
        visf = jnp.broadcast_to(vis, (n_kv, s * group, block_size)
                                ).reshape(n_kv * s * group, block_size)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
        p = jnp.exp(logits - m_new[:, None])
        p = jnp.where(visf, p, 0.0)  # fully-masked rows contribute 0
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            (l_scr[:, 0] * alpha + jnp.sum(p, axis=1))[:, None],
            l_scr.shape)
        vg = jnp.swapaxes(v_att, 0, 1)                   # [n_kv, bs, hd]
        pv = jax.lax.dot_general(
            p.reshape(n_kv, s * group, block_size), vg,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(n_kv * s * group, hd)
        acc[:] = acc[:] * alpha[:, None] + pv
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)

    @pl.when(bj == nb - 1)
    def _finish():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = (acc[:] / safe_l[:, None]).reshape(n_kv, s, group, hd)
        o_ref[0] = out.transpose(1, 0, 2, 3).reshape(
            s, n_q, hd).astype(o_ref.dtype)


def vmem_bytes(s: int, n_q: int, n_kv: int, hd: int, block_size: int,
               itemsize: int) -> int:
    """Upper estimate of the VMEM one grid step holds, from the shapes:
    the kernel keeps all `s * n_q` query rows resident. The call asks
    for this much as `vmem_limit_bytes` where it exceeds Mosaic's 16 MiB
    default scoped limit (s=256 at n_q=16 needs 22.5 MiB), and
    `ops.attention`'s auto rule compares it with `VMEM_BUDGET_BYTES`."""
    rows = s * n_q
    lanes = max(block_size, 128)          # fp32 [rows, block_size] tiles
    scratch = rows * (hd + 2 * 128) * 4   # acc + lane-replicated m, l
    # double-buffered blocks: q and out rows, the new K/V, and the pool
    # block four times (K and V, in and out)
    blocks = 2 * itemsize * hd * (2 * rows + 2 * s * n_kv
                                  + 4 * block_size * n_kv)
    # fp32 body values: q and its head-major copy, pv and the acc read,
    # logits / probs / visibility, the merged K and V blocks
    body = 4 * (4 * rows * hd + 3 * rows * lanes
                + 4 * block_size * n_kv * hd)
    return scratch + blocks + body


# What `auto` lets this kernel ask for: half of the 128 MiB a v5e core
# has, so the rest of the program's fusions keep their share.
VMEM_BUDGET_BYTES = 64 * 2**20


def paged_prefill_append(
    q: jnp.ndarray,            # [b, s, n_q, hd]
    k_new: jnp.ndarray,        # [b, s, n_kv, hd]
    v_new: jnp.ndarray,        # [b, s, n_kv, hd]
    k_pool: jnp.ndarray,       # [(L,) num_blocks, block_size, n_kv, hd]
    v_pool: jnp.ndarray,       # [(L,) num_blocks, block_size, n_kv, hd]
    block_table: jnp.ndarray,  # [b, blocks_per_slot] int32 physical ids
    q_start: jnp.ndarray,      # [b] int32 — append cursor per row
    q_lens: jnp.ndarray,       # [b] int32 — valid new tokens per row
    kv_mask: jnp.ndarray | None = None,  # [b, blocks_per_slot*block_size]
    *,
    layer=None,                # int32 scalar, with a rank-5 pool
    window: int | None = None,
    interpret: bool | None = None,
):
    """Append s new tokens per row through the block table and attend
    them, in one pass over the live blocks. Returns
    `(out [b, s, n_q, hd], k_pool, v_pool)` with the pools updated IN
    PLACE (input_output_aliases). HBM traffic per row is one
    read+write of `ceil((q_start + s) / block_size)` blocks — the new
    cells never round-trip, and the table's trash tail is never read.
    """
    interpret = resolve_interpret(interpret)
    b, s, n_q, hd = q.shape
    if k_new.shape != v_new.shape or k_new.shape[:2] != (b, s):
        raise ValueError(
            f"k_new/v_new must be [b={b}, s={s}, n_kv, hd], got "
            f"{k_new.shape} / {v_new.shape}")
    given = k_pool.shape
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
    if q_start.shape != (b,) or q_lens.shape != (b,):
        raise ValueError(
            f"q_start/q_lens must be [b={b}], got {q_start.shape} / "
            f"{q_lens.shape}")
    if kv_mask is None:
        kv_mask = jnp.ones((b, width), bool)
    if kv_mask.shape != (b, width):
        raise ValueError(
            f"kv_mask must be [b={b}, {width}], got {kv_mask.shape}")
    starts = q_start.astype(jnp.int32)
    lens = q_lens.astype(jnp.int32)
    table = block_table.astype(jnp.int32)

    # Clamped logical block index: the live range is [window lo, append
    # hi]; out-of-range iterations repeat a boundary block (no DMA) and
    # `pl.when` gates the compute — same scheme as paged_attention.py.
    def _clamp(bj, start):
        hi = (start + s - 1) // block_size
        if window is None:
            return jnp.minimum(bj, hi)
        lo = jnp.maximum((start - window + 1) // block_size, 0)
        return jnp.clip(bj, lo, hi)

    def kv_map(b_i, bj, qs_ref, ql_ref, tab_ref, layer_ref):
        # the prefetched layer's block dimension is squeezed away
        return (layer_ref[0], tab_ref[b_i, _clamp(bj, qs_ref[b_i])],
                0, 0, 0)

    def mask_map(b_i, bj, qs_ref, ql_ref, tab_ref, layer_ref):
        return (b_i, _clamp(bj, qs_ref[b_i]), 0, 0)

    def row_map(b_i, bj, qs_ref, ql_ref, tab_ref, layer_ref):
        return (b_i, 0, 0, 0)

    kv_block = pl.BlockSpec((None, 1, block_size, n_kv, hd), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, s, n_q, hd), row_map),
            pl.BlockSpec((1, s, n_kv, hd), row_map),
            pl.BlockSpec((1, s, n_kv, hd), row_map),
            kv_block,
            kv_block,
            pl.BlockSpec((1, 1, 1, block_size), mask_map),
        ],
        out_specs=[pl.BlockSpec((1, s, n_q, hd), row_map),
                   kv_block, kv_block],
        scratch_shapes=[
            pltpu.VMEM((s * n_q, hd), jnp.float32),
            pltpu.VMEM((s * n_q, 128), jnp.float32),
            pltpu.VMEM((s * n_q, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=hd**-0.5, window=window, block_size=block_size,
        s=s, nb=nb, n_kv=n_kv, group=group, hd=hd,
    )
    # operand order: 4 prefetch scalars, then q, k_new, v_new, k_pool,
    # v_pool, kv_mask — the WHOLE pools (operands 7/8) alias outputs
    # 1/2, so the layers the index map never names keep their bytes.
    out, k_pool, v_pool = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 * 2**20, vmem_bytes(
                s, n_q, n_kv, hd, block_size, q.dtype.itemsize))),
        interpret=interpret,
        name="prefill_append",
    )(starts, lens, table, layer.reshape(1), q, k_new, v_new, k_pool,
      v_pool, kv_mask.astype(jnp.int32).reshape(b, nb, 1, block_size))
    return out, k_pool.reshape(given), v_pool.reshape(given)
