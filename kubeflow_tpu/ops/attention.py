"""Attention: XLA reference path + TPU Pallas flash-attention dispatch.

Design: one public `dot_product_attention` that dispatches by backend.
- CPU / debugging: pure-XLA grouped-query attention with fp32 logits.
- TPU: Pallas flash attention kernel (kubeflow_tpu.ops.pallas.flash_attention)
  for long sequences, XLA for short ones (XLA's fused attention is
  already good below ~1k tokens). Every choice "auto" makes is a rule on
  the platform and the call's shapes, written here; nothing is chosen by
  catching an error.

The XLA path never materializes repeated KV heads: queries are reshaped to
[batch, q_per_kv, kv_heads, ...] and contracted against the kv heads
directly — keeps HBM traffic at the GQA level, which is the point of GQA.

Each public entry opens the `jax.named_scope` of the kernel whose work
it does — `flash_attention`, `decode_attention`, `paged_attention`,
`prefill_append` — whichever implementation runs it, so a device trace
reads the same work under "xla" and "pallas" by one name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0**30  # large-but-finite: avoids NaNs from (-inf) - (-inf)

# Trace-time dispatch counters. `dot_product_attention` runs in Python at
# trace time, so these count how many traced call sites took each impl —
# which is how bench.py and chip_smoke.py *prove* which kernel a step
# was traced with.
_impl_counts = {"flash": 0, "xla": 0, "decode": 0, "paged": 0,
                "paged_xla": 0, "paged_pallas": 0, "paged_prefill": 0,
                "paged_prefill_xla": 0, "paged_prefill_pallas": 0}


def reset_impl_counts() -> None:
    for key in _impl_counts:
        _impl_counts[key] = 0


def impl_counts() -> dict[str, int]:
    return dict(_impl_counts)


def _xla_attention(
    q: jnp.ndarray,            # [b, sq, n_q, hd]
    k: jnp.ndarray,            # [b, skv, n_kv, hd]
    v: jnp.ndarray,            # [b, skv, n_kv, hd_v]
    q_positions: jnp.ndarray,  # [b, sq]
    kv_positions: jnp.ndarray, # [b, skv]
    *,
    causal: bool,
    kv_mask: jnp.ndarray | None,  # [b, skv] bool, False = padded/invalid
    window: int | None = None,
) -> jnp.ndarray:
    b, sq, n_q, hd = q.shape
    n_kv = k.shape[2]
    assert n_q % n_kv == 0, (n_q, n_kv)
    group = n_q // n_kv
    scale = hd**-0.5

    qg = q.reshape(b, sq, n_kv, group, hd)
    # logits: [b, n_kv, group, sq, skv] in fp32
    logits = jnp.einsum(
        "bsngh,btnh->bngst", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale

    mask = jnp.ones((b, sq, k.shape[1]), dtype=bool)
    if causal:
        mask &= q_positions[:, :, None] >= kv_positions[:, None, :]
    if window is not None:
        # sliding window: each query attends its last `window` positions
        mask &= (q_positions[:, :, None]
                 - kv_positions[:, None, :]) < window
    if kv_mask is not None:
        mask &= kv_mask[:, None, :]
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngst,btnh->bsngh", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, n_q, v.shape[-1]).astype(q.dtype)


def _check_impl(impl: str, what: str) -> None:
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"{what} impl must be 'auto', 'xla' or 'pallas', got {impl!r}")


def layered_pool(k_pool, v_pool, layer):
    """The paged entries' pool as `[L, num_blocks, block_size, *cell]`
    plus an int32 scalar layer (`cell` is a token's K, or V, of one
    layer, in one of `pool_cell_shape`'s forms). What decides is the
    pool's rank: a rank-5 pool (every layer's, the serving engines'
    scan carry) comes with the layer to read; a rank-4 pool is one
    layer's and is lifted to `[1, ...]` at layer 0 — a bitcast, so
    both reach one kernel body. -> (k_pool, v_pool, layer)."""
    if k_pool.shape != v_pool.shape:
        raise ValueError(
            f"k_pool/v_pool shapes disagree: {k_pool.shape} vs "
            f"{v_pool.shape}")
    if k_pool.ndim == 4 and layer is None:
        return k_pool[None], v_pool[None], jnp.int32(0)
    if k_pool.ndim == 5 and layer is not None:
        return k_pool, v_pool, jnp.asarray(layer, jnp.int32)
    raise ValueError(
        "a pool [L, num_blocks, block_size, *cell] comes with a "
        "layer, a pool [num_blocks, block_size, *cell] without one; "
        f"got pools {k_pool.shape} / {v_pool.shape} and layer "
        + ("None" if layer is None
           else f"of shape {jnp.shape(layer)}"))


def _kernel_copies_heads_of(head_dim: int | None) -> bool:
    """The compiled paged kernels copy pool blocks in whole 128-lane
    tiles, so they take heads whose size is a multiple of 128 and
    raise on any other (ops/pallas/paged_attention.py). A caller that
    does not say the size is not judged by it."""
    return head_dim is None or head_dim % 128 == 0


def pool_cell_shape(n_kv: int, head_dim: int) -> tuple[int, int]:
    """The form `(rows, lanes)` of one KV pool cell: a token's K, or V,
    of one layer, the two minor dimensions of the pool
    `[L, num_blocks, block_size, rows, lanes]`. Heads the paged kernels
    copy (a multiple of 128: whole lane tiles) lie a head a row,
    `(n_kv, head_dim)`. Any other size would leave part of every
    128-lane tile empty, and the chip then rests the pool in a layout of
    its own (the block index minor) which no program works on: each
    copied the pool whole on entry, on exit and around every scatter
    (PERF.md, PR 37). So there a cell's heads lie side by side in one
    row, `(1, n_kv * head_dim)`: the same bytes in the same order. The
    one place that decides the form; the paged entries below take
    either, from the shapes."""
    if _kernel_copies_heads_of(head_dim):
        return n_kv, head_dim
    return 1, n_kv * head_dim


def _split_heads(pool, head_dim: int):
    """A pool `[L, num_blocks, block_size, *cell]` in either form of
    its cell -> (n_kv, the pool's shape with the cell as
    `[n_kv, head_dim]`)."""
    rows, lanes = pool.shape[3:]
    if (rows * lanes) % head_dim:
        raise ValueError(
            f"a pool cell {pool.shape[3:]} does not hold whole heads of "
            f"{head_dim} (pool {pool.shape})")
    n_kv = rows * lanes // head_dim
    return n_kv, pool.shape[:3] + (n_kv, head_dim)


def resolve_paged_prefill_impl(impl: str, *, vmem_bytes: int = 0,
                               head_dim: int | None = None) -> str:
    """Resolve a `paged_prefill_attention` impl request to "xla" or
    "pallas". "auto" is a rule on platform and shape, nothing else: the
    fused kernel on TPU while the VMEM it needs for the call's shapes
    (`prefill_append.vmem_bytes`, which grows with the chunk's
    `s * n_q` query rows) fits `VMEM_BUDGET_BYTES` and the heads are a
    size it copies (`head_dim`, a multiple of 128); the XLA
    scatter+gather for longer chunks, other heads and on every other
    backend. Without `vmem_bytes` or `head_dim` only the rest is
    judged — what an engine can say before it has seen a chunk."""
    _check_impl(impl, "paged prefill")
    if impl != "auto":
        return impl
    from kubeflow_tpu.ops.pallas.prefill_append import VMEM_BUDGET_BYTES

    if (jax.default_backend() == "tpu" and vmem_bytes <= VMEM_BUDGET_BYTES
            and _kernel_copies_heads_of(head_dim)):
        return "pallas"
    return "xla"


def resolve_paged_attention_impl(impl: str, *,
                                 head_dim: int | None = None) -> str:
    """Resolve a `paged_attention` impl request to "xla" or "pallas".

    "auto" is the fused Pallas kernel on TPU for heads of a size it
    copies (`head_dim`, a multiple of 128; not judged where it is not
    given), and the XLA gather for other heads and on every other
    backend (there the kernel runs only where a test asks for
    interpret mode). Resolving once at engine construction (rather
    than per trace) is what lets serving label its metrics with the
    impl that actually runs.
    """
    _check_impl(impl, "paged attention")
    if impl == "auto":
        return ("pallas" if jax.default_backend() == "tpu"
                and _kernel_copies_heads_of(head_dim) else "xla")
    return impl


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    *,
    causal: bool = True,
    kv_mask: jnp.ndarray | None = None,
    window: int | None = None,
    impl: str = "auto",
    contiguous_positions: bool = False,
) -> jnp.ndarray:
    """Grouped-query attention. `window` limits each query to its last
    `window` positions (sliding-window attention; requires causal) —
    supported by both impls, position-based in XLA, index-based in flash.

    impl: "auto" | "xla" | "flash" | "decode". "auto" picks, on TPU:
    the Pallas flash kernel for long sequences when safe (no kv_mask,
    positions declared contiguous), or the fused
    decode kernel for single-token causal steps against a >=256-cell
    cache (again only with `contiguous_positions=True` — it masks by
    cache cell index against each row's cursor). Packed sequences with
    per-segment position resets, and caches whose cell index is not
    the token position, MUST take the XLA path, which masks by the
    actual position tensors.
    """
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        long_seq = q.shape[1] >= 1024 and q.shape[1] % 512 == 0
        same_len = q.shape[1] == k.shape[1]
        # One query token against a longer cache = the serving decode
        # step. The fused kernel skips cache blocks past each row's
        # cursor (HBM traffic tracks fill, not max_len) — worthwhile
        # once the cache is big enough to block (>= 256 cells). It
        # masks by CACHE CELL INDEX, so like flash it needs the
        # caller's declaration that positions are cell indices
        # (`contiguous_positions=True`) — a packed/rotated cache whose
        # cell index != token position MUST take the XLA path, which
        # compares the actual position tensors.
        decode_step = (q.shape[1] == 1 and k.shape[1] >= 256
                       and causal and contiguous_positions)
        if (on_tpu and long_seq and same_len and causal
                and kv_mask is None and contiguous_positions):
            impl = "flash"
        elif on_tpu and decode_step:
            impl = "decode"
        else:
            impl = "xla"
    _impl_counts[impl] = _impl_counts.get(impl, 0) + 1
    # the scope names the work by its shape, not by who does it: a
    # full-sequence call is flash attention's, a one-token step against
    # a cache the decode kernel's
    with jax.named_scope("decode_attention" if q.shape[1] == 1
                         and k.shape[1] > 1 else "flash_attention"):
        return _attention(q, k, v, q_positions, kv_positions, causal=causal,
                          kv_mask=kv_mask, window=window, impl=impl,
                          contiguous_positions=contiguous_positions)


def _attention(q, k, v, q_positions, kv_positions, *, causal, kv_mask,
               window, impl, contiguous_positions):
    """`dot_product_attention` once the impl is resolved."""
    if impl == "decode":
        if q.shape[1] != 1:
            raise ValueError("impl='decode' is for single-token steps")
        if not contiguous_positions:
            raise ValueError(
                "impl='decode' masks by cache cell index: the caller "
                "must declare cell index == token position "
                "(contiguous_positions=True); packed/rotated caches "
                "must use impl='xla'")
        if not causal:
            # the kernel masks idx <= cursor unconditionally; a
            # bidirectional single-query lookup would silently lose
            # the cells past the cursor (same discipline as the
            # flash door's unsupported-combo raises)
            raise ValueError("impl='decode' is causal-only")
        from kubeflow_tpu.ops.pallas.decode_attention import (
            decode_attention,
        )

        return decode_attention(
            q, k, v, q_positions[:, 0], kv_mask, window=window)
    if impl == "flash":
        if kv_mask is not None or not contiguous_positions:
            raise ValueError(
                "impl='flash' masks by row/col index only: it supports "
                "neither kv_mask nor non-contiguous positions (pass "
                "contiguous_positions=True for plain causal batches, or "
                "use impl='xla')"
            )
        from kubeflow_tpu.ops.pallas.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, window=window)
    return _xla_attention(
        q, k, v, q_positions, kv_positions, causal=causal,
        kv_mask=kv_mask, window=window,
    )


@jax.named_scope("paged_attention")
def paged_attention(
    q: jnp.ndarray,            # [b, 1, n_q, hd] — single decode step
    k_pool: jnp.ndarray,       # [(L,) num_blocks, block_size, *cell]
    v_pool: jnp.ndarray,       # [(L,) num_blocks, block_size, *cell]
    block_table: jnp.ndarray,  # [b, blocks_per_slot] int32 physical ids
    q_positions: jnp.ndarray,  # [b, 1]
    kv_positions: jnp.ndarray, # [b, blocks_per_slot * block_size]
    *,
    causal: bool = True,
    kv_mask: jnp.ndarray | None = None,  # [b, blocks_per_slot * block_size]
    window: int | None = None,
    layer=None,                # int32 scalar, with a rank-5 pool
    impl: str = "xla",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Decode attention against a paged KV cache.

    The pool is one layer's (rank 4) or every layer's with `layer`
    saying which to read (rank 5; see `layered_pool`): a caller whose
    layers share one array hands it over whole, and neither impl
    materialises the layer's slice. A cell of it is `[n_kv, hd]` or
    `[1, n_kv * hd]` (`pool_cell_shape`): `hd` is `q`'s, and `n_kv` is
    what the cell holds of it.

    impl: "auto" | "xla" | "pallas".

    - "xla" (default): each row's K/V is gathered from the block pool
      through its table, then fed to the same grouped-query attention
      as the dense path. Because masked cells contribute an exact +0.0
      to the softmax sums (NEG_INF logits underflow to 0.0 in fp32
      exp), the gathered layout is bit-identical to a dense cache
      holding the same tokens at the same logical cells — which is
      what lets the tests compare paged decode against dense decode
      exactly. The gather materializes the full
      `[b, blocks_per_slot * block_size]` K/V window per layer — fine
      for CPU and short-to-mid contexts, HBM-wasteful at long max_len.
    - "pallas": the fused kernel (ops/pallas/paged_attention.py) walks
      the block table IN-KERNEL — scalar-prefetched cursors clamp the
      DMA range to each row's live blocks, so HBM traffic tracks cache
      fill instead of the full window. Causal-only (it masks by cell
      index against the cursor, so it also requires the pool's
      cell-index == token-position invariant, which insert-time
      compaction guarantees). `interpret=True` runs the kernel in
      Pallas interpret mode — the tests' CPU vehicle; without it a
      non-TPU backend is an error.
    - "auto": pallas on TPU, xla on every other backend.

    The two impls agree to fp32 tolerance (online-softmax merge vs
    single-pass softmax); tests/test_paged_attention_kernel.py pins
    the kernel against this gather path as the numerics oracle.
    """
    b = q.shape[0]
    if block_table.ndim != 2 or block_table.shape[0] != b:
        raise ValueError(
            f"block_table must be [b={b}, blocks_per_slot], got "
            f"{block_table.shape}")
    k_pool, v_pool, layer = layered_pool(k_pool, v_pool, layer)
    blocks_per_slot = block_table.shape[1]
    block_size, hd = k_pool.shape[2], q.shape[-1]
    n_kv, split = _split_heads(k_pool, hd)
    width = blocks_per_slot * block_size
    # Geometry mismatches (a pool rebuilt with a different block_size
    # than the tables/masks were laid out for) used to surface as an
    # opaque reshape/gather shape error deep inside jit; check here
    # with the actual numbers instead.
    if kv_positions.shape != (b, width):
        raise ValueError(
            f"kv_positions shape {kv_positions.shape} does not match "
            f"blocks_per_slot * block_size = {blocks_per_slot} * "
            f"{block_size} = {width} (pool {k_pool.shape}, table "
            f"{block_table.shape})")
    if kv_mask is not None and kv_mask.shape != (b, width):
        raise ValueError(
            f"kv_mask shape {kv_mask.shape} does not match "
            f"blocks_per_slot * block_size = {blocks_per_slot} * "
            f"{block_size} = {width}")
    impl = resolve_paged_attention_impl(impl, head_dim=hd)
    _impl_counts["paged"] += 1
    _impl_counts["paged_" + impl] += 1
    if impl == "pallas":
        if not causal:
            # the kernel masks idx <= cursor unconditionally (same
            # door discipline as impl='decode')
            raise ValueError("impl='pallas' paged attention is "
                             "causal-only; use impl='xla'")
        from kubeflow_tpu.ops.pallas.paged_attention import (
            paged_decode_attention,
        )

        # the kernel takes a head a row: its own form where it compiles
        # (heads of 128), a view of the rows where a test interprets it
        # at smaller heads
        return paged_decode_attention(
            q, k_pool.reshape(split), v_pool.reshape(split), block_table,
            q_positions[:, 0], kv_mask, layer=layer, window=window,
            interpret=interpret)
    # the gathered window is split into heads, never the pool
    k = k_pool[layer, block_table].reshape(b, width, n_kv, hd)
    v = v_pool[layer, block_table].reshape(b, width, n_kv, hd)
    # impl="xla" said explicitly: "auto" would hand this single-token
    # step to the Pallas decode kernel on TPU, and "xla" must mean XLA
    # on every platform (it is the kernels' oracle).
    return dot_product_attention(
        q, k, v, q_positions, kv_positions, causal=causal,
        kv_mask=kv_mask, window=window, impl="xla",
    )


@jax.named_scope("prefill_append")
def paged_prefill_attention(
    q: jnp.ndarray,            # [b, s, n_q, hd] — s new tokens per row
    k_new: jnp.ndarray,        # [b, s, n_kv, hd]
    v_new: jnp.ndarray,        # [b, s, n_kv, hd]
    k_pool: jnp.ndarray,       # [(L,) num_blocks, block_size, *cell]
    v_pool: jnp.ndarray,       # [(L,) num_blocks, block_size, *cell]
    block_table: jnp.ndarray,  # [b, blocks_per_slot] int32 physical ids
    q_start: jnp.ndarray,      # [b] int32 — append cursor per row
    q_lens: jnp.ndarray | None = None,  # [b] int32 — valid new tokens
    *,
    kv_mask: jnp.ndarray | None = None,  # [b, blocks_per_slot*block_size]
    window: int | None = None,
    layer=None,                # int32 scalar, with a rank-5 pool
    impl: str = "xla",
    interpret: bool | None = None,
):
    """Append s new tokens per row into the paged KV pool and attend
    them against everything written so far. Returns
    `(out [b, s, n_q, hd], k_pool, v_pool)` — the serving primitive
    behind chunked prefill (the chunk's tokens) and speculative verify
    (the γ+1 draft-window tokens). The pools come back in the shape
    they were given (rank, and the cell's form: `[n_kv, hd]` or
    `[1, n_kv * hd]`, `pool_cell_shape`): with a rank-5 pool and `layer`
    (`layered_pool`) only layer `layer`'s visited blocks are written,
    and every other byte of the array stays where it is.

    Row r's token t lands at logical cell `q_start[r] + t` (physical:
    through the row's block table) and attends causally by absolute
    cell index — cell index == logical token position is a
    precondition, as for `paged_attention`. Tokens with `t >= q_lens[r]`
    are group padding: their K/V is routed to the trash block and their
    attention output is garbage the caller discards.

    impl: "auto" | "xla" | "pallas".
    - "xla" (default): scatter the new cells through the table with
      `.at[].set`, then gather the full window and run the shared XLA
      grouped-query attention — correct everywhere, but the new cells
      round-trip through HBM and the dead tail streams every chunk.
    - "pallas": the fused kernel (ops/pallas/prefill_append.py) merges
      the new tokens into each live block in-register, writes the pool
      in place (input_output_aliases) and attends in the same pass —
      one read+write of `ceil((q_start+s)/block_size)` blocks per row.
      Causal-only. `interpret`: as for `paged_attention`.
    - "auto": pallas on TPU while the chunk's VMEM need fits the
      kernel's budget (`resolve_paged_prefill_impl`), xla otherwise.
    """
    b, s, n_q, hd = q.shape
    given = k_pool.shape
    k_pool, v_pool, layer = layered_pool(k_pool, v_pool, layer)
    block_size = k_pool.shape[2]
    n_kv, split = _split_heads(k_pool, hd)
    if block_table.ndim != 2 or block_table.shape[0] != b:
        raise ValueError(
            f"block_table must be [b={b}, blocks_per_slot], got "
            f"{block_table.shape}")
    blocks_per_slot = block_table.shape[1]
    width = blocks_per_slot * block_size
    if q_lens is None:
        q_lens = jnp.full((b,), s, jnp.int32)
    if kv_mask is not None and kv_mask.shape != (b, width):
        raise ValueError(
            f"kv_mask shape {kv_mask.shape} does not match "
            f"blocks_per_slot * block_size = {blocks_per_slot} * "
            f"{block_size} = {width}")
    from kubeflow_tpu.ops.pallas.prefill_append import (
        paged_prefill_append,
        vmem_bytes,
    )

    impl = resolve_paged_prefill_impl(impl, head_dim=hd, vmem_bytes=vmem_bytes(
        s, n_q, n_kv, hd, block_size, q.dtype.itemsize))
    _impl_counts["paged_prefill"] += 1
    _impl_counts["paged_prefill_" + impl] += 1
    if impl == "pallas":
        # the kernel takes a head a row (as `paged_attention`'s does)
        out, k_pool, v_pool = paged_prefill_append(
            q, k_new, v_new, k_pool.reshape(split), v_pool.reshape(split),
            block_table, q_start, q_lens, kv_mask, layer=layer,
            window=window, interpret=interpret)
        return out, k_pool.reshape(given), v_pool.reshape(given)
    # XLA reference: scatter the new cells through the table (invalid
    # tokens to the trash block — the pool's garbage-write convention),
    # then gather and attend with the shared fp32 path.
    pos = (q_start[:, None].astype(jnp.int32)
           + jnp.arange(s, dtype=jnp.int32)[None, :])
    valid = jnp.arange(s)[None, :] < q_lens[:, None]
    safe = jnp.minimum(pos, width - 1)
    blk = jnp.take_along_axis(block_table, safe // block_size, axis=1)
    blk = jnp.where(valid, blk, 0)
    off = safe % block_size
    cell = k_pool.shape[3:]
    k_pool = k_pool.at[layer, blk, off].set(
        k_new.reshape(b, s, *cell).astype(k_pool.dtype))
    v_pool = v_pool.at[layer, blk, off].set(
        v_new.reshape(b, s, *cell).astype(v_pool.dtype))
    k = k_pool[layer, block_table].reshape(b, width, n_kv, hd)
    v = v_pool[layer, block_table].reshape(b, width, n_kv, hd)
    kv_positions = jnp.broadcast_to(
        jnp.arange(width, dtype=jnp.int32)[None, :], (b, width))
    out = _xla_attention(
        q, k, v, pos, kv_positions, causal=True, kv_mask=kv_mask,
        window=window)
    return out, k_pool.reshape(given), v_pool.reshape(given)
