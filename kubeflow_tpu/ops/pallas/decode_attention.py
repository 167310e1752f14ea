"""Fused decode attention over the KV cache (Pallas TPU kernel).

The decode step's attention is one query token per row against that
row's cache prefix. The XLA path computes masked scores over the FULL
[max_len] cache for every row — correct, but it streams the invalid
tail through HBM every token, and decode MBU is the whole game
(bench.py's roofline). This kernel (VERDICT r04 stretch #9):

- grid = (rows, kv blocks); each row's cursor is SCALAR-PREFETCHED so
  blocks wholly past the cursor are skipped — the BlockSpec index map
  clamps to the last needed block (a repeated index means no new DMA)
  and `pl.when` gates the compute, so HBM traffic tracks the cache
  FILL, not max_len;
- GQA stays at KV resolution in memory (queries reshape to
  [n_kv, group] inside the kernel; the cache never repeats);
- per-cell validity (the engines' left-pad holes) rides in as an int32
  mask laid out `[b, kv blocks, 1, block_k]` (see paged_attention.py
  for why not a bool `(1, block_k)` block); causality and sliding
  windows mask by absolute cell index against the prefetched cursor.

Numerics match ops.attention._xla_attention exactly in structure:
fp32 logits, one softmax over the visible set (single-pass here — the
online-softmax merge is algebraically the same sum).

A row whose visible set is empty (pad holes over its whole causal
prefix) comes out as exact zeros; the XLA path gives such a row the mean
of V. Only host-masked filler rows are ever in that state, and
tools/smoke_kernels.py pins the convention on the chip.

Reference parity: the reference has no attention code (SURVEY.md §2b);
this is the serving-side sibling of flash_attention.py, pinned against
the XLA oracle by tests/test_decode_attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.attention import NEG_INF
from kubeflow_tpu.ops.pallas.flash_attention import (
    _pick_block,
    resolve_interpret,
)

DEFAULT_BLOCK_K = 256


def _kernel(pos_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
            acc, m_scr, l_scr, *, scale, window, block_k, nk, n_kv,
            group):
    b_i, ki = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b_i]

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # Relevance: skip blocks past the cursor AND (with a sliding
    # window) blocks wholly older than the attention band — without
    # the lower bound, a window-1024 model at cursor 32k would stream
    # all 32k cells per token, the exact waste this kernel exists to
    # cut on the causal side.
    relevant = ki * block_k <= pos
    if window is not None:
        relevant &= (ki * block_k + block_k - 1) >= pos - window + 1

    @pl.when(relevant)
    def _compute():
        n_q = n_kv * group
        q = q_ref[0, 0].astype(jnp.float32)           # [n_q, hd]
        k = k_ref[0].astype(jnp.float32)              # [bk, n_kv, hd]
        qg = q.reshape(n_kv, group, -1)
        kt = jnp.swapaxes(k, 0, 1)                    # [n_kv, bk, hd]
        # [n_kv, group, bk]: batch over kv heads — GQA without repeat
        logits = jax.lax.dot_general(
            qg, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        logits = logits.reshape(n_q, block_k)

        idx = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (n_q, block_k), 1)
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
        v = v_ref[0].astype(jnp.float32)              # [bk, n_kv, hd]
        vg = jnp.swapaxes(v, 0, 1)                    # [n_kv, bk, hd]
        pv = jax.lax.dot_general(
            p.reshape(n_kv, group, block_k), vg,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(n_q, -1)                            # [n_q, hd]
        acc[:] = acc[:] * alpha[:, None] + pv
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / safe_l[:, None]).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,            # [b, 1, n_q, hd]
    k: jnp.ndarray,            # [b, max_len, n_kv, hd]
    v: jnp.ndarray,            # [b, max_len, n_kv, hd]
    q_positions: jnp.ndarray,  # [b] int32 — each row's cursor
    kv_mask: jnp.ndarray | None = None,  # [b, max_len] bool
    *,
    window: int | None = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One-token-per-row attention over each row's cache prefix."""
    interpret = resolve_interpret(interpret)
    b, sq, n_q, hd = q.shape
    if sq != 1:
        raise ValueError(f"decode_attention is s=1 only, got sq={sq}")
    max_len = k.shape[1]
    n_kv = k.shape[2]
    if n_q % n_kv:
        raise ValueError(f"{n_q} query heads not grouped by {n_kv} kv")
    group = n_q // n_kv
    if kv_mask is None:
        kv_mask = jnp.ones((b, max_len), bool)
    block_k = _pick_block(max_len, block_k)
    nk = max_len // block_k
    positions = q_positions.astype(jnp.int32)

    # Clamped index maps: iterations outside a row's needed block range
    # re-reference a boundary block — consecutive equal indices skip
    # the DMA, which is where the ragged saving comes from. The range
    # is [first block the window can see, cursor block].
    def _clamp(ki, pos):
        hi = pos // block_k
        if window is None:
            return jnp.minimum(ki, hi)
        lo = jnp.maximum((pos - window + 1) // block_k, 0)
        return jnp.clip(ki, lo, hi)

    def kv_map(b_i, ki, pos_ref):
        return (b_i, _clamp(ki, pos_ref[b_i]), 0, 0)

    def mask_map(b_i, ki, pos_ref):
        return (b_i, _clamp(ki, pos_ref[b_i]), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, 1, n_q, hd),
                         lambda b_i, ki, pos_ref: (b_i, 0, 0, 0)),
            pl.BlockSpec((1, block_k, n_kv, hd), kv_map),
            pl.BlockSpec((1, block_k, n_kv, hd), kv_map),
            pl.BlockSpec((1, 1, 1, block_k), mask_map),
        ],
        out_specs=pl.BlockSpec((1, 1, n_q, hd),
                               lambda b_i, ki, pos_ref: (b_i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_q, hd), jnp.float32),
            pltpu.VMEM((n_q, 128), jnp.float32),
            pltpu.VMEM((n_q, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=hd**-0.5, window=window, block_k=block_k,
        nk=nk, n_kv=n_kv, group=group,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(positions, q, k, v,
      kv_mask.astype(jnp.int32).reshape(b, nk, 1, block_k))
