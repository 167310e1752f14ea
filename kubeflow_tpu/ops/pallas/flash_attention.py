"""Flash attention as Pallas TPU kernels (fwd + custom-VJP bwd).

Blockwise attention that never materializes the [s, s] score matrix:
Q blocks stay VMEM-resident while K/V blocks stream through, merging
into an online-softmax accumulator — O(block_q * block_k) VMEM instead
of O(s^2) HBM, with every matmul landing on the MXU in fp32 accumulation.

Backward is the standard two-kernel formulation (saved row logsumexp +
recomputed probabilities):
  - dq kernel:   grid over Q blocks, streaming K/V blocks;
  - dk/dv kernel: grid over K blocks, streaming Q/dO blocks.
GQA is handled by index-mapping each query head onto its KV head inside
the BlockSpecs (KV never repeats in HBM); dk/dv come out at query-head
resolution and are group-summed outside the kernel.

Causal masking is by absolute row/col block index — packed sequences with
position resets must use the XLA path (see ops.attention dispatcher).

Reference parity: the reference has no attention/compute code at all
(SURVEY.md §2b); this is the TPU-native hot-op layer BASELINE.json's
tokens/sec/chip metric exercises.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.attention import NEG_INF


def _apply_causal_mask(logits, qi, ki, block_q, block_k, window):
    rows = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    q_pos = qi * block_q + rows
    k_pos = ki * block_k + cols
    mask = q_pos >= k_pos
    if window is not None:
        # sliding window: attend the last `window` positions (self incl.)
        mask &= (q_pos - k_pos) < window
    return jnp.where(mask, logits, NEG_INF)


def _block_relevant(qi, ki, block_q, block_k, window):
    """Trace-time predicate: does (q block, k block) intersect the
    causal band at all? Above-diagonal blocks skip always; with a
    window, blocks entirely OLDER than the band skip too."""
    newest_q = qi * block_q + block_q - 1
    keep = ki * block_k <= newest_q
    if window is not None:
        oldest_q = qi * block_q
        newest_k = ki * block_k + block_k - 1
        keep &= newest_k > oldest_q - window
    return keep

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


# Interpret mode is the tests' vehicle and only theirs: the kernels in
# this package compile for the TPU, and nothing on the serving/training
# path may infer "interpret" from the backend — a host whose chip failed
# to attach would then run the "kernels" interpreted on the CPU and
# report success. tests/conftest.py turns this on for the CPU suite.
_forced_interpret = False


@contextlib.contextmanager
def force_interpret(on: bool = True):
    """Run every kernel of this package in Pallas interpret mode inside
    the block (unless a call passes `interpret=` itself). Resolved at
    trace time: a jit cached outside the block keeps what it traced."""
    global _forced_interpret
    prev, _forced_interpret = _forced_interpret, on
    try:
        yield
    finally:
        _forced_interpret = prev


def resolve_interpret(interpret: bool | None) -> bool:
    """`interpret=None` means compiled, unless a test forced interpret
    mode. Compiling a TPU kernel on another backend is an error, never
    a quiet switch to the interpreter."""
    if interpret is None:
        interpret = _forced_interpret
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"Pallas TPU kernel requested on backend "
            f"{jax.default_backend()!r}: use the XLA impl, or pass "
            "interpret=True (tests: ops.pallas.force_interpret)")
    return interpret


def _pick_block(s: int, block: int) -> int:
    b = min(block, s)
    while s % b:
        b //= 2
    return max(b, 1)


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
                *, scale, causal, window, block_q, block_k, nk):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # [bq, bk]
        if causal:
            logits = _apply_causal_mask(logits, qi, ki, block_q, block_k,
                                        window)

        m_prev = m_scr[:, 0]                          # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
        p = jnp.exp(logits - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
        acc[:] = acc[:] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    if causal:
        # Skip blocks strictly above the diagonal, and (with a sliding
        # window) blocks entirely older than the attention band.
        @pl.when(_block_relevant(qi, ki, block_q, block_k, window))
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / safe_l[:, None]).astype(o_ref.dtype)
        lse = m_scr[:, 0] + jnp.log(safe_l)
        # lane-replicated rows: TPU blocks need the trailing dims tiled
        # (8, 128), so per-row scalars are stored [s, 128] like the
        # in-tree kernel's l/m residuals.
        lse_ref[0, 0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[2:])


def _fwd(q4, k4, v4, *, causal, window, block_q, block_k, interpret):
    """q4: [b, nq, s, hd]; k4: [b, nkv, s, hd]; v4: [b, nkv, s, hdv]
    (each block takes its array's own last dimension, so `v` may have
    another head size than `q`/`k`) → (o4 [b, nq, s, hdv], lse)."""
    b, nq, s, hd = q4.shape
    nkv, hdv = k4.shape[1], v4.shape[3]
    g = nq // nkv
    scale = hd**-0.5
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(s, block_k)
    nqb, nkb = s // block_q, s // block_k

    grid = (b * nq, nqb, nkb)
    q_spec = pl.BlockSpec(
        (1, 1, block_q, hd),
        lambda bh, qi, ki: (bh // nq, bh % nq, qi, 0),
    )
    def kv_spec(width):
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda bh, qi, ki: (bh // nq, (bh % nq) // g, ki, 0),
        )

    o_spec = pl.BlockSpec(
        (1, 1, block_q, hdv),
        lambda bh, qi, ki: (bh // nq, bh % nq, qi, 0),
    )
    lse_spec = pl.BlockSpec(
        (1, 1, block_q, 128),
        lambda bh, qi, ki: (bh // nq, bh % nq, qi, 0),
    )

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nkb,
    )
    o4, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec(hd), kv_spec(hdv)],
        out_specs=[o_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, nq, s, hdv), q4.dtype),
            jax.ShapeDtypeStruct((b, nq, s, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hdv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q4, k4, v4)
    return o4, lse


# --------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, window, block_q, block_k, nk):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, 0]                     # [bq]
        delta = delta_ref[0, 0][:, 0]                 # [bq]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            logits = _apply_causal_mask(logits, qi, ki, block_q, block_k,
                                        window)
        p = jnp.exp(logits - lse[:, None])            # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None])
        dq_acc[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32) * scale

    if causal:
        @pl.when(_block_relevant(qi, ki, block_q, block_k, window))
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, causal, window, block_q, block_k, nq_blocks):
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, 0]
        delta = delta_ref[0, 0][:, 0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # [bq, bk]
        if causal:
            logits = _apply_causal_mask(logits, qi, ki, block_q, block_k,
                                        window)
        p = jnp.exp(logits - lse[:, None])
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None])                # [bq, bk]
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    if causal:
        # Q blocks strictly above the diagonal see none of this K block
        # (and with a window, q blocks entirely newer than the band).
        @pl.when(_block_relevant(qi, ki, block_q, block_k, window))
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == nq_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(causal, window, block_q, block_k, interpret, res, do4):
    q4, k4, v4, o4, lse = res
    b, nq, s, hd = q4.shape
    nkv, hdv = k4.shape[1], v4.shape[3]
    g = nq // nkv
    scale = hd**-0.5
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(s, block_k)
    nqb, nkb = s // block_q, s // block_k

    delta = jnp.sum(do4.astype(jnp.float32) * o4.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 128))

    def q_spec(width):
        return pl.BlockSpec(
            (1, 1, block_q, width),
            lambda bh, qi, ki: (bh // nq, bh % nq, qi, 0))

    def kv_spec(width):
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda bh, qi, ki: (bh // nq, (bh % nq) // g, ki, 0))

    row_spec = pl.BlockSpec(
        (1, 1, block_q, 128),
        lambda bh, qi, ki: (bh // nq, bh % nq, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q,
                          block_k=block_k, nk=nkb),
        grid=(b * nq, nqb, nkb),
        in_specs=[q_spec(hd), kv_spec(hd), kv_spec(hdv), q_spec(hdv),
                  row_spec, row_spec],
        out_specs=q_spec(hd),
        out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(q4, k4, v4, do4, lse, delta)

    # dk/dv at query-head resolution; kv-head index maps stream the same
    # K/V block to every query head in the group.
    def q_spec2(width):
        return pl.BlockSpec(
            (1, 1, block_q, width),
            lambda bh, ki, qi: (bh // nq, bh % nq, qi, 0))

    def kv_spec2(width):
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda bh, ki, qi: (bh // nq, (bh % nq) // g, ki, 0))

    row_spec2 = pl.BlockSpec(
        (1, 1, block_q, 128),
        lambda bh, ki, qi: (bh // nq, bh % nq, qi, 0))

    def dkv_out_spec(width):
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda bh, ki, qi: (bh // nq, bh % nq, ki, 0))

    dk_full, dv_full = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q,
                          block_k=block_k, nq_blocks=nqb),
        grid=(b * nq, nkb, nqb),
        in_specs=[q_spec2(hd), kv_spec2(hd), kv_spec2(hdv), q_spec2(hdv),
                  row_spec2, row_spec2],
        out_specs=[dkv_out_spec(hd), dkv_out_spec(hdv)],
        out_shape=[
            jax.ShapeDtypeStruct((b, nq, s, hd), k4.dtype),
            jax.ShapeDtypeStruct((b, nq, s, hdv), v4.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hdv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_dkv",
    )(q4, k4, v4, do4, lse, delta)

    # Group-sum query-head gradients onto their KV head.
    dk = dk_full.reshape(b, nkv, g, s, hd).sum(axis=2).astype(k4.dtype)
    dv = dv_full.reshape(b, nkv, g, s, hdv).sum(axis=2).astype(v4.dtype)
    return dq, dk, dv


# -------------------------------------------------------------- public API


# Block-level entry points for ring attention (parallel.ring): the ring
# composes per-KV-shard kernel calls itself — forward merges per-block
# (o, lse) online, backward re-runs these kernels per visiting block
# against the FINAL (o, lse) residuals, which is mathematically the
# whole-sequence flash bwd split along KV blocks (p = exp(logits - LSE)
# and delta = rowsum(do*o_final) are both global quantities).
def flash_block_fwd(q4, k4, v4, *, causal, interpret, window=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """[b, n, s, hd] tensors -> (normalized o4, lse[b, nq, s, 128])."""
    return _fwd(q4, k4, v4, causal=causal, window=window,
                block_q=block_q, block_k=block_k, interpret=interpret)


def flash_block_bwd(res, do4, *, causal, interpret, window=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """res = (q4, k4, v4, o4, lse128) — o4/lse may be the MERGED ring
    totals; returns (dq4, dk4, dv4) with GQA group-summing applied."""
    return _bwd(causal, window, block_q, block_k, interpret, res, do4)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q4, k4, v4, causal, window, block_q, block_k, interpret):
    o4, _ = _fwd(q4, k4, v4, causal=causal, window=window,
                 block_q=block_q, block_k=block_k, interpret=interpret)
    return o4


def _flash_fwd(q4, k4, v4, causal, window, block_q, block_k, interpret):
    o4, lse = _fwd(q4, k4, v4, causal=causal, window=window,
                   block_q=block_q, block_k=block_k, interpret=interpret)
    return o4, (q4, k4, v4, o4, lse)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, do4):
    return _bwd(causal, window, block_q, block_k, interpret, res, do4)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,  # [b, s, n_q, hd]
    k: jnp.ndarray,  # [b, s, n_kv, hd]
    v: jnp.ndarray,  # [b, s, n_kv, hd_v]: its own head size
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Flash attention with GQA, differentiable (custom VJP).

    Layout contract matches ops.attention.dot_product_attention:
    [batch, seq, heads, head_dim] in/out; the output has `v`'s head
    size, the softmax scale is `q`'s. `interpret`: see
    `resolve_interpret` (compiled unless a test says otherwise).
    """
    interpret = resolve_interpret(interpret)
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    b, s, n_q, hd = q.shape
    n_kv = k.shape[2]
    if n_q % n_kv:
        raise ValueError(f"n_q={n_q} not a multiple of n_kv={n_kv}")
    if k.shape[1] != s:
        raise ValueError("flash kernel requires equal q/kv sequence lengths")
    def kernel(q, k, v):
        q4 = jnp.transpose(q, (0, 2, 1, 3))
        k4 = jnp.transpose(k, (0, 2, 1, 3))
        v4 = jnp.transpose(v, (0, 2, 1, 3))
        o4 = _flash(q4, k4, v4, causal, window, block_q, block_k,
                    interpret)
        return jnp.transpose(o4, (0, 2, 1, 3))

    # lazy: parallel/ imports ops.attention, which imports this module
    from kubeflow_tpu.parallel.sharding import per_shard

    # batch rows and heads are independent, and contiguous head shards
    # keep each query group with its KV head — the layout the model's
    # own activation constraints already ask for
    q_axes = ("batch", "seq", "act_heads", None)
    kv_axes = ("batch", "seq", "act_kv_heads", None)
    return per_shard(kernel, (q_axes, kv_axes, kv_axes), q_axes)(q, k, v)
