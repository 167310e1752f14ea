"""Sequence/context parallelism: ring attention + Ulysses head-scatter.

Long-context attention over a sequence-sharded batch, the two TPU-idiomatic
layouts (SURVEY.md §5 "long-context"):

- **Ring attention** (`ring_attention`, `ring_attention_sharded`): each
  device keeps its Q shard resident and streams K/V shards around the ICI
  ring with `jax.lax.ppermute`, accumulating blockwise online-softmax
  partial results. O(s/N) activation memory per device, neighbor-only
  collectives (rides ICI links, never DCN). Explicit collectives via
  `shard_map` — this is deliberately NOT left to XLA: GSPMD would
  all-gather the full K/V.

- **Ulysses** (`ulysses_attention`): all-to-all swaps the sequence shard
  for a head shard, runs *full* local attention per head group, and swaps
  back. Cheaper when heads >= ring size and sequence fits after the swap;
  two all-to-alls instead of N-1 permutes.

Reference parity: the reference has no attention code of any kind
(SURVEY.md §2b row "SP/CP, ring attention"); this subsystem is green-field
TPU design.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.ops.attention import NEG_INF
from kubeflow_tpu.parallel import mesh as mesh_lib


def _block_attend(q, k, v, mask):
    """One blockwise-attention accumulation step (grouped-query, fp32).

    q: [b, sq, n_kv, g, hd]   (queries pre-grouped per kv head)
    k, v: [b, sk, n_kv, hd]
    mask: [b, sq, sk] bool (True = attend)
    Returns unnormalized (o, m, l) for online-softmax merging:
      o: [b, sq, n_kv, g, hd], m/l: [b, sq, n_kv, g]
    """
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bsngh,btnh->bngst", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                       # [b, n_kv, g, sq]
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bngst,btnh->bngsh", p, v.astype(jnp.float32))
    # rearrange to [b, sq, n_kv, g, ...] so seq leads like q/k/v
    perm = (0, 3, 1, 2)
    return (
        jnp.transpose(o, (0, 3, 1, 2, 4)),
        jnp.transpose(m, perm),
        jnp.transpose(l, perm),
    )


def ring_attention(
    q: jnp.ndarray,  # [b, s_local, n_q, hd]
    k: jnp.ndarray,  # [b, s_local, n_kv, hd]
    v: jnp.ndarray,  # [b, s_local, n_kv, hd]
    *,
    axis_name: str,
    causal: bool = True,
) -> jnp.ndarray:
    """Ring attention over sequence shards. Call inside `shard_map`.

    The global sequence is the concatenation of per-device shards in
    axis-index order. K/V rotate one hop per step (N-1 ppermutes for an
    N-device ring) while each block's contribution merges into an
    online-softmax accumulator — numerically identical to full softmax
    attention over the gathered sequence.

    Causal masking is by *global* position, derived from the axis index of
    the device each K/V block originated on; fully-future blocks still
    execute (static schedule — no data-dependent control flow under jit)
    but contribute zero weight.
    """
    size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s, n_q, hd = q.shape
    n_kv = k.shape[2]
    assert n_q % n_kv == 0, (n_q, n_kv)
    g = n_q // n_kv
    qg = q.reshape(b, s, n_kv, g, hd)

    local_pos = jnp.arange(s, dtype=jnp.int32)
    q_pos = my_idx * s + local_pos                      # [s] global positions

    perm = [(i, (i + 1) % size) for i in range(size)]   # rotate k/v upward

    # Static unrolled ring (size is a compile-time constant under shard_map):
    # exactly size-1 ppermute hops — the last block needs no onward rotation.
    o = jnp.zeros((b, s, n_kv, g, hd), jnp.float32)
    m = jnp.full((b, s, n_kv, g), NEG_INF, jnp.float32)
    l = jnp.zeros((b, s, n_kv, g), jnp.float32)
    k_blk, v_blk = k, v
    for i in range(size):
        # Block i arrived after i hops: it originated on device my_idx - i.
        src = (my_idx - i) % size
        kv_pos = src * s + local_pos
        if causal:
            mask = q_pos[:, None] >= kv_pos[None, :]
        else:
            mask = jnp.ones((s, s), dtype=bool)
        mask = jnp.broadcast_to(mask, (b, s, s))
        o_i, m_i, l_i = _block_attend(qg, k_blk, v_blk, mask)
        m_new = jnp.maximum(m, m_i)
        a = jnp.exp(m - m_new)
        a_i = jnp.exp(m_i - m_new)
        o = o * a[..., None] + o_i * a_i[..., None]
        l = l * a + l_i * a_i
        m = m_new
        if i + 1 < size:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    # Causal guarantees every row attends at least to itself, so l > 0.
    out = o / l[..., None]
    return out.reshape(b, s, n_q, hd).astype(q.dtype)


def ring_attention_sharded(
    q: jnp.ndarray,  # [b, s_global, n_q, hd]
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    seq_axis: str = mesh_lib.FSDP_AXIS,
    causal: bool = True,
) -> jnp.ndarray:
    """shard_map wrapper: sequence dim sharded over `seq_axis`, the rest
    replicated across it. Context parallelism conventionally reuses the
    fsdp device axis as the sequence axis (mesh.py axis convention)."""
    n = mesh.shape[seq_axis]
    if q.shape[1] % n != 0:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by "
            f"{seq_axis}={n}"
        )
    spec = P(None, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


# ------------------------------------------------- ring x flash kernel
#
# The XLA ring above materializes per-block [s_local, s_local] fp32
# logits; this variant runs each block through the Pallas flash kernel
# (ops.pallas.flash_attention) instead — fused online softmax in VMEM,
# MXU fp32 accumulation — and adds a real skip: fully-future blocks
# execute a zero-cost lax.cond branch rather than computing logits and
# masking them to -inf.
#
# Backward is the ring-flash decomposition: flash's bwd formula with the
# GLOBAL row lse and delta = rowsum(do * o_final) splits cleanly along
# KV blocks, so the bwd ring re-runs the dq/dkv kernels per visiting
# block against the final (o, lse) residuals. dk/dv accumulators rotate
# WITH their blocks; after the last step one more hop lands each
# accumulator back on its home device.


def _lse_rows(lse128: jnp.ndarray) -> jnp.ndarray:
    return lse128[..., 0]                        # [b, nq, s]


def _merge_blocks(o, lse, o_i, lse_i):
    """Online merge of normalized per-block (o, lse) pairs, -inf-safe."""
    new = jnp.logaddexp(lse, lse_i)
    w = jnp.where(lse == NEG_INF, 0.0, jnp.exp(lse - new))
    w_i = jnp.where(lse_i == NEG_INF, 0.0, jnp.exp(lse_i - new))
    return o * w[..., None] + o_i * w_i[..., None], new


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q4, k4, v4, axis_name, causal, interpret):
    o4, _ = _ring_flash_fwd(q4, k4, v4, axis_name, causal, interpret)
    return o4


def _ring_flash_fwd(q4, k4, v4, axis_name, causal, interpret):
    from kubeflow_tpu.ops.pallas.flash_attention import flash_block_fwd

    size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, nq, s, hd = q4.shape
    o = jnp.zeros((b, nq, s, hd), jnp.float32)
    lse = jnp.full((b, nq, s), NEG_INF, jnp.float32)
    k_blk, v_blk = k4, v4
    for i in range(size):
        if i == 0:
            # the diagonal block: local causal masking (or full when the
            # whole attention is bidirectional)
            o_i, lse_i = flash_block_fwd(
                q4, k_blk, v_blk, causal=causal, interpret=interpret)
            o_i, lse_i = o_i.astype(jnp.float32), _lse_rows(lse_i)
        else:
            def attend(kv):
                oo, ll = flash_block_fwd(
                    q4, kv[0], kv[1], causal=False, interpret=interpret)
                return oo.astype(jnp.float32), _lse_rows(ll)

            def skip(kv):
                return (jnp.zeros((b, nq, s, hd), jnp.float32),
                        jnp.full((b, nq, s), NEG_INF, jnp.float32))

            if causal:
                # block i hops old = from device my-i: past iff my >= i
                o_i, lse_i = jax.lax.cond(
                    my >= i, attend, skip, (k_blk, v_blk))
            else:
                o_i, lse_i = attend((k_blk, v_blk))
        o, lse = _merge_blocks(o, lse, o_i, lse_i)
        if i + 1 < size:
            perm = [(d, (d + 1) % size) for d in range(size)]
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    return o.astype(q4.dtype), lse


def _ring_flash_fwd_vjp(q4, k4, v4, axis_name, causal, interpret):
    o4, lse = _ring_flash_fwd(q4, k4, v4, axis_name, causal, interpret)
    return o4, (q4, k4, v4, o4, lse)


def _ring_flash_bwd(axis_name, causal, interpret, res, do4):
    from kubeflow_tpu.ops.pallas.flash_attention import flash_block_bwd

    q4, k4, v4, o4, lse = res
    size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, nq, s, hd = q4.shape
    nkv = k4.shape[1]
    lse128 = jnp.broadcast_to(lse[..., None], (*lse.shape, 128))

    dq = jnp.zeros((b, nq, s, hd), jnp.float32)
    dk_acc = jnp.zeros((b, nkv, s, hd), jnp.float32)
    dv_acc = jnp.zeros((b, nkv, s, hd), jnp.float32)
    k_blk, v_blk = k4, v4
    perm = [(d, (d + 1) % size) for d in range(size)]
    for i in range(size):
        if i == 0:
            dq_i, dk_i, dv_i = flash_block_bwd(
                (q4, k_blk, v_blk, o4, lse128), do4,
                causal=causal, interpret=interpret)
        else:
            def backprop(kv):
                a, bb, c = flash_block_bwd(
                    (q4, kv[0], kv[1], o4, lse128), do4,
                    causal=False, interpret=interpret)
                return (a.astype(jnp.float32), bb.astype(jnp.float32),
                        c.astype(jnp.float32))

            def skip(kv):
                return (jnp.zeros((b, nq, s, hd), jnp.float32),
                        jnp.zeros((b, nkv, s, hd), jnp.float32),
                        jnp.zeros((b, nkv, s, hd), jnp.float32))

            if causal:
                dq_i, dk_i, dv_i = jax.lax.cond(
                    my >= i, backprop, skip, (k_blk, v_blk))
            else:
                dq_i, dk_i, dv_i = backprop((k_blk, v_blk))
        dq = dq + dq_i.astype(jnp.float32)
        dk_acc = dk_acc + dk_i.astype(jnp.float32)
        dv_acc = dv_acc + dv_i.astype(jnp.float32)
        # Accumulators travel WITH their block; the rotation after the
        # final step is the hop that returns each accumulator home (the
        # K/V blocks themselves are dead after the last step — no hop).
        if i + 1 < size:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
    return (dq.astype(q4.dtype), dk_acc.astype(k4.dtype),
            dv_acc.astype(v4.dtype))


_ring_flash.defvjp(_ring_flash_fwd_vjp, _ring_flash_bwd)


def ring_flash_attention(
    q: jnp.ndarray,  # [b, s_local, n_q, hd]
    k: jnp.ndarray,  # [b, s_local, n_kv, hd]
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Ring attention with Pallas flash blocks. Call inside shard_map;
    same contract as `ring_attention` (global sequence = shard
    concatenation in axis order), differentiable via the ring-flash
    custom VJP. `interpret`: see flash_attention.resolve_interpret."""
    from kubeflow_tpu.ops.pallas.flash_attention import resolve_interpret

    interpret = resolve_interpret(interpret)
    q4 = jnp.transpose(q, (0, 2, 1, 3))
    k4 = jnp.transpose(k, (0, 2, 1, 3))
    v4 = jnp.transpose(v, (0, 2, 1, 3))
    o4 = _ring_flash(q4, k4, v4, axis_name, causal, interpret)
    return jnp.transpose(o4, (0, 2, 1, 3))


def ring_flash_attention_sharded(
    q: jnp.ndarray,  # [b, s_global, n_q, hd]
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    seq_axis: str = mesh_lib.FSDP_AXIS,
    causal: bool = True,
) -> jnp.ndarray:
    """shard_map wrapper for ring_flash_attention (see
    ring_attention_sharded for the layout contract)."""
    n = mesh.shape[seq_axis]
    if q.shape[1] % n != 0:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by "
            f"{seq_axis}={n}"
        )
    spec = P(None, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ring_flash_attention, axis_name=seq_axis,
                          causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ulysses_attention(
    q: jnp.ndarray,  # [b, s_local, n_q, hd]
    k: jnp.ndarray,  # [b, s_local, n_kv, hd]
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = True,
    impl: str = "xla",
) -> jnp.ndarray:
    """Ulysses sequence parallelism. Call inside `shard_map`.

    all-to-all #1: [b, s/N, n, hd] -> [b, s, n/N, hd] (gather sequence,
    scatter heads); full attention on the now-complete sequence for the
    local head group; all-to-all #2 swaps back. Requires n_q and n_kv
    divisible by the axis size. The local attention is a COMPLETE
    causal attention over contiguous positions, so impl="flash" routes
    it straight through the Pallas kernel.
    """
    if impl not in ("xla", "flash"):
        raise ValueError(f"impl must be 'xla' or 'flash', got {impl!r}")
    size = jax.lax.psum(1, axis_name)
    n_q, n_kv = q.shape[2], k.shape[2]
    if n_q % size or n_kv % size:
        raise ValueError(
            f"ulysses needs heads divisible by axis size: "
            f"n_q={n_q} n_kv={n_kv} size={size}"
        )

    # split_axis=2 (heads), concat_axis=1 (sequence): tiled=True keeps the
    # array rank stable.
    def scatter_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def gather_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    b, s, nh, hd = qh.shape
    if impl == "flash":
        from kubeflow_tpu.ops.pallas.flash_attention import flash_attention

        return gather_heads(flash_attention(qh, kh, vh, causal=causal))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    g = nh // kh.shape[2]
    qg = qh.reshape(b, s, kh.shape[2], g, hd)
    mask = (
        pos[:, :, None] >= pos[:, None, :]
        if causal
        else jnp.ones((b, s, s), dtype=bool)
    )
    o, m, l = _block_attend(qg, kh, vh, mask)
    out = (o / l[..., None]).reshape(b, s, nh, hd).astype(q.dtype)
    return gather_heads(out)


def ulysses_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    seq_axis: str = mesh_lib.FSDP_AXIS,
    causal: bool = True,
    impl: str = "xla",
) -> jnp.ndarray:
    """shard_map wrapper for `ulysses_attention` (see ring_attention_sharded)."""
    spec = P(None, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ulysses_attention, axis_name=seq_axis,
                          causal=causal, impl=impl),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
