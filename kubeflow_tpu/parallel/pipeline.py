"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

SURVEY.md §2b row "Pipeline parallelism (PP)": the reference has none; the
TPU-native equivalent is stage partitioning with activations flowing over
ICI/DCN neighbor links. Design:

- Per-stage params are STACKED on a leading stage dim and sharded over the
  stage axis — each device holds exactly its stage's weights (like the
  stacked-layer scan in the Llama model, but across devices).
- The schedule is a single `lax.scan` over M + S - 1 ticks. At tick t,
  stage s computes microbatch t - s; boundary activations move one hop
  per tick with `jax.lax.ppermute` (neighbor-only: rides ICI within a
  slice, DCN between slices — never an all-gather).
- Everything is static-shaped; inactive (bubble) ticks compute on zeros
  and mask their writes. That wastes the bubble FLOPs (standard GPipe
  cost, S-1 of M+S-1 ticks) but keeps XLA's schedule fully static.

The transformation is differentiable (scan + ppermute have VJPs), so the
same code path trains — grads for each stage's params stay resident on
that stage's device.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,        # this device's stage params (leading dim dropped)
    x_mb: jnp.ndarray,        # [M, mb, ...] microbatches (replicated input)
    *,
    axis_name: str,
) -> jnp.ndarray:
    """Run the pipeline schedule. Call inside shard_map.

    `stage_fn(params, x) -> y` must map activations to same-shaped
    activations (the classic homogeneous-stage constraint; embed/unembed
    belong inside the first/last stage_fn via lax.cond on the stage index
    or — simpler — as pre/post transforms outside the pipeline).

    Returns [M, mb, ...] outputs, replicated across the stage axis.
    """
    S = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = x_mb.shape[0]
    act_shape = x_mb.shape[1:]
    total = M + S - 1

    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def tick(carry, t):
        prev_act, outs = carry
        mb_idx = t - idx
        active = (mb_idx >= 0) & (mb_idx < M)
        # Stage 0 pulls a fresh microbatch; later stages consume the
        # activation handed over the ring on the previous tick.
        fresh = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
        )
        inp = jnp.where(idx == 0, fresh, prev_act)
        out = stage_fn(stage_params, inp)
        out = jnp.where(active, out, jnp.zeros_like(out))
        # Last stage deposits its finished microbatch.
        write = jnp.where(
            (idx == S - 1) & active, out, jnp.zeros_like(out)
        )
        outs = jax.lax.dynamic_update_index_in_dim(
            outs,
            jax.lax.dynamic_index_in_dim(
                outs, jnp.clip(mb_idx, 0, M - 1), axis=0, keepdims=False
            ) + write,
            jnp.clip(mb_idx, 0, M - 1),
            axis=0,
        )
        # Hand the activation to the next stage (stage S-1 sends nowhere).
        nxt = jax.lax.ppermute(out, axis_name, fwd_perm) if S > 1 else out
        return (nxt, outs), None

    outs0 = jnp.zeros((M, *act_shape), x_mb.dtype)
    act0 = jnp.zeros(act_shape, x_mb.dtype)
    (_, outs), _ = jax.lax.scan(
        tick, (act0, outs0), jnp.arange(total, dtype=jnp.int32)
    )
    # Results live on the last stage only; share them ring-wide so every
    # stage returns the same replicated output (psum of one-hot deposits).
    return jax.lax.psum(outs, axis_name)


def pipeline_sharded(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,      # leaves [S, ...] — stage-major stacked
    x: jnp.ndarray,           # [batch, ...] global batch
    mesh: Mesh,
    *,
    stage_axis: str,
    num_microbatches: int,
) -> jnp.ndarray:
    """shard_map wrapper: split batch into microbatches, shard stacked
    params over `stage_axis`, run the schedule, return [batch, ...].

    The stage axis is whichever mesh axis the caller dedicates to PP
    (inter-slice DCN meshes typically use the outermost axis so stage
    hops are the only cross-slice traffic).
    """
    S = mesh.shape[stage_axis]
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(
            f"batch {b} not divisible by microbatches {num_microbatches}"
        )
    leaves = jax.tree.leaves(stacked_params)
    if any(leaf.shape[0] != S for leaf in leaves):
        raise ValueError(
            f"stacked params' leading dim must equal {stage_axis}={S}, "
            f"got {sorted({leaf.shape[0] for leaf in leaves})}"
        )
    x_mb = x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])

    def local(params_stacked_local, x_rep):
        # shard_map hands each device a [1, ...] slice; drop the dim.
        params_local = jax.tree.map(
            lambda leaf: jnp.squeeze(leaf, axis=0), params_stacked_local
        )
        return pipeline(stage_fn, params_local, x_rep, axis_name=stage_axis)

    param_specs = jax.tree.map(lambda _: P(stage_axis), stacked_params)
    # Manual collectives only over the stage axis; any other mesh axes
    # (data, fsdp, ...) stay automatic, so GSPMD keeps handling their
    # sharding — and their gradient reductions — inside the stage loop.
    # This is what lets PP compose with a (stage, data) mesh and the real
    # Trainer optimizer without hand-written data-parallel psums.
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        axis_names={stage_axis},
        check_vma=False,
    )
    y_mb = fn(stacked_params, x_mb)
    return y_mb.reshape(b, *y_mb.shape[2:])


def stack_stage_params(per_stage: list[Any]) -> Any:
    """[stage0_tree, stage1_tree, ...] → one tree with leading stage dim."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage)


def pipeline_spec_rules() -> dict[str, str]:
    """Logical-axis additions for sharding.py rule tables ("stage")."""
    return {"stage": "stage"}


def reference_forward(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    per_stage_params: list[Any],
    x: jnp.ndarray,
) -> jnp.ndarray:
    """Sequential stage composition — the numerics oracle for tests."""
    for p in per_stage_params:
        x = stage_fn(p, x)
    return x
