"""Logical-axis sharding rules (t5x-style) mapped onto the mesh.

Every parameter/activation carries *logical* axis names ("embed", "heads",
"mlp", "batch", ...). A `ShardingRules` table maps logical names to mesh
axes; `logical_to_spec` resolves them into `PartitionSpec`s. Changing the
parallelism strategy (FSDP vs TP vs both) is a rules change, not a model
change — this is the TPU-idiomatic answer to the reference's absent
parallelism layer (SURVEY.md §2b).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.parallel import mesh as mesh_lib


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Maps logical axis names to mesh axis names (or None = replicated)."""

    rules: Mapping[str, str | tuple[str, ...] | None]

    def resolve(self, logical_axes: tuple[str | None, ...]) -> P:
        out: list[Any] = []
        for ax in logical_axes:
            if ax is None:
                out.append(None)
            else:
                if ax not in self.rules:
                    raise KeyError(f"no sharding rule for logical axis {ax!r}")
                out.append(self.rules[ax])
        # Trailing Nones can be dropped but keeping them is harmless.
        return P(*out)


# The canonical Llama/transformer rule set. Params and activations use
# DISTINCT logical names: a param's embed dim shards over fsdp (ZeRO-3 —
# gathered per-layer), while an activation's embed dim stays unsharded
# (its batch dim already carries data×fsdp); TP shards params' and
# activations' heads/mlp/vocab dims over tensor.
LLAMA_RULES = ShardingRules(
    rules={
        # --- params ---
        "embed": mesh_lib.FSDP_AXIS,
        "heads": mesh_lib.TENSOR_AXIS,
        "kv_heads": mesh_lib.TENSOR_AXIS,
        "head_dim": None,
        "mlp": mesh_lib.TENSOR_AXIS,
        "vocab": mesh_lib.TENSOR_AXIS,
        "layers": None,
        "experts": mesh_lib.TENSOR_AXIS,
        "stage": None,
        "lora_rank": None,  # rank dim is tiny — always replicated
        # --- activations ---
        # dcn leads: on hybrid multi-slice meshes the batch's outermost
        # split is across slices (pure DP over DCN); single-slice meshes
        # have no dcn axis and _filter_spec_to_mesh drops it.
        "batch": (mesh_lib.DCN_AXIS, mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS),
        "seq": None,
        "act_embed": None,
        "act_heads": mesh_lib.TENSOR_AXIS,
        "act_kv_heads": mesh_lib.TENSOR_AXIS,
        "act_mlp": mesh_lib.TENSOR_AXIS,
        "act_vocab": mesh_lib.TENSOR_AXIS,
    }
)


def logical_to_spec(rules: ShardingRules, logical: Any) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: rules.resolve(axes),
        logical,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x),
    )


def shard_pytree_specs(rules: ShardingRules, logical: Any, mesh: Mesh) -> Any:
    """Like logical_to_spec but returns NamedShardings bound to `mesh`."""
    specs = logical_to_spec(rules, logical)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _spec_axes(spec: P) -> set[str]:
    """All mesh axis names a PartitionSpec already consumes."""
    used: set[str] = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


def zero_extend_spec(spec: P, shape: tuple[int, ...], mesh: Mesh,
                     axis: str = mesh_lib.DATA_AXIS) -> P:
    """Fold `axis` (default "data") into `spec`, ZeRO-style.

    Optimizer moments normally mirror their parameter's sharding, which
    leaves them REPLICATED over the data axis — every data-parallel
    replica holds a full copy. ZeRO partitions that redundancy away:
    extend the spec so the first dimension that (a) is divisible by the
    axis size after any existing sharding and (b) doesn't already use
    the axis, is additionally split over `axis`. XLA then materializes
    the update as reduce-scatter(grads) + sharded-update + all-gather
    (params) instead of an all-reduce plus N redundant updates.

    Returns `spec` unchanged when the axis is absent/size-1, already
    used, or no dimension divides — so data=1 meshes (all existing
    tests) are exact no-ops.
    """
    if axis not in mesh.axis_names:
        return spec
    axis_size = mesh.shape[axis]
    if axis_size <= 1 or axis in _spec_axes(spec):
        return spec
    entries: list[Any] = list(spec) + [None] * (len(shape) - len(spec))
    for i, dim in enumerate(shape):
        entry = entries[i]
        if entry is None:
            existing: tuple[str, ...] = ()
        elif isinstance(entry, (tuple, list)):
            existing = tuple(entry)
        else:
            existing = (entry,)
        sharded_by = 1
        for name in existing:
            sharded_by *= mesh.shape.get(name, 1)
        per_shard = dim // sharded_by if sharded_by and dim % sharded_by == 0 else 0
        if per_shard and per_shard % axis_size == 0:
            entries[i] = existing + (axis,) if existing else axis
            return P(*entries)
    return spec  # nothing divides (scalars, tiny leaves) — stay mirrored


def zero_extend_sharding(sharding: NamedSharding, shape: tuple[int, ...],
                         axis: str = mesh_lib.DATA_AXIS) -> NamedSharding:
    """NamedSharding-level zero_extend_spec (same mesh, extended spec)."""
    spec = zero_extend_spec(sharding.spec, shape, sharding.mesh, axis)
    return NamedSharding(sharding.mesh, spec)


def make_shard_and_gather_fns(shardings: Any):
    """Per-leaf (shard_fns, gather_fns) for a pytree of NamedShardings.

    shard_fns place a host/numpy leaf onto the mesh under its spec;
    gather_fns pull a (possibly sharded) leaf back to a host array.
    This is the checkpoint-resize bridge: gather under the OLD mesh,
    shard under the NEW one — the two meshes never need to coexist
    inside a single jit.
    """
    is_leaf = lambda x: isinstance(x, NamedSharding)  # noqa: E731

    def make_shard(s: NamedSharding):
        return lambda x: jax.device_put(x, s)

    def make_gather(_s: NamedSharding):
        return lambda x: jax.device_get(x)

    return (
        jax.tree.map(make_shard, shardings, is_leaf=is_leaf),
        jax.tree.map(make_gather, shardings, is_leaf=is_leaf),
    )


def _auto_axes(mesh) -> set[str]:
    return {
        name
        for name, t in zip(mesh.axis_names, mesh.axis_types)
        if t == jax.sharding.AxisType.Auto
    }


def _filter_spec_to_mesh(spec: P) -> P:
    """Drop mesh axes the current context can't constrain.

    Model code names logical axes unconditionally; which physical axes
    exist — and which are already manual because we're inside a
    shard_map (e.g. the PP stage axis) — depends on the caller's mesh.
    Axes missing from the mesh or not Auto are unconstrainable there by
    definition, so dropping them is the correct meaning of the
    constraint, not a silent loss (typos are still caught earlier by
    rules.resolve on the LOGICAL name)."""
    mesh = mesh_lib.get_abstract_mesh()
    if mesh is None:
        return spec  # no mesh context; with_sharding_constraint will no-op
    auto = _auto_axes(mesh)

    def filt(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in auto)
            return kept if kept else None
        return entry if entry in auto else None

    return P(*(filt(e) for e in spec))


def with_sharding_constraint(x: Any, logical_axes: tuple[str | None, ...],
                             rules: ShardingRules = LLAMA_RULES) -> Any:
    """Constrain an activation's sharding by logical axes (no-op outside jit
    without a mesh context)."""
    spec = rules.resolve(logical_axes)  # typos in logical names must raise
    spec = _filter_spec_to_mesh(spec)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception as e:
        # Only the no-mesh-context case is advisory (plain eager CPU runs).
        # Anything else — unknown mesh axis, duplicate axes in one spec —
        # is a real sharding bug and must surface. (A broad "mesh" match
        # would swallow "Resource axis ... not found in mesh" too.)
        msg = str(e).lower()
        if "empty mesh" in msg or "mesh context" in msg or "requires a mesh" in msg:
            return x
        raise


def per_shard(fn, in_logical, out_logical,
              rules: ShardingRules = LLAMA_RULES):
    """`fn` run once per shard of the ambient mesh, its operands split by
    their logical axes — or `fn` itself where there is nothing to split
    over (no mesh, one device, or every axis already manual).

    This is how a Pallas TPU kernel sits inside a GSPMD-partitioned
    step: the partitioner cannot split a Mosaic call ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map"), so the call names its own partitioning. Only the
    mesh's Auto axes become manual, so it nests inside a shard_map that
    already took some (the pipeline's stage axis)."""
    mesh = mesh_lib.get_abstract_mesh()
    auto = _auto_axes(mesh) if mesh is not None else set()
    if math.prod(mesh.shape[a] for a in auto) == 1:
        return fn

    def spec(logical_axes):
        return _filter_spec_to_mesh(rules.resolve(logical_axes))

    return jax.shard_map(
        fn, in_specs=tuple(spec(a) for a in in_logical),
        out_specs=spec(out_logical), axis_names=frozenset(auto),
        check_vma=False)
