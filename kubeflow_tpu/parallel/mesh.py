"""Device-mesh construction from TPU slice topologies.

The control plane places notebook/training pods on TPU slices and injects
topology env (see kubeflow_tpu.controlplane.webhook); this module is the
compute-side consumer: it turns a slice topology (e.g. "v5e-16") plus a
parallelism layout into a `jax.sharding.Mesh` whose collectives ride ICI.

Reference parity: the reference has zero mesh/parallelism code
(SURVEY.md §2b); its closest hook is topology-aware placement
(tensorboard_controller.go:408-451). Here the topology becomes a first-class
object so both the control plane (placement, replica counts) and JAX
(mesh axes) read from the same source of truth.

Axis convention (outer → inner, slowest-varying → fastest):
  "data"   — pure data parallelism, gradients all-reduced (DCN-friendly)
  "fsdp"   — sharded data parallelism: params/optimizer sharded, gathered
             per-layer (ZeRO-3 style, ICI all-gather/reduce-scatter)
  "tensor" — tensor (Megatron-style) parallelism inside a layer
Sequence ("seq") and expert ("expert") axes are introduced by the
ring-attention / MoE transforms in kubeflow_tpu.parallel, reusing these
same device axes via mesh reshaping rather than separate physical axes.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
TENSOR_AXIS = "tensor"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
STAGE_AXIS = "stage"
DCN_AXIS = "dcn"

MESH_AXES = (DATA_AXIS, FSDP_AXIS, TENSOR_AXIS)
HYBRID_MESH_AXES = (DCN_AXIS,) + MESH_AXES

NUM_SLICES_ENV = "KFTPU_NUM_SLICES"
MEGASCALE_NUM_SLICES_ENV = "MEGASCALE_NUM_SLICES"


def get_abstract_mesh():
    """The ambient mesh (`jax.set_mesh`), or None outside any mesh
    context. Callers treat None as "trivial mesh" (gather /
    no-constraint paths)."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


@dataclasses.dataclass(frozen=True)
class SliceTopology:
    """A TPU slice: chip grid plus host layout.

    `hosts` is the number of TPU VM hosts (pods the controller must gang-
    schedule; each host sees `chips_per_host` local chips). This is what
    the notebook controller uses for StatefulSet replica counts and what
    the webhook uses to build TPU_WORKER_HOSTNAMES.
    """

    name: str           # e.g. "v5e-16"
    generation: str     # "v5e", "v5p", "v4", ...
    chips: int          # total chips in the slice
    grid: tuple[int, ...]  # physical ICI grid, e.g. (4, 4)
    chips_per_host: int    # chips visible to one TPU VM host

    @property
    def hosts(self) -> int:
        return max(1, self.chips // self.chips_per_host)


def _v5e(n: int, grid: tuple[int, ...]) -> SliceTopology:
    # v5e: 1,4 or 8 chips/host depending on slice; 4 for multi-host slices,
    # n for single-host slices up to 8.
    cph = n if n <= 8 else 4
    return SliceTopology(f"v5e-{n}", "v5e", n, grid, cph)


def _v5p(n: int, grid: tuple[int, ...]) -> SliceTopology:
    return SliceTopology(f"v5p-{n}", "v5p", n, grid, min(n, 4))


def _v4(n: int, grid: tuple[int, ...]) -> SliceTopology:
    return SliceTopology(f"v4-{n}", "v4", n, grid, min(n, 4))


SLICE_TOPOLOGIES: dict[str, SliceTopology] = {
    t.name: t
    for t in [
        _v5e(1, (1, 1)),
        _v5e(4, (2, 2)),
        _v5e(8, (2, 4)),
        _v5e(16, (4, 4)),
        _v5e(32, (4, 8)),
        _v5e(64, (8, 8)),
        _v5e(128, (8, 16)),
        _v5e(256, (16, 16)),
        _v5p(8, (2, 2, 1)),
        _v5p(16, (2, 2, 2)),
        _v5p(32, (2, 2, 4)),
        _v5p(128, (4, 4, 4)),
        _v4(8, (2, 2, 1)),
        _v4(16, (2, 2, 2)),
        _v4(32, (2, 2, 4)),
    ]
}


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A parallelism layout over a device set.

    Sizes of -1 mean "absorb the remaining devices" (at most one axis may
    be -1). The product of resolved sizes must equal the device count.
    """

    data: int = 1
    fsdp: int = -1
    tensor: int = 1

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = {DATA_AXIS: self.data, FSDP_AXIS: self.fsdp, TENSOR_AXIS: self.tensor}
        free = [k for k, v in sizes.items() if v == -1]
        if len(free) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {free}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if free:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[free[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} does not cover {n_devices} devices"
            )
        return sizes


def create_mesh(
    spec: MeshSpec | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
    topology: str | SliceTopology | None = None,
) -> Mesh:
    """Build a Mesh with (data, fsdp, tensor) axes over the given devices.

    JAX device order on TPU already follows the physical ICI grid; keeping
    the innermost mesh axes innermost therefore maps their collectives onto
    ICI neighbor links. When `topology` names a known slice it is used for
    validation: a device count that matches neither the slice's chips nor
    a CPU simulation is rejected so a control-plane/topology mismatch fails
    here instead of producing a silently wrong mesh.
    """
    if devices is None:
        devices = jax.devices()
    spec = spec or MeshSpec()
    if isinstance(topology, str):
        topology = SLICE_TOPOLOGIES[topology]
    if topology is not None:
        backend = getattr(devices[0], "platform", jax.default_backend())
        if backend == "tpu" and len(devices) != topology.chips:
            raise ValueError(
                f"topology {topology.name} has {topology.chips} chips but "
                f"{len(devices)} TPU devices are visible — control-plane "
                "topology env and actual slice disagree"
            )
        if backend != "tpu" and len(devices) != topology.chips:
            logging.getLogger(__name__).warning(
                "simulating topology %s (%d chips) with %d %s devices",
                topology.name, topology.chips, len(devices), backend,
            )
    sizes = spec.resolve(len(devices))
    dev_array = np.asarray(devices).reshape(
        sizes[DATA_AXIS], sizes[FSDP_AXIS], sizes[TENSOR_AXIS]
    )
    return Mesh(dev_array, MESH_AXES)


def create_hybrid_mesh(
    spec: MeshSpec | None = None,
    *,
    num_slices: int,
    devices: Sequence[jax.Device] | None = None,
    topology: str | SliceTopology | None = None,
) -> Mesh:
    """Hybrid multi-slice mesh: ("dcn", "data", "fsdp", "tensor").

    The outer `dcn` axis spans TPU slices; collectives over it ride the
    data-center network, everything inner rides ICI. The scaling-book
    recipe for >1-slice jobs: keep bandwidth-hungry sharding (fsdp/
    tensor) inside a slice, put pure data parallelism — one gradient
    all-reduce per step — across slices. Params carry no `dcn` rule
    (parallel.sharding.LLAMA_RULES), so they replicate per-slice and
    only grads cross DCN.

    Slice membership comes from `device.slice_index` when the runtime
    exposes it (real multi-slice jobs); simulated/virtual device sets
    fall back to contiguous equal chunks, which matches how
    `xla_force_host_platform_device_count` lays out virtual devices.
    `spec` describes the layout WITHIN one slice.
    """
    if devices is None:
        devices = jax.devices()
    if num_slices < 1:
        raise ValueError(f"num_slices must be >= 1, got {num_slices}")
    if len(devices) % num_slices:
        raise ValueError(
            f"{len(devices)} devices not divisible into {num_slices} slices"
        )
    per_slice = len(devices) // num_slices

    by_slice: dict[int, list[jax.Device]] = {}
    groups: list[list[jax.Device]] | None = None
    if all(getattr(d, "slice_index", None) is not None for d in devices):
        for d in devices:
            by_slice.setdefault(d.slice_index, []).append(d)
        if len(by_slice) == num_slices and all(
            len(g) == per_slice for g in by_slice.values()
        ):
            groups = [by_slice[k] for k in sorted(by_slice)]
        elif getattr(devices[0], "platform",
                     jax.default_backend()) == "tpu":
            # Real hardware disagreeing with the control plane must
            # fail here, not build a mesh whose "cross-slice" axis
            # doesn't actually cross slices.
            raise ValueError(
                f"device slice_index grouping "
                f"{sorted((k, len(v)) for k, v in by_slice.items())} "
                f"does not match num_slices={num_slices} x {per_slice}"
            )
        else:
            # Virtual CPU devices carry slice_index=0 across ALL
            # processes (observed in the 4-process hybrid gang test) —
            # the attribute exists but is meaningless off-TPU, so fall
            # through to contiguous chunks, which matches both
            # xla_force_host_platform_device_count layout and
            # process-ordinal ordering in multi-process groups.
            logging.getLogger(__name__).warning(
                "ignoring non-TPU slice_index grouping %s; using "
                "contiguous %d-device chunks",
                sorted((k, len(v)) for k, v in by_slice.items()),
                per_slice,
            )
    if groups is None:
        groups = [
            list(devices[i * per_slice:(i + 1) * per_slice])
            for i in range(num_slices)
        ]

    spec = spec or MeshSpec()
    if isinstance(topology, str):
        topology = SLICE_TOPOLOGIES[topology]
    if topology is not None and per_slice != topology.chips:
        # Same rule as create_mesh: on real TPU a control-plane/slice
        # disagreement must fail here, not build a silently wrong mesh;
        # only CPU/virtual simulations downgrade to a warning.
        backend = getattr(devices[0], "platform", jax.default_backend())
        if backend == "tpu":
            raise ValueError(
                f"topology {topology.name} has {topology.chips} chips "
                f"per slice but {per_slice} TPU devices per slice are "
                "visible — control-plane topology env and actual "
                "slices disagree"
            )
        logging.getLogger(__name__).warning(
            "simulating %d-slice %s (%d chips each) with %d devices/slice",
            num_slices, topology.name, topology.chips, per_slice,
        )
    sizes = spec.resolve(per_slice)
    dev_array = np.stack([
        np.asarray(g).reshape(
            sizes[DATA_AXIS], sizes[FSDP_AXIS], sizes[TENSOR_AXIS]
        )
        for g in groups
    ])
    return Mesh(dev_array, HYBRID_MESH_AXES)


def num_slices_from_env() -> int:
    """Slice count injected by the webhook (KFTPU_NUM_SLICES, mirroring
    MEGASCALE_NUM_SLICES); 1 when absent."""
    for var in (NUM_SLICES_ENV, MEGASCALE_NUM_SLICES_ENV):
        raw = os.environ.get(var, "")
        if raw:
            try:
                return max(1, int(raw))
            except ValueError:
                raise ValueError(f"malformed {var}={raw!r}") from None
    return 1


def mesh_from_env(devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build a mesh from control-plane-injected env.

    The webhook injects KFTPU_MESH="data=1,fsdp=16,tensor=1" (and the
    topology via KFTPU_TOPOLOGY). Falls back to pure-FSDP over all devices.
    Multi-slice gangs (KFTPU_NUM_SLICES > 1) get the hybrid mesh with the
    extra outer "dcn" axis; KFTPU_MESH then describes one slice's layout.
    """
    raw = os.environ.get("KFTPU_MESH", "")
    kwargs: dict[str, int] = {}
    if raw:
        for part in raw.split(","):
            k, _, v = part.partition("=")
            k = k.strip()
            if k in (DATA_AXIS, FSDP_AXIS, TENSOR_AXIS):
                try:
                    kwargs[k] = int(v)
                except ValueError:
                    raise ValueError(
                        f"malformed KFTPU_MESH entry {part!r} "
                        f"(full value: {raw!r})"
                    ) from None
    spec = MeshSpec(**kwargs) if kwargs else MeshSpec()
    topo = os.environ.get("KFTPU_TOPOLOGY") or None
    if topo is not None and topo not in SLICE_TOPOLOGIES:
        # Control plane injected a topology this library build doesn't
        # know — proceed without topology validation but say so.
        logging.getLogger(__name__).warning(
            "unknown KFTPU_TOPOLOGY %r (known: %s); skipping slice "
            "validation", topo, sorted(SLICE_TOPOLOGIES),
        )
        topo = None
    n_slices = num_slices_from_env()
    if n_slices > 1:
        return create_hybrid_mesh(
            spec, num_slices=n_slices, devices=devices, topology=topo
        )
    return create_mesh(spec, devices=devices, topology=topo)
