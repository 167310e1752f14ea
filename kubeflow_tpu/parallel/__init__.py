"""Parallelism layer: meshes, sharding rules, and parallel transforms.

The reference control plane has no parallelism code (SURVEY.md §2b); this
package is the TPU-native value-add: jax.sharding Mesh construction from
slice topology, logical-axis sharding rules, and FSDP/TP/SP/EP strategies.
"""

from kubeflow_tpu.parallel.mesh import (
    MeshSpec,
    SliceTopology,
    SLICE_TOPOLOGIES,
    create_hybrid_mesh,
    create_mesh,
    get_abstract_mesh,
    mesh_from_env,
    num_slices_from_env,
)
from kubeflow_tpu.parallel.sharding import (
    ShardingRules,
    LLAMA_RULES,
    logical_to_spec,
    shard_pytree_specs,
    with_sharding_constraint,
)
from kubeflow_tpu.parallel.ring import (
    ring_attention,
    ring_attention_sharded,
    ring_flash_attention,
    ring_flash_attention_sharded,
    ulysses_attention,
    ulysses_attention_sharded,
)
# NOTE: the bare `pipeline` schedule fn is NOT re-exported — it would
# shadow the `kubeflow_tpu.parallel.pipeline` submodule name.
from kubeflow_tpu.parallel.pipeline import (
    pipeline_sharded,
    stack_stage_params,
)
from kubeflow_tpu.parallel.moe import (
    MoEConfig,
    init_moe,
    moe_logical_axes,
    moe_mlp,
    moe_mlp_expert_parallel,
    moe_mlp_sharded,
)
