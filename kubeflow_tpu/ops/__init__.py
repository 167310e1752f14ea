"""TPU-friendly ops: norms, rotary embeddings, attention dispatch.

Hot ops get Pallas TPU kernels (flash attention); everything else is plain
jnp left to XLA fusion — hand-scheduling what the compiler already fuses
would only hurt (see /opt/skills/guides/pallas_guide.md).
"""

from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.rotary import apply_rope, rope_frequencies
from kubeflow_tpu.ops.attention import (
    dot_product_attention,
    paged_attention,
    paged_prefill_attention,
    pool_cell_shape,
    resolve_paged_attention_impl,
    resolve_paged_prefill_impl,
)
