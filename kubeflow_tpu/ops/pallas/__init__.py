"""Pallas TPU kernels for the hot ops (flash attention first).

Kernels are written against the TPU memory hierarchy (HBM → VMEM → MXU)
and compile for the TPU only. The CPU tests run them in interpret mode,
which they ask for themselves (`force_interpret`, or `interpret=True` on
a call); no code path infers it from the backend.
"""

from kubeflow_tpu.ops.pallas.flash_attention import (
    flash_attention,
    force_interpret,
)
from kubeflow_tpu.ops.pallas.paged_attention import paged_decode_attention
