"""Cross-cutting utilities: profiling hooks, hot-reloaded config."""

from kubeflow_tpu.utils.config import WatchedConfig
from kubeflow_tpu.utils.profiling import (
    StepTimer,
    device_stamp,
    time_to_first_compile,
    trace,
)
