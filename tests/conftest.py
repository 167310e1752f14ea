"""Test config: hermetic 8-device CPU mesh (the fake-TPU backend).

Mirrors the reference's envtest philosophy (SURVEY.md §4): test the real
code against a simulated environment. Here: JAX CPU with 8 virtual
devices stands in for a TPU slice so sharding/collectives are exercised
without hardware.

`JAX_PLATFORMS=cpu` in the environment is all it takes to hold JAX to
the CPU; it is set here too so a bare `pytest` works.

The Pallas kernels compile for the TPU only. On this backend the suite
runs them in interpret mode, and says so here, once — the library never
infers interpret mode from the backend (ops/pallas/flash_attention.py).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402

from kubeflow_tpu.ops.pallas import force_interpret  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _pallas_interpret_mode():
    with force_interpret():
        yield
