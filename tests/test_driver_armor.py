"""`__graft_entry__.dryrun_multichip`'s parent never touches a JAX
backend: the virtual-device flag must be set before JAX starts, and a
process that has touched JAX holds the chip. Proven by giving the parent
a platform that does not exist; only the CPU child may import jax.
(bench.py has no such parent any more: it is one process that owns the
chip — tests/test_chip_contract.py.)
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(**overrides):
    """Env for a fresh child: no inherited virtual-device flags, no
    dryrun marker leaking in from this test process."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    env.pop("KFTPU_DRYRUN_CHILD", None)
    env.update(overrides)
    return env


@pytest.mark.slow
def test_dryrun_parent_is_backend_free_and_budget_degrades():
    """dryrun_multichip must succeed even when the parent's platform is
    unusable (the child pins CPU itself), and a tiny wall-clock budget
    must skip optional sections instead of overrunning."""
    env = _clean_env(
        JAX_PLATFORMS="no-such-platform",  # parent must never touch it
        KFTPU_DRYRUN_BUDGET_S="1",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"), "2"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip ok" in proc.stdout
    # Budget of 1s is spent before any optional section starts; with
    # n=2 that skips ep+pp (sp/hybrid aren't attempted at this count).
    assert "skipped_over_budget=['ep', 'pp']" in proc.stdout


@pytest.mark.slow
def test_dryrun_full_sections_at_default_budget():
    """With the default budget nothing is skipped at n=2: EP (tensor=2)
    and PP both run; the ok-line reports their shapes."""
    env = _clean_env(JAX_PLATFORMS="no-such-platform")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"), "2"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=540,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout
    assert "dryrun_multichip ok" in out
    assert "ep=True" in out
    assert "pp_layers_per_stage=2" in out
    assert "skipped_over_budget" not in out
