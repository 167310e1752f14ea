"""What keeps a run from passing without the chip — the part of that
contract a CPU can check. (The rest is `chip_smoke.py`'s, on the chip.)

- `chip_smoke.py` has no CPU mode, fails in seconds without an
  accelerator, prints no result then, and its parent stays off JAX;
- the compile cache is where `JAX_COMPILATION_CACHE_DIR` says, else one
  fixed in-checkout path, from any working directory;
- `bench.py` is one process with no probe and no fallback: an unknown
  device is an error, a CPU has no peak, a failed section fails the run,
  CPU-child sections stay out of the TPU sweep;
- `impl="xla"` means XLA on every platform, `auto` is a rule on platform
  and shape, and a Pallas kernel on another backend is an error unless a
  test asked for interpret mode.
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from kubeflow_tpu import compile_cache
from kubeflow_tpu.models import llama
from kubeflow_tpu.ops import attention
from kubeflow_tpu.ops.pallas import force_interpret
from kubeflow_tpu.ops.pallas.flash_attention import flash_attention
from kubeflow_tpu.ops.pallas.prefill_append import (
    VMEM_BUDGET_BYTES,
    vmem_bytes,
)
from kubeflow_tpu.parallel import MeshSpec, create_mesh, get_abstract_mesh
from kubeflow_tpu.parallel.sharding import per_shard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
from tools import smoke_kernels, smoke_train  # noqa: E402


def _env(**overrides):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = REPO
    env.update(overrides)
    return env


def _results(stdout: str) -> list[dict]:
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return out


# ------------------------------------------------------------ chip_smoke.py


def test_chip_smoke_fails_in_seconds_without_a_chip():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_env(JAX_PLATFORMS="cpu"), cwd=REPO, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    assert not _results(proc.stdout), proc.stdout
    assert "FAILED in phase kernels" in proc.stderr


def test_chip_smoke_parent_never_imports_jax():
    """A parent that has touched JAX holds the chip its children need."""
    code = (
        "import runpy, sys\n"
        "try:\n"
        "    runpy.run_path('chip_smoke.py', run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    print('RC', e.code)\n"
        "print('JAX_IMPORTED', 'jax' in sys.modules)\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=_env(JAX_PLATFORMS="cpu"),
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert "RC 1" in proc.stdout, proc.stdout + proc.stderr
    assert "JAX_IMPORTED False" in proc.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    env = _env()
    del env["PYTHONPATH"]
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not _results(proc.stdout)


def test_chip_smoke_children_are_held_to_the_tpu(monkeypatch):
    import chip_smoke

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    env = chip_smoke.child_env()
    assert env["JAX_PLATFORMS"] == "tpu"
    assert "XLA_FLAGS" not in env


# ------------------------------------------------------------ compile cache


def test_cache_dir_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.cache_dir() == str(tmp_path)
    assert compile_cache.enable() == str(tmp_path)
    # the environment named the place: code sets no other
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_is_one_in_checkout_path_from_any_cwd(tmp_path):
    code = ("from kubeflow_tpu.compile_cache import cache_dir; "
            "print(cache_dir())")
    seen = {
        subprocess.run([sys.executable, "-c", code], env=_env(), cwd=cwd,
                       capture_output=True, text=True,
                       timeout=60).stdout.strip()
        for cwd in (REPO, str(tmp_path))}
    assert seen == {os.path.join(REPO, ".jax_cache")}


def test_cache_enable_points_jax_at_the_checkout_when_env_is_unset():
    code = ("import jax; from kubeflow_tpu import compile_cache as c; "
            "p = c.enable(); "
            "print(jax.config.jax_compilation_cache_dir == p == "
            "c.cache_dir())")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=_env(JAX_PLATFORMS="cpu"),
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.stdout.strip() == "True", proc.stdout + proc.stderr


def test_cache_module_is_importable_without_jax():
    code = ("import sys; import kubeflow_tpu.compile_cache; "
            "print('jax' in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], env=_env(),
                          cwd=REPO, capture_output=True, text=True,
                          timeout=60)
    assert proc.stdout.strip() == "False", proc.stdout + proc.stderr


# ----------------------------------------------------------------- bench.py


@dataclasses.dataclass
class _Device:
    device_kind: str
    platform: str = "tpu"


def _fake_tpu(monkeypatch, kind="TPU v5 lite", count=1):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_Device(kind)] * count)


def test_detect_generation_raises_on_an_unknown_device_kind(monkeypatch):
    assert bench.detect_generation() == "cpu"
    _fake_tpu(monkeypatch)
    assert bench.detect_generation() == "v5e"
    _fake_tpu(monkeypatch, kind="TPU v9 mega")
    with pytest.raises(RuntimeError, match="TPU v9 mega"):
        bench.detect_generation()


def test_a_cpu_run_reports_no_share_of_any_peak():
    assert "cpu" not in bench.PEAK_FLOPS
    assert "cpu" not in bench.PEAK_HBM_GBS
    assert bench._share_of_peak(1e12, bench.PEAK_FLOPS, "cpu") is None
    assert bench._share_of_peak(
        0.4 * 197e12, bench.PEAK_FLOPS, "v5e") == 1.0


def test_tpu_sweep_holds_no_cpu_child_section(monkeypatch):
    p = argparse.ArgumentParser()
    _fake_tpu(monkeypatch, count=1)
    sweep = bench._sweep_for("tpu", [], p)
    assert not {"serving-disagg", "scenario-replay", "train-zero"} \
        & set(sweep)
    assert sweep[-1] == "flash4k"
    with pytest.raises(SystemExit):
        bench._sweep_for("tpu", ["scenario-replay"], p)
    with pytest.raises(RuntimeError, match=">=4 devices"):
        bench.bench_train_zero()
    # four chips run train-zero in-process
    _fake_tpu(monkeypatch, count=4)
    assert "train-zero" in bench._sweep_for("tpu", [], p)
    # the explicit CPU sweep ci/bench_gate.py reads is unchanged
    assert "scenario-replay" in bench._sweep_for("cpu", [], p)


def test_bench_has_no_probe_fallback_or_orchestrator():
    for name in ("_probe_backend", "resolve_backend", "_reexec_cpu_fallback",
                 "_chip_alive", "_orchestrate", "_run_section_child"):
        assert not hasattr(bench, name), name
    with open(os.path.join(REPO, "bench.py")) as f:
        source = f.read()
    for needle in ("KFTPU_BENCH_", "KFTPU_FORCE_BACKEND_FAIL",
                   "skipped-wedged-backend"):
        assert needle not in source, needle


def test_a_failed_section_fails_the_run_after_the_others(
        monkeypatch, capsys):
    def boom(**kw):
        raise RuntimeError("section broke")

    monkeypatch.setattr(bench, "bench_mnist", boom)
    monkeypatch.setattr(bench, "bench_vit", lambda *a, **kw: {
        "metric": "vit[tiny,cpu]", "value": 1.0, "unit": "images/s/chip",
        "vs_baseline": None})
    rc = bench._run_sweep(["mnist", "vit"], "cpu", json_only=True)
    assert rc == 1
    (result,) = _results(capsys.readouterr().out)
    assert result["metric"] == "vit[tiny,cpu]"      # the others ran
    assert result["device"]["platform"] == "cpu"     # stamped
    assert [m["metric"] for m in result["extra_metrics"]] \
        == ["mnist[failed]"]


# ------------------------------------------------------------ ops/attention


def _paged_inputs(b=2, n_q=4, n_kv=2, hd=16, bs=64, nb=4):
    rng = np.random.default_rng(0)
    width = nb * bs                                   # 256 cells
    q = jnp.asarray(rng.normal(size=(b, 1, n_q, hd)), jnp.float32)
    kp, vp = (jnp.asarray(rng.normal(size=(1 + b * nb, bs, n_kv, hd)),
                          jnp.float32) for _ in range(2))
    table = jnp.asarray(1 + np.arange(b * nb).reshape(b, nb), jnp.int32)
    qpos = jnp.asarray([[width - 1], [width // 2]], jnp.int32)
    kvpos = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32)[None],
                             (b, width))
    return q, kp, vp, table, qpos, kvpos


def test_paged_xla_never_counts_a_pallas_impl(monkeypatch):
    """`impl="xla"` used to end in `dot_product_attention(impl="auto")`,
    which on TPU hands a single-token step over >= 256 cells to the
    Pallas decode kernel."""
    # heads of 128: "auto" sends other sizes to XLA on the chip too
    args = _paged_inputs(hd=128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    attention.reset_impl_counts()
    jax.eval_shape(lambda *a: attention.paged_attention(*a, impl="xla"),
                   *args)
    counts = attention.impl_counts()
    assert counts["paged_xla"] == 1 and counts["xla"] == 1
    assert counts["decode"] == counts["paged_pallas"] == 0
    # the same shapes under "auto" do go to the kernels on TPU
    attention.reset_impl_counts()
    q, kp, vp, table, qpos, kvpos = args
    k = kp[table].reshape(2, 256, 2, 128)
    jax.eval_shape(lambda q, k: attention.dot_product_attention(
        q, k, k, qpos, kvpos, contiguous_positions=True), q, k)
    jax.eval_shape(lambda *a: attention.paged_attention(*a, impl="auto"),
                   *args)
    counts = attention.impl_counts()
    assert counts["decode"] == 1 and counts["paged_pallas"] == 1


def test_a_pallas_impl_on_cpu_without_interpret_raises():
    args = _paged_inputs()
    q = jnp.zeros((1, 128, 4, 16), jnp.float32)
    k = jnp.zeros((1, 128, 2, 16), jnp.float32)
    with force_interpret(False):
        with pytest.raises(RuntimeError, match="backend 'cpu'"):
            attention.paged_attention(*args, impl="pallas")
        with pytest.raises(RuntimeError, match="backend 'cpu'"):
            flash_attention(q, k, k)
        # the explicit argument is the one way in
        out = attention.paged_attention(*args, impl="pallas",
                                        interpret=True)
    np.testing.assert_allclose(
        out, attention.paged_attention(*args, impl="xla"),
        atol=1e-5, rtol=1e-5)


def test_engine_refuses_pallas_on_cpu_at_construction():
    from kubeflow_tpu.serving import (
        LLAMA_FAMILY, EngineConfig, InferenceEngine)
    from kubeflow_tpu.serving.continuous import ContinuousEngine

    cfg = llama.LLAMA_TINY
    engine = InferenceEngine(llama.init(jax.random.key(0), cfg), cfg,
                             LLAMA_FAMILY, EngineConfig(max_len=64))
    with force_interpret(False):
        with pytest.raises(RuntimeError, match="backend 'cpu'"):
            ContinuousEngine(engine, max_slots=2, block_size=8,
                             paged_attention_impl="pallas")
        ce = ContinuousEngine(engine, max_slots=2, block_size=8)
    assert (ce.attention_impl, ce.prefill_impl) == ("xla", "xla")


def test_auto_is_a_rule_on_platform_and_shape(monkeypatch):
    """llama3-1b heads: a 256-token chunk fits the prefill kernel's VMEM
    budget, a 1024-token chunk does not and takes XLA."""
    fits = vmem_bytes(256, 16, 8, 128, 64, 2)
    too_big = vmem_bytes(1024, 16, 8, 128, 64, 2)
    assert fits < VMEM_BUDGET_BYTES < too_big
    for resolve in (attention.resolve_paged_attention_impl,
                    attention.resolve_paged_prefill_impl):
        assert resolve("auto") == "xla"               # this backend
        with pytest.raises(ValueError, match="impl"):
            resolve("cuda")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention.resolve_paged_attention_impl("auto") == "pallas"
    assert attention.resolve_paged_prefill_impl("auto") == "pallas"
    assert attention.resolve_paged_prefill_impl(
        "auto", vmem_bytes=fits) == "pallas"
    assert attention.resolve_paged_prefill_impl(
        "auto", vmem_bytes=too_big) == "xla"
    assert attention.resolve_paged_prefill_impl(
        "pallas", vmem_bytes=too_big) == "pallas"     # said explicitly


@pytest.mark.parametrize("head_dim,auto", [
    (128, "pallas"),     # mistral-7b.steady's heads: as before
    (256, "pallas"),
    (None, "pallas"),    # not said: only the platform is judged
    (64, "xla"),         # granite-4.0-h-micro's: half a 128-lane tile
    (192, "xla"),
])
def test_auto_takes_xla_for_heads_the_paged_kernels_do_not_copy(
        monkeypatch, head_dim, auto):
    """The compiled paged kernels copy pool blocks in whole 128-lane
    tiles and raise on any other head size (inside a trace): "auto"
    answers from the shape instead, on the chip too."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention.resolve_paged_attention_impl(
        "auto", head_dim=head_dim) == auto
    assert attention.resolve_paged_prefill_impl(
        "auto", head_dim=head_dim) == auto
    # said explicitly it stays what was said
    assert attention.resolve_paged_attention_impl(
        "pallas", head_dim=head_dim) == "pallas"


def test_no_implementation_is_chosen_by_catching_an_error():
    assert not [n for n in dir(attention) if n.endswith("_available")]
    with open(attention.__file__) as f:
        source = f.read()
    assert "except" not in source


# ------------------------------------------------- kernels under a mesh


def test_flash_under_a_mesh_runs_per_shard_and_matches_xla():
    """GSPMD cannot partition a Mosaic call ("Please wrap the call in a
    shard_map"): under a multi-device mesh the flash kernel names its
    own partitioning, batch over (data, fsdp) and heads over tensor."""
    mesh = create_mesh(MeshSpec(data=2, fsdp=2, tensor=2))
    rng = np.random.default_rng(0)
    b, s = 4, 128
    q = jnp.asarray(rng.normal(size=(b, s, 4, 32)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b, s, 2, 32)), jnp.float32)
            for _ in range(2))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def loss(impl):
        def f(q, k, v):
            return (attention.dot_product_attention(
                q, k, v, pos, pos, impl=impl,
                contiguous_positions=True) ** 2).sum()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

    with jax.set_mesh(mesh):
        sh = NamedSharding(mesh, P(("data", "fsdp"), None, "tensor", None))
        q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
        got, ref = loss("flash")(q, k, v), loss("xla")(q, k, v)
    for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)
    assert got[1][0].sharding.spec == sh.spec


def test_per_shard_leaves_a_single_device_call_alone():
    def fn(x):
        return x

    axes = ("batch", "seq", "act_heads", None)
    assert get_abstract_mesh() is None
    assert per_shard(fn, (axes,), axes) is fn
    with jax.set_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1])):
        assert get_abstract_mesh().axis_names == ("data", "fsdp", "tensor")
        assert per_shard(fn, (axes,), axes) is fn
    with jax.set_mesh(create_mesh(MeshSpec())):
        assert per_shard(fn, (axes,), axes) is not fn


# ------------------------------------------------- the smoke's own scripts


def test_smoke_train_cuts_depth_never_width():
    cfg = dataclasses.replace(llama.LLAMA3_1B, param_dtype=jnp.bfloat16)
    gib = 2**30
    assert smoke_train.fit_depth(cfg, 1, 16 * gib) == 16
    assert smoke_train.fit_depth(cfg, 1, None) == 16
    shallow = smoke_train.fit_depth(cfg, 1, 8 * gib)
    assert 1 <= shallow < 16
    assert smoke_train.fit_depth(cfg, 4, 8 * gib) == 16
    with pytest.raises(RuntimeError, match="not even one layer"):
        smoke_train.fit_depth(cfg, 1, gib)


def test_smoke_train_rejects_parameters_left_on_one_device():
    mesh = create_mesh(MeshSpec(), devices=jax.devices()[:4])
    tree = {"embed": jnp.ones((64, 32)), "w": jnp.ones((8, 32, 16))}
    spread = jax.device_put(tree, {
        "embed": NamedSharding(mesh, P(None, "fsdp")),
        "w": NamedSharding(mesh, P(None, "fsdp", None))})
    info = smoke_train.check_spread(spread, 4)
    assert sorted(info["per_device_bytes"].values()) \
        == [info["total_bytes"] // 4] * 4
    with pytest.raises(AssertionError, match="1 of 4 devices"):
        smoke_train.check_spread(
            jax.device_put(tree, jax.devices()[0]), 4)
    with pytest.raises(AssertionError, match="not split 4 ways"):
        smoke_train.check_spread(
            jax.device_put(tree, NamedSharding(mesh, P())), 4)


def test_smoke_kernels_phase_passes_interpreted_at_a_tiny_size():
    """The kernels phase end to end (every comparison, the empty-row
    convention, the auto report) — here interpreted, on the chip
    compiled at the llama3-1b shapes."""
    result = smoke_kernels.run(n_q=4, n_kv=2, hd=32, block_size=8,
                               cells=64, chunk=16, flash_seq=128,
                               interpret=True,
                               steady=dict(n_q=8, blocks_per_slot=16,
                                           num_blocks=65, calls=2))
    assert result["ok"], result["problems"]
    assert result["kernels"]["empty_visible_set_rows_are_zero"]["ok"]
    assert len(result["kernels"]) == 16
    steady = result["kernels"][
        "paged_attention[s=1,16x16 blocks of 65,layer=2 of 3]"]
    assert steady["ok"] and steady["live_blocks"] == 48
    assert steady["call_us"] > 0
    assert result["kernels"][
        "prefill_append[s=16,layer=1 of 2].other_layers_untouched"]["ok"]
    assert set(result["auto"].values()) == {"xla"}      # this backend
