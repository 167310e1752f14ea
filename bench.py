#!/usr/bin/env python
"""Benchmarks across BASELINE.md's target configs on the local device(s).

Prints ONE JSON line (driver contract). The headline metric keeps the
round-1/2 shape so results stay comparable across rounds:

  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "extra_metrics": [{...}, {...}, ...]}

`extra_metrics` carries the rest of the BASELINE sweep, one dict per
metric with the same keys:
  - llama train tokens/s/chip on a ~1.1B-param bf16 Llama-3-shape model
    (the closest single-chip proxy to BASELINE.md's 8B-FSDP north star:
    same block shapes at 2048 hidden, bf16 params + Adam state sized to
    one 16 GB v5e chip via a 32k bench vocab + tied head),
  - train tokens/s/chip at seq 4096, with a hard assert that the
    attention dispatch took the Pallas flash kernel (ops/attention.py
    trace-time impl counters) — not the XLA path,
  - serving decode tokens/s on serving/engine.py (KV-cache scan decode),
  - pod-to-first-XLA-compile seconds (BASELINE.md north-star latency),
    measured from KFTPU_POD_START_TIME (webhook-injected; process start
    when absent) to the first compiled+executed training step.

The reference (kubeflow/kubeflow control plane) publishes no performance
numbers (BASELINE.md: `published: {}`), so `vs_baseline` normalizes
against hardware rooflines instead:
  - training: MFU / 0.40 (1.0 = 40% of peak bf16 FLOPs — a strong
    single-chip training bar; >1.0 beats it),
  - decode: MBU / 0.40 (model-bandwidth utilization vs peak HBM GB/s;
    decode is bandwidth-bound, so MBU is the roofline that matters),
  - first-compile: 120s budget / measured (>1.0 = faster than a 2-minute
    pod-to-first-step budget).
A CPU run (the explicit [tiny,cpu] smoke sweep ci/bench_gate.py reads)
has no roofline: its MFU/MBU entries carry `vs_baseline: null`.

One process runs the sweep and owns the chip. Whatever backend JAX
attached is stamped on the artifact (`backend`, `device`); a TPU that
does not attach fails the run, and a section that raises makes the exit
code non-zero after the others have run.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu import compile_cache
from kubeflow_tpu.utils import device_stamp

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))


# Peak bf16 FLOPs/sec and HBM GB/s per chip by TPU generation (public:
# Google Cloud TPU documentation, per-generation system architecture
# pages). A CPU has no row: a CPU run is a smoke test and reports no
# share of any peak.
PEAK_FLOPS = {
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6e": 918e12,
}
PEAK_HBM_GBS = {
    "v5e": 819e9,
    "v5p": 2765e9,
    "v4": 1228e9,
    "v6e": 1640e9,
}
FIRST_COMPILE_BUDGET_S = 120.0

# `device_kind` as JAX reports it -> generation. A kind that is not
# here is an error, not a default: a guessed peak makes every MFU/MBU
# in the artifact a guess.
_DEVICE_KINDS = {
    "tpu v4": "v4",
    "tpu v5 lite": "v5e",     # as a v5e reports itself (PR 21's chip run)
    "tpu v5p": "v5p",
    "tpu v6 lite": "v6e",
}


def detect_generation() -> str:
    """The metric label of the device this process runs on: "cpu" on the
    CPU backend (the explicit [tiny,cpu] smoke sweep), else the TPU
    generation of `device_kind`."""
    if jax.default_backend() == "cpu":
        return "cpu"
    kind = jax.devices()[0].device_kind
    gen = _DEVICE_KINDS.get(kind.lower())
    if gen is None:
        raise RuntimeError(
            f"unknown device_kind {kind!r} (platform "
            f"{jax.devices()[0].platform!r}): add it to _DEVICE_KINDS "
            "and both peak tables with its published peaks")
    return gen


def _share_of_peak(achieved: float, peaks: dict, gen: str) -> float | None:
    """`vs_baseline` of a roofline metric: the achieved share of the
    generation's peak over the 0.40 bar, or None where there is no peak
    (a CPU run)."""
    peak = peaks.get(gen)
    return None if peak is None else round(achieved / peak / 0.40, 4)


@dataclasses.dataclass
class Preset:
    name: str
    batch: int
    seq: int
    steps: int
    warmup: int
    model: str  # key into bench_configs()


def bench_configs():
    from kubeflow_tpu.models import llama

    # ~460M params, MXU-friendly shapes, 32k vocab: fits one v5e chip
    # with fp32 params + adam moments + remat at batch 8 x seq 2048.
    bench_500m = llama.LlamaConfig(
        vocab_size=32768, hidden_size=1536, intermediate_size=6144,
        num_layers=14, num_heads=12, num_kv_heads=4, head_dim=128,
    )
    # ~1.08B params: Llama-3-1B block shapes (hidden 2048, 16 layers,
    # GQA 16q/8kv) with bf16 master params. 32k bench vocab + tied head
    # keep params (2.2 GB) + bf16 Adam moments (4.3 GB) + fp32 logits
    # inside one 16 GB v5e chip; the block compute — where the 8B
    # north star's FLOPs live — is unchanged from llama.LLAMA3_1B.
    bench_1b = llama.LlamaConfig(
        vocab_size=32768, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=16, num_kv_heads=8, head_dim=128,
        param_dtype=jnp.bfloat16, tie_embeddings=True,
    )
    # bf16 serving weights for the decode bench (decode reads every
    # param every step — fp32 storage would halve effective MBU).
    bench_500m_serve = dataclasses.replace(
        bench_500m, param_dtype=jnp.bfloat16)
    return {
        "tiny": llama.LLAMA_TINY,
        "bench-500m": bench_500m,
        "bench-500m-serve": bench_500m_serve,
        "bench-1b-bf16": bench_1b,
        "llama3-1b": llama.LLAMA3_1B,
        "llama3-8b": llama.LLAMA3_8B,
    }


TRAIN_PRESETS = {
    "tpu-v5e-1": Preset("tpu-v5e-1", batch=8, seq=2048, steps=10, warmup=2,
                        model="bench-500m"),
    "tpu-1b-bf16": Preset("tpu-1b-bf16", batch=2, seq=2048, steps=10,
                          warmup=2, model="bench-1b-bf16"),
    "tpu-flash-4k": Preset("tpu-flash-4k", batch=2, seq=4096, steps=10,
                           warmup=2, model="bench-500m"),
    "tiny-cpu": Preset("tiny-cpu", batch=4, seq=128, steps=5, warmup=1,
                       model="tiny"),
}


def model_flops_per_token(cfg, seq: int) -> float:
    """Approximate train FLOPs/token: 6*N for matmul params + attention."""
    from kubeflow_tpu.models import llama

    n = llama.num_params(cfg)
    # The embedding lookup is free; a tied table is also the head matmul,
    # so only the untied case subtracts it from the matmul param count.
    n_matmul = n if cfg.tie_embeddings else n - cfg.vocab_size * cfg.hidden_size
    attn = 12 * cfg.num_layers * cfg.num_heads * cfg.head_dim * seq
    return 6 * n_matmul + attn


def param_bytes(cfg) -> int:
    from kubeflow_tpu.models import llama

    itemsize = jnp.dtype(cfg.param_dtype).itemsize
    return llama.num_params(cfg) * itemsize


_first_compile_s: float | None = None


def _record_first_compile(elapsed_since_pod_start: float) -> None:
    global _first_compile_s
    if _first_compile_s is None:
        _first_compile_s = elapsed_since_pod_start


def bench_train(preset: Preset, *, assert_flash: bool = False,
                verbose: bool = True, config=None) -> dict:
    """One training bench -> metric dict. Also records pod-to-first-compile
    the first time any train bench finishes its first step. `config`
    overrides the preset's named model (tools/remat_sweep.py variants).
    """
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.ops import attention
    from kubeflow_tpu.parallel import MeshSpec, create_mesh
    from kubeflow_tpu.train import Trainer, TrainConfig
    from kubeflow_tpu.train.trainer import chunked_cross_entropy_from_hidden
    from kubeflow_tpu.utils import profiling

    cfg = config if config is not None else bench_configs()[preset.model]
    n_devices = len(jax.devices())
    mesh = create_mesh(MeshSpec(data=1, fsdp=n_devices, tensor=1))
    # Global batch must divide evenly over the data*fsdp axes.
    batch = -(-preset.batch // n_devices) * n_devices

    def chunked_loss(params, tokens, targets, mask):
        # Never materializes the [b, s, vocab] fp32 logits — the step's
        # largest tensor (2 GB at batch 8 x 2048 x 32k) and its
        # cotangent both go away (trainer.py chunked CE docstring).
        h = llama.hidden(params, cfg, tokens)
        return chunked_cross_entropy_from_hidden(
            h, llama.unembed_matrix(params, cfg), targets, mask,
            num_chunks=16)

    trainer = Trainer(
        mesh=mesh,
        apply_fn=lambda p_, t: llama.apply(p_, cfg, t),
        init_fn=lambda k: llama.init(k, cfg),
        logical_axes=llama.param_logical_axes(cfg),
        train_config=TrainConfig(warmup_steps=10, total_steps=1000),
        loss_fn=chunked_loss,
    )
    state = trainer.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, preset.seq)), jnp.int32
    )
    targets = jnp.roll(tokens, -1, axis=1)

    attention.reset_impl_counts()
    for i in range(preset.warmup):
        state, loss = trainer.step(state, tokens, targets)
        if i == 0:
            # sync by device-to-host transfer: the value has left the
            # device, so the step that produced it has run
            float(loss)
            _record_first_compile(time.time() - profiling.pod_start_time())
    float(loss)
    counts = attention.impl_counts()
    if assert_flash and counts["flash"] == 0:
        raise AssertionError(
            f"preset {preset.name} (seq={preset.seq}) did not route through "
            f"the Pallas flash kernel: impl counts {counts}"
        )

    t0 = time.perf_counter()
    for _ in range(preset.steps):
        state, loss = trainer.step(state, tokens, targets)
    float(loss)
    dt = time.perf_counter() - t0
    del state, trainer  # free HBM before the next bench

    total_tokens = batch * preset.seq * preset.steps
    tok_per_sec_per_chip = total_tokens / dt / n_devices

    gen = detect_generation()
    flops_per_tok = model_flops_per_token(cfg, preset.seq)
    vs = _share_of_peak(tok_per_sec_per_chip * flops_per_tok,
                        PEAK_FLOPS, gen)

    if verbose:
        print(
            f"# preset={preset.name} devices={n_devices} "
            f"loss={float(loss):.3f} mfu/0.40={vs} "
            f"step_time={dt/preset.steps*1000:.1f}ms attn_impl={counts}",
            file=sys.stderr,
        )
    tag = "flash4k" if assert_flash else preset.model
    return {
        "metric": f"llama_train_tokens_per_sec_per_chip[{tag},{gen}]",
        "value": round(tok_per_sec_per_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": vs,
    }


def _train_zero_measure(*, steps: int = 5, warmup: int = 1, batch: int = 8,
                        seq: int = 64, verbose: bool = True) -> dict:
    """ZeRO A/B on a data=4 mesh: throughput + per-replica optimizer
    bytes with the optimizer sharded over the data axis vs fully
    replicated. Needs >=4 devices (bench_train_zero arranges them).

    The shard ratio (replicated bytes / ZeRO bytes per replica) is the
    acceptance number: ~= the data-axis extent (4), since every
    divisible optimizer leaf drops to 1/N per device and only scalar
    leaves (step counters) stay mirrored.
    """
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.parallel import MeshSpec, create_mesh
    from kubeflow_tpu.train import Trainer, TrainConfig

    cfg = bench_configs()["tiny"]
    n = len(jax.devices())
    if n < 4:
        raise RuntimeError(f"train-zero needs >=4 devices, have {n}")
    data = 4
    devices = jax.devices()[: data * (n // data)]
    mesh = create_mesh(
        MeshSpec(data=data, fsdp=len(devices) // data, tensor=1),
        devices=devices)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)

    arms = {}
    for zero in (True, False):
        trainer = Trainer(
            mesh=mesh,
            apply_fn=lambda p_, t: llama.apply(p_, cfg, t),
            init_fn=lambda k: llama.init(k, cfg),
            logical_axes=llama.param_logical_axes(cfg),
            train_config=TrainConfig(warmup_steps=10, total_steps=1000,
                                     zero_optimizer=zero),
        )
        state = trainer.init(jax.random.key(0))
        for _ in range(warmup):
            state, loss = trainer.step(state, tokens, targets)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = trainer.step(state, tokens, targets)
        final_loss = float(loss)
        dt = time.perf_counter() - t0
        arms[zero] = {
            "tok_per_sec_per_chip":
                batch * seq * steps / dt / len(devices),
            "opt_bytes_per_replica":
                trainer.opt_state_bytes(per_replica=True),
            "loss": final_loss,
        }
        del state, trainer

    # Both arms run the mathematically identical update — ZeRO only
    # re-shards where the state lives. Divergence means a sharding bug,
    # which must fail the bench rather than publish a tainted number.
    loss_div = abs(arms[True]["loss"] - arms[False]["loss"])
    if loss_div > 1e-4:
        raise AssertionError(
            f"ZeRO arm diverged from replicated arm: "
            f"{arms[True]['loss']:.6f} vs {arms[False]['loss']:.6f}")

    zb = arms[True]["opt_bytes_per_replica"]
    rb = arms[False]["opt_bytes_per_replica"]
    ratio = rb / max(zb, 1)
    gen = detect_generation()
    if verbose:
        print(
            f"# train-zero devices={len(devices)} data={data} "
            f"opt_bytes/replica zero={zb} replicated={rb} "
            f"ratio={ratio:.3f} loss_div={loss_div:.2e}",
            file=sys.stderr,
        )
    return {
        "metric": f"llama_train_tokens_per_sec_per_chip[tiny-zero,{gen}]",
        "value": round(arms[True]["tok_per_sec_per_chip"], 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(
            arms[True]["tok_per_sec_per_chip"]
            / max(arms[False]["tok_per_sec_per_chip"], 1e-9), 4),
        "extra_metrics": [
            {
                "metric":
                    f"llama_train_tokens_per_sec_per_chip"
                    f"[tiny-zero-off,{gen}]",
                "value": round(arms[False]["tok_per_sec_per_chip"], 2),
                "unit": "tokens/s/chip",
                "vs_baseline": 1.0,
            },
            {
                "metric": f"train_opt_bytes_per_replica[tiny-zero,{gen}]",
                "value": int(zb),
                "unit": "bytes",
                "vs_baseline": round(zb / rb, 4),
            },
            {
                "metric":
                    f"train_opt_bytes_per_replica[tiny-replicated,{gen}]",
                "value": int(rb),
                "unit": "bytes",
                "vs_baseline": 1.0,
            },
            {
                # The ISSUE acceptance gate: ~= data-axis extent (4.0).
                # Unit "ratio" makes bench_gate treat it higher-better,
                # so a sharding regression (ratio -> 1.0) fails CI.
                "metric": f"train_zero_opt_shard_ratio[{gen}]",
                "value": round(ratio, 4),
                "unit": "ratio",
                "vs_baseline": round(ratio / data, 4),
            },
        ],
    }


def bench_train_zero(*, verbose: bool = True) -> dict:
    """ZeRO A/B section. With four or more devices it runs in-process.
    The explicit CPU sweep has ONE device (no virtual-device forcing
    here, unlike tests/conftest.py), so there the data=4 mesh runs in a
    CPU child with forced host devices — XLA_FLAGS must be set before
    jax import. On a TPU with fewer than four chips the section is not
    in the sweep (`_sweep_for`): a CPU child's timing under a TPU
    artifact would be a lie, and this process holds the chip.
    """
    if len(jax.devices()) >= 4:
        return _train_zero_measure(verbose=verbose)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"train-zero needs >=4 devices, have {len(jax.devices())} "
            f"on {jax.default_backend()}")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    code = (
        "import sys, json; sys.path.insert(0, {root!r}); "
        "import bench; "
        "print(json.dumps(bench._train_zero_measure(verbose=False)))"
    ).format(root=_REPO_DIR)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=_REPO_DIR,
        stdout=subprocess.PIPE, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"train-zero child failed rc={proc.returncode}")
    out = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    m = json.loads(out[-1])
    if verbose:
        extras = {e["metric"]: e["value"] for e in m["extra_metrics"]}
        print(f"# train-zero (child, 8 virtual cpu devices): "
              f"headline={m['value']} {m['unit']} extras={extras}",
              file=sys.stderr)
    return m


def bench_train_goodput(*, steps: int = 6, seq: int = 16,
                        verbose: bool = True) -> dict:
    """Goodput observatory on the bench path (ISSUE 14): the tiny
    trainer runs under a real GoodputLedger — the first step books to
    `compile`, the rest to `productive` with the model-FLOPs estimate
    attached — and the section reports the resulting goodput fraction.
    The run also asserts the ledger's conservation invariant on real
    (not scripted) clocks. Unit "fraction" keeps the number
    informational in the bench gate: it is a property of this tiny
    compile-dominated run, not a regression surface."""
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.parallel import MeshSpec, create_mesh
    from kubeflow_tpu.train import Trainer, TrainConfig
    from kubeflow_tpu.train.goodput import GoodputLedger

    cfg = bench_configs()["tiny"]
    n_devices = len(jax.devices())
    mesh = create_mesh(MeshSpec(data=1, fsdp=n_devices, tensor=1))
    batch = n_devices  # one sample per device keeps the section cheap
    trainer = Trainer(
        mesh=mesh,
        apply_fn=lambda p_, t: llama.apply(p_, cfg, t),
        init_fn=lambda k: llama.init(k, cfg),
        logical_axes=llama.param_logical_axes(cfg),
        train_config=TrainConfig(warmup_steps=10, total_steps=1000),
    )
    state = trainer.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)

    ledger = GoodputLedger()
    flops = trainer.step_flops(batch, seq)
    for i in range(steps):
        # step walls on the ledger's own clock (time.monotonic): mixing
        # clocks lets bookings exceed wall by microseconds and trips
        # the conservation assert below
        t0 = time.monotonic()
        state, loss = trainer.step(state, tokens, targets)
        float(loss)  # device sync: the wall is the step, not dispatch
        ledger.note_step(i, time.monotonic() - t0,
                        tokens=batch * seq, flops=flops,
                        compiling=(i == 0))
    snap = ledger.snapshot()
    if not snap["conserved"]:
        raise AssertionError(
            f"goodput ledger failed conservation on the bench run: "
            f"booked {snap['booked_seconds']:.3f}s != wall "
            f"{snap['wall_seconds']:.3f}s "
            f"(unattributed {snap['seconds']['unattributed']:.3f}s)")
    gen = detect_generation()
    if verbose:
        s = snap["seconds"]
        print(f"# train-goodput steps={steps} "
              f"fraction={snap['goodput_fraction']:.3f} "
              f"compile={s['compile']:.2f}s "
              f"productive={s['productive']:.2f}s "
              f"tokens/s={snap['tokens_per_second']:.0f}",
              file=sys.stderr)
    return {
        "metric": f"train_goodput_fraction[tiny,{gen}]",
        "value": round(snap["goodput_fraction"], 4),
        "unit": "fraction",
        "vs_baseline": round(snap["goodput_fraction"], 4),
    }


def _decode_model(name: str):
    """(cfg, init_fn, family) for the decode benches: the llama bench
    configs plus the gemma family (BASELINE config #5 "Gemma-2B
    serving"). Gemma-2B serves bf16 weights for the same reason as
    bench-500m-serve: decode reads every param every step."""
    from kubeflow_tpu.models import gemma, llama
    from kubeflow_tpu.serving import engine as engine_lib

    if name == "gemma-tiny":
        return gemma.GEMMA_TINY, gemma.init, engine_lib.GEMMA_FAMILY
    if name == "gemma-2b":
        cfg = dataclasses.replace(gemma.GEMMA_2B,
                                  param_dtype=jnp.bfloat16)
        return cfg, gemma.init, engine_lib.GEMMA_FAMILY
    return bench_configs()[name], llama.init, engine_lib.LLAMA_FAMILY


def bench_decode(model: str, *, batch: int, prompt_len: int,
                 max_new: int, max_len: int, int8: bool = False,
                 verbose: bool = True) -> dict:
    """Serving decode throughput on the KV-cache scan engine."""
    from kubeflow_tpu.serving import engine as engine_lib
    from kubeflow_tpu.serving import quant

    cfg, init_fn, family = _decode_model(model)
    # jit the init: eager per-op dispatch is pathological over remote
    # PJRT transports (each op is a round-trip).
    params = jax.jit(lambda k: init_fn(k, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    if int8:
        # weight-only int8: the decode step's HBM read halves vs bf16,
        # which is the whole metric (MBU roofline) — quantize on device.
        params = jax.jit(quant.quantize_blocks)(params)
        jax.block_until_ready(params)
    eng = engine_lib.InferenceEngine(
        params, cfg, family,
        engine_lib.EngineConfig(max_len=max_len),
    )
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)

    # Isolate decode from prefill: time generate at max_new=1 (prefill +
    # one sampled token, zero scan steps) and at max_new; the difference
    # is pure decode-scan time for max_new - 1 tokens. Timing one full
    # generate would attribute the prompt's prefill FLOPs to "decode"
    # and understate tokens/s as prompts grow.
    from kubeflow_tpu.ops import attention

    attention.reset_impl_counts()
    for mn in (1, max_new):  # compile + warmup both entry points
        np.asarray(eng.generate(prompt, max_new=mn))
    attn_counts = attention.impl_counts()

    def best_of(mn: int, reps: int = 3) -> float:
        # min-of-reps is the standard noise filter for microbenchmarks;
        # np.asarray forces device-to-host sync (see bench_train note).
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(eng.generate(prompt, max_new=mn))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t_prefill = best_of(1)
    t_full = best_of(max_new)
    # Floor the difference at 5% of the full run: on tiny CPU smoke
    # configs, single-shot timing noise once made (full - prefill)
    # collapse to ~0 and the artifact reported a physically impossible
    # 1.4e10 tok/s. Decode of max_new-1 tokens can never truly be under
    # a twentieth of the full generate.
    dt = t_full - t_prefill
    if dt < 0.05 * t_full:
        print(f"# decode timing floored: full={t_full:.4f}s "
              f"prefill={t_prefill:.4f}s — reported tok/s is an upper "
              "bound from the 5% floor, not a measurement",
              file=sys.stderr)
        dt = 0.05 * t_full
    decoded = max_new - 1

    n_devices = len(jax.devices())
    tok_per_sec = batch * decoded / dt / n_devices

    # Bandwidth roofline: each decode step reads every param once plus the
    # valid KV cache slots (2 caches, avg fill over the run).
    gen = detect_generation()
    avg_len = prompt_len + max_new / 2
    kv_bytes = (2 * cfg.num_layers * batch * avg_len * cfg.num_kv_heads
                * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)
    # Actual leaf bytes (QTensor- and family-aware), not a llama-only
    # closed form.
    weight_bytes = quant.param_bytes(params)
    step_bytes = weight_bytes + kv_bytes
    # Per-step time bounds MBU; batch tokens amortize one weight read.
    step_time = dt / decoded
    vs = _share_of_peak(step_bytes / step_time, PEAK_HBM_GBS, gen)

    if verbose:
        print(
            f"# decode model={model} batch={batch} prompt={prompt_len} "
            f"max_new={max_new} tok/s={tok_per_sec:.1f} mbu/0.40={vs} "
            f"attn_impl={attn_counts}",
            file=sys.stderr,
        )
    return {
        "metric": ("serving_decode_tokens_per_sec_per_chip"
                   f"[{model}{'-int8' if int8 else ''},{gen}]"),
        "value": round(tok_per_sec, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": vs,
    }


def bench_decode_continuous(model: str, *, slots: int, prompt_len: int,
                            rounds: int, chunk: int, max_len: int,
                            verbose: bool = True) -> dict:
    """Steady-state decode through the CONTINUOUS slot engine at full
    occupancy — quantifies what the slot design (per-row cursors,
    scatter KV writes, chunked stepping) costs on-device vs the fused
    decode scan `bench_decode` times. Same model, same batch size, same
    MBU roofline normalization, so the two metrics are directly
    comparable in one artifact."""
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import engine as engine_lib
    from kubeflow_tpu.serving.continuous import ContinuousEngine

    cfg = bench_configs()[model]
    params = jax.jit(lambda k: llama.init(k, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    eng = engine_lib.InferenceEngine(
        params, cfg, engine_lib.LLAMA_FAMILY,
        engine_lib.EngineConfig(max_len=max_len),
    )
    ce = ContinuousEngine(eng, max_slots=slots)
    rng = np.random.default_rng(0)
    key = jax.random.key(1)
    st = ce.init_slots()
    # total decoded tokens across warmup + 3 timing reps — the cache
    # must hold them all so cursors never clamp mid-measurement
    budget = (3 * rounds + 1) * chunk
    assert prompt_len + budget <= max_len, (prompt_len, budget, max_len)
    sp = eng._resolve_sampling(
        np.zeros(slots, np.float32), np.zeros(slots, np.int64),
        np.ones(slots, np.float32), key, batch=slots)[0]
    mb = ce.blocks_per_slot
    for i in range(slots):
        # the batcher's admission: a slot's own run of blocks, the
        # whole prompt as one slice
        p = rng.integers(0, cfg.vocab_size, prompt_len).tolist()
        st = ce.adopt_slot(st, i, 1 + i * mb + np.arange(mb), 0, p[0])
        st, _, _, key = ce.append_rows(st, [i], [p], [prompt_len],
                                       [True], sp, key)
    st, toks, _, key = ce.step(st, sp, key, steps=chunk)  # compile + warm
    jax.block_until_ready(toks)
    decoded = rounds * chunk
    reps = []  # (dt, avg KV fill DURING this rep) — fill accumulates
    # across reps on one SlotState, so each rep's KV traffic differs;
    # MBU must use the WINNING rep's own fill or it undercounts.
    for r in range(3):
        start_fill = prompt_len + chunk + r * decoded
        t0 = time.perf_counter()
        for _ in range(rounds):
            st, toks, _, key = ce.step(st, sp, key, steps=chunk)
        jax.block_until_ready(toks)
        reps.append((time.perf_counter() - t0,
                     start_fill + decoded / 2))
    dt, avg_len = min(reps)
    n_devices = len(jax.devices())
    tok_per_sec = slots * decoded / dt / n_devices

    gen = detect_generation()
    kv_bytes = (2 * cfg.num_layers * slots * avg_len * cfg.num_kv_heads
                * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)
    step_bytes = param_bytes(cfg) + kv_bytes
    vs = _share_of_peak(step_bytes / (dt / decoded), PEAK_HBM_GBS, gen)
    if verbose:
        print(f"# decode-cont model={model} slots={slots} chunk={chunk} "
              f"tok/s={tok_per_sec:.1f} mbu/0.40={vs}", file=sys.stderr)
    return {
        "metric": ("serving_decode_tokens_per_sec_per_chip"
                   f"[{model}-cont,{gen}]"),
        "value": round(tok_per_sec, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": vs,
    }


def bench_decode_paged(model: str, *, slots: int, prompt_len: int,
                       max_new: int, requests: int, max_len: int,
                       block_size: int, verbose: bool = True) -> dict:
    """Repeated-prompt serving through the ContinuousBatcher's paged KV
    cache + radix prefix cache. Every request carries the SAME prompt,
    so after the first admission (the cold miss) each later admission
    should seed its prefill from cached blocks and compute only the
    uncacheable last token — the workload the prefix cache exists for.

    Headline: decoded tokens/s/chip. Extra metrics carry the cache's
    own evidence: hit rate (> 0 or the radix tree is dead), prompt
    tokens actually prefilled vs the `requests * prompt_len` a no-reuse
    baseline would compute (vs_baseline = baseline/actual, > 1 means
    reuse saved prefill work), tokens served from cache, and KV HBM
    bytes — pool blocks in use x block bytes vs the dense per-slot
    cache the paged pool replaced."""
    import asyncio

    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import engine as engine_lib
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    cfg = bench_configs()[model]
    params = jax.jit(lambda k: llama.init(k, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    eng = engine_lib.InferenceEngine(
        params, cfg, engine_lib.LLAMA_FAMILY,
        engine_lib.EngineConfig(max_len=max_len),
    )
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).tolist()
    warm = rng.integers(0, cfg.vocab_size, prompt_len).tolist()

    async def run():
        batcher = ContinuousBatcher(
            eng, asyncio.Lock(), max_slots=slots, chunk=4,
            kv_block_size=block_size)
        try:
            # compile + page-pool warm on a DIFFERENT prompt, then
            # snapshot the counters so the timed phase's stats are its
            # own (the warm prompt shares no prefix, so it costs pool
            # blocks but no hits)
            await batcher.submit(warm, max_new, ())
            base = batcher.prefix_cache_stats()
            t0 = time.perf_counter()
            # first request alone: its retirement donates the prompt's
            # blocks, making every later admission a deterministic hit
            # (concurrent first-wave admissions would share in-flight
            # anyway, but sequencing makes the measured rate exact)
            await batcher.submit(prompt, max_new, ())
            await asyncio.gather(*[
                batcher.submit(prompt, max_new, ())
                for _ in range(requests - 1)])
            dt = time.perf_counter() - t0
            stats = batcher.prefix_cache_stats()
            blocks_in_use = batcher.kv_blocks_in_use()
            blk_bytes = batcher.cengine.kv_block_bytes()
            anatomy = batcher.cache_ledger.snapshot()
            return dt, {k: stats[k] - base.get(k, 0)
                        for k in ("hits", "misses", "tokens_prefilled",
                                  "tokens_reused")}, \
                blocks_in_use, blk_bytes, anatomy
        finally:
            await batcher.close()

    dt, stats, blocks_in_use, blk_bytes, anatomy = asyncio.run(run())
    n_devices = len(jax.devices())
    tok_per_sec = requests * max_new / dt / n_devices
    hit_rate = stats["hits"] / max(1, stats["hits"] + stats["misses"])
    no_reuse = requests * prompt_len  # every prompt fully prefilled
    prefilled = stats["tokens_prefilled"]
    paged_bytes = blocks_in_use * blk_bytes
    dense_bytes = eng.kv_cache_bytes(slots)

    gen = detect_generation()
    # cache anatomy (ISSUE 13): recent-window reuse-distance quantiles
    # (in admissions — how far apart touches of the same block land)
    # and the eviction-cause mix from the block lifecycle ledger. The
    # bench is the offline half of the sizing walkthrough in
    # docs/operator-guide.md: reuse-distance p95 vs pool capacity says
    # whether kv_pool_blocks has headroom.
    reuse_p50 = anatomy["reuse_distance"]["p50"] or 0.0
    reuse_p95 = anatomy["reuse_distance"]["p95"] or 0.0
    cause_mix = {c: anatomy["frees"].get(c, 0)
                 for c in ("lru", "pressure", "refdrop")}
    if verbose:
        print(f"# decode-paged model={model} slots={slots} "
              f"requests={requests} tok/s={tok_per_sec:.1f} "
              f"hit_rate={hit_rate:.3f} prefilled={prefilled} "
              f"reused={stats['tokens_reused']} "
              f"kv_bytes={paged_bytes} (dense {dense_bytes})",
              file=sys.stderr)
        print(f"# decode-paged reuse_distance p50={reuse_p50} "
              f"p95={reuse_p95} eviction_mix={cause_mix} "
              f"conserved={anatomy['conserved']}", file=sys.stderr)
    return {
        "metric": ("serving_decode_tokens_per_sec_per_chip"
                   f"[{model}-paged,{gen}]"),
        "value": round(tok_per_sec, 2),
        "unit": "tokens/s/chip",
        # prefill-work saving vs a no-reuse baseline; > 1 == cache won
        "vs_baseline": round(no_reuse / max(1, prefilled), 4),
        "extra_metrics": [
            {"metric": f"serving_prefix_cache_hit_rate[{model},{gen}]",
             "value": round(hit_rate, 4), "unit": "ratio",
             "vs_baseline": round(hit_rate, 4)},
            {"metric": f"serving_prefill_tokens_computed[{model},{gen}]",
             "value": float(prefilled), "unit": "tokens",
             "vs_baseline": round(no_reuse / max(1, prefilled), 4)},
            {"metric": f"serving_prefill_tokens_reused[{model},{gen}]",
             "value": float(stats["tokens_reused"]), "unit": "tokens",
             "vs_baseline": round(
                 stats["tokens_reused"] / max(1, no_reuse), 4)},
            {"metric": f"serving_kv_hbm_bytes_paged[{model},{gen}]",
             "value": float(paged_bytes), "unit": "bytes",
             "vs_baseline": round(
                 dense_bytes / max(1, paged_bytes), 4)},
            {"metric": f"serving_kv_reuse_distance_p50[{model},{gen}]",
             "value": float(reuse_p50), "unit": "admissions",
             "vs_baseline": 1.0},
            {"metric": f"serving_kv_reuse_distance_p95[{model},{gen}]",
             "value": float(reuse_p95), "unit": "admissions",
             "vs_baseline": 1.0},
            *[{"metric":
               f"serving_kv_evictions_{c}[{model},{gen}]",
               "value": float(n), "unit": "blocks",
               "vs_baseline": 1.0}
              for c, n in cause_mix.items()],
        ],
    }


def bench_decode_spec_paged(model: str, *, slots: int, prompt_len: int,
                            max_new: int, requests: int, max_len: int,
                            block_size: int, gamma: int,
                            verbose: bool = True) -> dict:
    """Speculative decoding folded into the continuous/paged engine
    (ISSUE 9), A/B'd against the SAME batcher with speculation off on
    the same request mix. Self-draft (draft == target): under greedy
    sampling every proposal accepts, so the measured ratio is the
    upper bound of the speculation win at this gamma — each round
    replaces gamma + 1 sequential decode dispatches with gamma batched
    draft forwards plus ONE fused paged verify. A real deployment's
    ratio scales with its draft's acceptance rate (reported as an
    extra metric straight from the batcher's own counters)."""
    import asyncio

    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import engine as engine_lib
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    cfg = bench_configs()[model]
    params = jax.jit(lambda k: llama.init(k, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    eng = engine_lib.InferenceEngine(
        params, cfg, engine_lib.LLAMA_FAMILY,
        engine_lib.EngineConfig(max_len=max_len),
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(requests)]
    warm = rng.integers(0, cfg.vocab_size, prompt_len).tolist()

    def run(draft):
        async def go():
            b = ContinuousBatcher(
                eng, asyncio.Lock(), max_slots=slots, chunk=4,
                kv_block_size=block_size, draft=draft,
                spec_gamma=gamma)
            try:
                await b.submit(warm, max_new, ())  # compile + warm
                t0 = time.perf_counter()
                await asyncio.gather(*[
                    b.submit(p, max_new, ()) for p in prompts])
                dt = time.perf_counter() - t0
                return dt, b.spec_proposed, b.spec_accepted
            finally:
                await b.close()

        return asyncio.run(go())

    plain_dt, _, _ = run(None)
    dt, proposed, accepted = run(eng)
    n_devices = len(jax.devices())
    tok_per_sec = requests * max_new / dt / n_devices
    plain_tok_s = requests * max_new / plain_dt / n_devices
    accept_rate = accepted / max(1, proposed)

    gen = detect_generation()
    if verbose:
        print(f"# decode-spec-paged model={model} slots={slots} "
              f"gamma={gamma} tok/s={tok_per_sec:.1f} "
              f"(plain {plain_tok_s:.1f}, "
              f"x{tok_per_sec / plain_tok_s:.2f}) "
              f"accept={accept_rate:.3f} "
              f"({accepted}/{proposed})", file=sys.stderr)
    return {
        "metric": ("serving_decode_tokens_per_sec_per_chip"
                   f"[{model}-spec,{gen}]"),
        "value": round(tok_per_sec, 2),
        "unit": "tokens/s/chip",
        # > 1 == speculation beat plain decode on this workload
        "vs_baseline": round(tok_per_sec / max(plain_tok_s, 1e-9), 4),
        "extra_metrics": [
            {"metric": f"serving_spec_acceptance_rate[{model},{gen}]",
             "value": round(accept_rate, 4), "unit": "ratio",
             "vs_baseline": round(accept_rate, 4)},
        ],
    }


def bench_decode_spill(model: str, *, slots: int, prompt_len: int,
                       max_new: int, prompts: int, pool_blocks: int,
                       max_len: int, block_size: int,
                       verbose: bool = True) -> dict:
    """Host-RAM spill tier A/B (ISSUE 19): a working set of distinct
    prompts deliberately larger than the device pool, churned once
    cold and then re-requested. With the tier OFF every re-request
    recomputes the prefix the pool just evicted; with the tier ON the
    eviction demoted the blocks to host RAM and the re-request
    restores them with a host->device copy. Both arms run the same
    prompts on the same pool geometry; the re-request pass's
    per-request wall (full generation — the one-shot TTFT upper
    bound) is the compared number.

    Headline: re-request decoded tokens/s/chip with the tier ON
    (gated). The speedup ratio off/on is informational ("x"), like
    serving-disagg's: on a CPU runner both arms timeshare one core
    and the restore's host<->"device" copies are memcpys, so the win
    understates what a real PCIe host sees."""
    import asyncio

    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import engine as engine_lib
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    cfg = bench_configs()[model]
    params = jax.jit(lambda k: llama.init(k, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    eng = engine_lib.InferenceEngine(
        params, cfg, engine_lib.LLAMA_FAMILY,
        engine_lib.EngineConfig(max_len=max_len),
    )
    rng = np.random.default_rng(0)
    # distinct first blocks: each prompt parks its own chains in the
    # radix, so `prompts` of them overflow the pool deterministically
    prompt_set = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
                  for _ in range(prompts)]

    async def run(spill_bytes: int):
        batcher = ContinuousBatcher(
            eng, asyncio.Lock(), max_slots=slots, chunk=4,
            kv_block_size=block_size, kv_pool_blocks=pool_blocks,
            kv_spill_bytes=spill_bytes)
        try:
            # churn pass: cold prefills; evictions demote (tier on)
            # or discard (tier off). The first re-request pass warms
            # the restore path's one-time compiles (untimed); the
            # working set is 2x the pool, so the TIMED pass still
            # demotes/restores on every request — steady-state tier
            # traffic, not a warm-cache victory lap.
            for p in prompt_set + prompt_set:
                await batcher.submit(p, max_new, ())
            before = batcher.cache_ledger.snapshot()["spill"]
            walls = []
            t0 = time.perf_counter()
            for p in prompt_set:
                w0 = time.perf_counter()
                await batcher.submit(p, max_new, ())
                walls.append(time.perf_counter() - w0)
            dt = time.perf_counter() - t0
            anatomy = batcher.cache_ledger.snapshot()
            spill_delta = {k: anatomy["spill"][k] - before[k]
                           for k in ("demotions", "restores", "drops")}
            return dt, walls, anatomy, spill_delta
        finally:
            await batcher.close()

    off_dt, off_walls, off_anatomy, _ = asyncio.run(run(0))
    on_dt, on_walls, on_anatomy, spill = asyncio.run(run(64 << 20))
    assert off_anatomy["conserved"] and on_anatomy["conserved"], \
        "cache ledger out of balance under the spill A/B"
    if spill["restores"] < 1:
        raise RuntimeError(
            f"spill arm restored nothing in the timed pass (books: "
            f"{spill}) — the working set did not overflow the pool; "
            "the A/B measured two identical warm caches")

    n_devices = len(jax.devices())
    tok_per_sec = prompts * max_new / on_dt / n_devices
    p95 = lambda xs: float(np.percentile(np.asarray(xs), 95))  # noqa: E731
    off_p95, on_p95 = p95(off_walls), p95(on_walls)
    speedup = off_p95 / max(on_p95, 1e-9)
    gen = detect_generation()
    if verbose:
        print(f"# decode-spill model={model} prompts={prompts} "
              f"pool={pool_blocks} tok/s(on)={tok_per_sec:.1f} "
              f"rereq_p95 off={off_p95 * 1e3:.2f}ms "
              f"on={on_p95 * 1e3:.2f}ms x{speedup:.2f} "
              f"demotions={spill['demotions']} "
              f"restores={spill['restores']} drops={spill['drops']}",
              file=sys.stderr)
    return {
        "metric": ("serving_decode_tokens_per_sec_per_chip"
                   f"[{model}-spill,{gen}]"),
        "value": round(tok_per_sec, 2),
        "unit": "tokens/s/chip",
        # > 1 == restoring spilled blocks beat recomputing them
        "vs_baseline": round(speedup, 4),
        "extra_metrics": [
            {"metric": f"serving_spill_rereq_p95_ms[{model}-off,{gen}]",
             "value": round(off_p95 * 1e3, 3), "unit": "ms",
             "vs_baseline": 1.0},
            {"metric": f"serving_spill_rereq_p95_ms[{model}-on,{gen}]",
             "value": round(on_p95 * 1e3, 3), "unit": "ms",
             "vs_baseline": round(speedup, 4)},
            {"metric": f"serving_spill_restore_speedup[{model},{gen}]",
             "value": round(speedup, 4), "unit": "x",
             "vs_baseline": round(speedup, 4)},
            {"metric": f"serving_kv_spill_demotions[{model},{gen}]",
             "value": float(spill["demotions"]), "unit": "blocks",
             "vs_baseline": 1.0},
            {"metric": f"serving_kv_spill_restores[{model},{gen}]",
             "value": float(spill["restores"]), "unit": "blocks",
             "vs_baseline": 1.0},
        ],
    }


def bench_decode_cont_ttft(model: str, *, slots: int, short_len: int,
                           long_len: int, budget: int, max_len: int,
                           block_size: int,
                           verbose: bool = True) -> dict:
    """TTFT of a SHORT interactive request that arrives just after a
    LONG prompt was submitted — the collision chunked prefill exists
    for: with `prefill_chunk_tokens=budget` the long prompt trickles
    in budget-size slices and the shortest-remaining-first scheduler
    finishes the short prompt ahead of it."""
    import asyncio

    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import engine as engine_lib
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    cfg = bench_configs()[model]
    params = jax.jit(lambda k: llama.init(k, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    eng = engine_lib.InferenceEngine(
        params, cfg, engine_lib.LLAMA_FAMILY,
        engine_lib.EngineConfig(max_len=max_len),
    )
    rng = np.random.default_rng(0)

    async def go():
        b = ContinuousBatcher(
            eng, asyncio.Lock(), max_slots=slots, chunk=4,
            kv_block_size=block_size, prefill_chunk_tokens=budget)
        try:
            # compile the prefill slice + decode before timing
            await asyncio.gather(
                b.submit(rng.integers(
                    0, cfg.vocab_size, long_len).tolist(), 2, ()),
                b.submit(rng.integers(
                    0, cfg.vocab_size, short_len).tolist(), 2, ()))
            ttfts = []
            for _ in range(3):  # fresh prompts: no radix shortcut
                long_p = rng.integers(
                    0, cfg.vocab_size, long_len).tolist()
                short_p = rng.integers(
                    0, cfg.vocab_size, short_len).tolist()
                fut_l = asyncio.ensure_future(
                    b.submit(long_p, 2, ()))
                await asyncio.sleep(0)  # long enqueues FIRST
                t0 = time.perf_counter()
                fut_s, q = b.open_stream(short_p, 2, ())
                tok = await q.get()
                ttfts.append(time.perf_counter() - t0)
                while tok is not None:  # drain the stream
                    tok = await q.get()
                await fut_s
                await fut_l
            return min(ttfts)
        finally:
            await b.close()

    chunk_s = asyncio.run(go())
    gen = detect_generation()
    if verbose:
        print(f"# decode-cont-ttft model={model} long={long_len} "
              f"short={short_len} budget={budget} "
              f"ttft={chunk_s * 1e3:.1f}ms", file=sys.stderr)
    return {
        "metric": f"serving_interactive_ttft_ms[{model}-cont,{gen}]",
        "value": round(chunk_s * 1e3, 2),
        "unit": "ms",
        "vs_baseline": 1.0,
    }


def bench_attribution(model: str, *, slots: int, prompt_len: int,
                      max_new: int, max_len: int,
                      verbose: bool = True) -> dict:
    """Step-anatomy attribution (ISSUE 8): WHERE the continuous
    batcher's wall time goes, phase by phase, against the fused
    one-shot decode scan on the SAME weights and shapes — the measured
    explanation for the decode-cont vs decode gap in the bench artifact
    (r05: 6.9k vs 10.7k tok/s/chip, 0.37x).

    Method: the one-shot side reuses bench_decode's prefill-subtracted
    timing (generate at max_new=1 vs max_new). The continuous side runs
    the same request mix TWICE through one `ContinuousBatcher` and
    DIFFS its PhaseProfiler totals across the second run, so the
    attribution is steady state — the first pass eats every compile.
    The profiler's invariant makes the second-pass phase sums reconcile
    against the independently measured wall time (asserted at 5% here;
    `reconciliation` in the payload is the measured ratio)."""
    import asyncio

    from kubeflow_tpu.serving import engine as engine_lib
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    cfg, init_fn, family = _decode_model(model)
    params = jax.jit(lambda k: init_fn(k, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    eng = engine_lib.InferenceEngine(
        params, cfg, family, engine_lib.EngineConfig(max_len=max_len))
    rng = np.random.default_rng(0)

    # -- one-shot side (bench_decode's method, same engine) -----------
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (slots, prompt_len)), jnp.int32)
    for mn in (1, max_new):  # compile + warmup both entry points
        np.asarray(eng.generate(prompt, max_new=mn))

    def best_of(mn: int, reps: int = 3) -> float:
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(eng.generate(prompt, max_new=mn))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t_prefill = best_of(1)
    t_full = best_of(max_new)
    one_decoded = slots * (max_new - 1)
    one_phases = {"prefill": t_prefill,
                  "decode": max(t_full - t_prefill, 1e-9)}

    # -- continuous side ----------------------------------------------
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(slots)]

    async def run():
        b = ContinuousBatcher(eng, asyncio.Lock(), max_slots=slots)
        for _ in range(2):  # warmup: pass 1 compiles the decode path,
            # pass 2 the deferred slot-recycle program (pass 1's
            # retirements park the loop idle, so their reset runs —
            # and first-compiles — at the NEXT wake)
            await asyncio.gather(
                *(b.submit(p, max_new, ()) for p in prompts))
        before = b.profiler.totals()
        tok_before = b.profiler.phase_tokens()
        t0 = time.perf_counter()
        await asyncio.gather(  # the measured steady-state window
            *(b.submit(p, max_new, ()) for p in prompts))
        wall = time.perf_counter() - t0
        after = b.profiler.totals()
        tok_after = b.profiler.phase_tokens()
        recompiles = dict(b.compile_watch.counts())
        goodput = b.profiler.goodput()
        await b.close()
        phases = {p: after[p] - before.get(p, 0.0)
                  for p in after if p != "idle"}
        decoded = (tok_after.get("decode", 0)
                   - tok_before.get("decode", 0))
        return phases, decoded, wall, recompiles, goodput

    cont_phases, cont_decoded, cont_wall, recompiles, goodput = (
        asyncio.run(run()))
    cont_decoded = max(cont_decoded, 1)

    # Attribution invariant: the non-idle phase sums of the measured
    # window must explain the independently clocked wall.
    recon = sum(cont_phases.values()) / cont_wall if cont_wall else 0.0
    recon_ok = abs(1.0 - recon) <= 0.05

    # Per-decoded-token gap, phase by phase: the one-shot side only has
    # prefill + decode; every other continuous phase is pure overhead
    # the fused scan never pays.
    one_per_tok = {p: s / one_decoded for p, s in one_phases.items()}
    gap = {p: s / cont_decoded - one_per_tok.get(p, 0.0)
           for p, s in cont_phases.items()}
    top_phase = max(gap, key=lambda p: gap[p])
    gap_total = (cont_wall / cont_decoded) - (t_full / one_decoded)
    top_share = (gap[top_phase] / gap_total) if gap_total > 0 else 0.0

    n_devices = len(jax.devices())
    cont_tok_s = cont_decoded / cont_wall / n_devices
    one_tok_s = one_decoded / t_full / n_devices
    gen = detect_generation()
    if verbose:
        print(f"# attribution model={model} slots={slots} "
              f"cont={cont_tok_s:.1f} one-shot={one_tok_s:.1f} tok/s "
              f"(x{cont_tok_s / one_tok_s:.2f}) recon={recon:.3f} "
              f"{'OK' if recon_ok else 'FAIL(>5%)'}", file=sys.stderr)
        for p in sorted(cont_phases, key=lambda p: -cont_phases[p]):
            print(f"#   {p:<11} cont={cont_phases[p] * 1e3:8.2f}ms "
                  f"({cont_phases[p] / cont_wall * 100:5.1f}%)  "
                  f"gap={gap[p] * 1e6:+9.1f}us/tok"
                  f"{'   <-- top gap' if p == top_phase else ''}",
                  file=sys.stderr)
        print(f"# recompiles(pass1+2)={recompiles} "
              f"goodput={goodput['goodput_ratio']:.3f}", file=sys.stderr)
    extras = [
        {"metric": f"serving_attribution_top_gap[{top_phase},"
                   f"{model},{gen}]",
         "value": round(top_share, 4), "unit": "fraction_of_gap",
         "vs_baseline": round(cont_tok_s / one_tok_s, 4)},
    ]
    extras += [
        {"metric": f"serving_step_phase_ms_per_ktok[{p},{model},{gen}]",
         "value": round(s / cont_decoded * 1e6, 3), "unit": "ms/ktok",
         "vs_baseline": round(s / cont_wall, 4)}
        for p, s in sorted(cont_phases.items(), key=lambda kv: -kv[1])
        if s > 0
    ]
    return {
        "metric": f"serving_attribution_reconciliation[{model},{gen}]",
        "value": round(recon, 4),
        "unit": "phase_sum_over_wall",
        "vs_baseline": round(goodput["goodput_ratio"], 4),
        "extra_metrics": extras,
    }


def bench_decode_paged_kernel(*, b: int, n_q: int, n_kv: int, hd: int,
                              block_size: int, blocks_per_slot: int,
                              iters: int, interpret: bool = False,
                              verbose: bool = True) -> dict:
    """Ops-level A/B of the two paged-attention impls on one synthetic
    pool: the XLA gather (materializes every row's full
    `blocks_per_slot * block_size` window) vs the fused Pallas kernel
    (walks the block table in-kernel). `interpret=True` is what the
    explicit CPU sweep passes: its tokens/s is then a numerics vehicle,
    not a speed claim — the HBM model below is the portable number.

    Timed at LOW fill — the regime the fused kernel exists for: a
    long-max_len pool where most of each row's window is dead. Per-step
    HBM bytes are modeled from what each impl demonstrably reads
    (tests/test_paged_attention_kernel.py's NaN-poison test): gather =
    full window regardless of fill; fused = each row's live blocks,
    `ceil((cursor+1)/block_size)`. Reported at two fills so the
    artifact shows fused bytes SCALING WITH FILL while gather stays
    flat — vs_baseline on the byte entries is gather/fused, the
    modeled traffic saving."""
    from kubeflow_tpu.ops.attention import paged_attention

    width = blocks_per_slot * block_size
    num_blocks = 1 + b * blocks_per_slot
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, 1, n_q, hd)), jnp.float32)
    kp = jnp.asarray(
        rng.normal(size=(num_blocks, block_size, n_kv, hd)), jnp.float32)
    vp = jnp.asarray(
        rng.normal(size=(num_blocks, block_size, n_kv, hd)), jnp.float32)
    # each row owns a disjoint live chain; tails point at trash block 0
    fill_lo, fill_hi = width // 8 - 1, width - 1
    pos = np.full((b,), fill_lo, np.int32)
    table = np.zeros((b, blocks_per_slot), np.int32)
    for i in range(b):
        live = pos[i] // block_size + 1
        table[i, :live] = 1 + i * blocks_per_slot + np.arange(live)
    table = jnp.asarray(table)
    qpos = jnp.asarray(pos)[:, None]
    kvpos = jnp.broadcast_to(
        jnp.arange(width, dtype=jnp.int32)[None], (b, width))
    mask = jnp.ones((b, width), bool)

    def timed(impl: str) -> float:
        fn = jax.jit(lambda *a: paged_attention(
            *a, causal=True, impl=impl,
            interpret=interpret if impl == "pallas" else None))
        jax.block_until_ready(fn(q, kp, vp, table, qpos, kvpos))
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(q, kp, vp, table, qpos, kvpos)
        jax.block_until_ready(out)
        return b * iters / (time.perf_counter() - t0)

    xla_tok_s = timed("xla")
    pallas_tok_s = timed("pallas")

    cell_bytes = 2 * n_kv * hd * kp.dtype.itemsize  # K + V per cell
    gather_bytes = b * width * cell_bytes  # fill-independent
    def fused_bytes(fill):
        return b * (fill // block_size + 1) * block_size * cell_bytes

    gen = detect_generation()
    if verbose:
        print(f"# decode-paged-kernel b={b} width={width} "
              f"fill={fill_lo} xla_tok/s={xla_tok_s:.1f} "
              f"pallas_tok/s={pallas_tok_s:.1f} "
              f"hbm_gather={gather_bytes} "
              f"hbm_fused@{fill_lo}={fused_bytes(fill_lo)} "
              f"hbm_fused@{fill_hi}={fused_bytes(fill_hi)}",
              file=sys.stderr)
    return {
        "metric": f"paged_attention_fused_tokens_per_sec[{gen}]",
        "value": round(pallas_tok_s, 2),
        "unit": "tokens/s",
        # measured step-rate ratio vs the gather at the same low fill
        "vs_baseline": round(pallas_tok_s / max(1e-9, xla_tok_s), 4),
        "extra_metrics": [
            {"metric": f"paged_attention_gather_tokens_per_sec[{gen}]",
             "value": round(xla_tok_s, 2), "unit": "tokens/s",
             "vs_baseline": 1.0},
            {"metric": f"paged_attention_hbm_bytes_gather[{gen}]",
             "value": float(gather_bytes), "unit": "bytes/step",
             "vs_baseline": 1.0},
            {"metric": ("paged_attention_hbm_bytes_fused"
                        f"[fill={fill_lo},{gen}]"),
             "value": float(fused_bytes(fill_lo)), "unit": "bytes/step",
             "vs_baseline": round(
                 gather_bytes / fused_bytes(fill_lo), 4)},
            {"metric": ("paged_attention_hbm_bytes_fused"
                        f"[fill={fill_hi},{gen}]"),
             "value": float(fused_bytes(fill_hi)), "unit": "bytes/step",
             "vs_baseline": round(
                 gather_bytes / fused_bytes(fill_hi), 4)},
        ],
    }


def bench_serving_disagg(*, clients: int = 12, requests: int = 48,
                         max_new: int = 16,
                         verbose: bool = True) -> dict:
    """Disaggregated prefill/decode pools vs an equal-total symmetric
    fleet (ISSUE 12), measured by the loadtest's `--mode disagg` A/B:
    real router + replica subprocesses, mixed long-prompt/short-decode
    traffic, cross-arm token parity, and a SIGKILLed prefill replica
    after the timed window. Headline = the disagg arm's aggregate
    client tokens/s; vs_baseline = disagg/symmetric (> 1 == the split
    fleet out-served the same replica count mixed)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serving_loadtest",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "loadtest", "serving_loadtest.py"))
    lt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lt)
    r = lt.run_disagg(clients, requests, max_new)
    if r["client_failures"] or not r["token_parity"]:
        raise RuntimeError(
            f"disagg A/B failed its own bars: failures="
            f"{r['client_failures']} parity={r['token_parity']}")
    gen = detect_generation()
    if verbose:
        print(f"# serving-disagg pools={r['prefill_replicas']}p+"
              f"{r['decode_replicas']}d tok/s={r['tokens_per_sec']} "
              f"(symmetric {r['symmetric_tokens_per_sec']}) "
              f"speedup={r['disagg_speedup']} "
              f"handoff={r['handoff']}", file=sys.stderr)
    return {
        "metric": f"serving_disagg_tokens_per_sec[tiny,{gen}]",
        "value": r["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": r["disagg_speedup"],
        "extra_metrics": [
            # informational ("x"), not gated: on a shared/1-core CI
            # host the 5-subprocess A/B is scheduling-noise-dominated
            # and the ratio swings well past the gate tolerance; the
            # parity and zero-failure bars above are the hard claims.
            # The symmetric control arm's absolute tok/s is headline
            # value divided by this ratio — not emitted separately so
            # the gate doesn't hold a second noisy throughput.
            {"metric": f"serving_disagg_speedup[tiny,{gen}]",
             "value": r["disagg_speedup"], "unit": "x",
             "vs_baseline": r["disagg_speedup"]},
            {"metric": f"serving_disagg_handoff_bytes[tiny,{gen}]",
             "value": float(r["handoff_bytes"]), "unit": "bytes",
             "vs_baseline": 1.0},
        ],
    }


def bench_scenario_replay(*, scenario: str = "tenant_flood",
                          fidelity_pct: float = 10.0,
                          verbose: bool = True) -> dict:
    """Record/replay fidelity of the scenario engine (ISSUE 20): replay
    the committed tenant-flood trace against a live continuous server,
    capture the run off the server's timeline store, then replay the
    RECORDING interleaved with the original against the same warm
    engine (the loadtest's paired fidelity path). Headline = fidelity
    headroom, 1 - delta/budget, where delta is the paired
    |recorded - original| p95-TTFT fraction and budget is the run's
    own assertion bound — unit "ratio" so the gate holds it
    higher-is-better: headroom collapsing toward 0 means the recorder
    is drifting from what it observed. The absolute TTFT p95s ride
    along in ms, informational: on a shared CPU runner absolute
    service rate swings run to run, while the paired delta stays
    stable — which is exactly why the delta-derived number is the
    gated one."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serving_loadtest",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "loadtest", "serving_loadtest.py"))
    lt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lt)
    r = lt.run_scenario(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "loadtest", "scenarios", f"{scenario}.jsonl"),
        target="single", max_batch=1, fidelity_pct=fidelity_pct)
    # run_scenario already raised on expect violations, client
    # failures, lost recordings, or a delta past the budget; reaching
    # here means the hard bars held — the gate's only job is to catch
    # headroom EROSION across commits.
    fid = r["fidelity"]
    delta = fid["delta_frac"]
    budget = fid["max_frac"]
    headroom = round(1.0 - delta / budget, 4)
    gen = detect_generation()
    label = scenario.replace("_", "-")
    if verbose:
        print(f"# scenario-replay {r['scenario']} "
              f"offered={r['offered']} completed={r['completed']} "
              f"p95 orig={fid['orig_ttft_p95_s']}s "
              f"recorded={fid['recorded_ttft_p95_s']}s "
              f"delta={delta:.2%} (budget {budget:.0%})",
              file=sys.stderr)
    return {
        "metric": f"scenario_replay_fidelity_headroom[{label},{gen}]",
        "value": headroom,
        "unit": "ratio",
        "vs_baseline": headroom,
        "extra_metrics": [
            {"metric":
                f"scenario_replay_fidelity_delta[{label},{gen}]",
             "value": delta, "unit": "fraction",
             "vs_baseline": headroom},
            {"metric":
                f"scenario_replay_ttft_p95_ms[{label}-orig,{gen}]",
             "value": round(fid["orig_ttft_p95_s"] * 1000.0, 3),
             "unit": "ms", "vs_baseline": 1.0},
            {"metric":
                f"scenario_replay_ttft_p95_ms[{label}-recorded,{gen}]",
             "value": round(fid["recorded_ttft_p95_s"] * 1000.0, 3),
             "unit": "ms", "vs_baseline": 1.0},
        ],
    }


def bench_mnist(*, steps: int = 200, batch: int = 256,
                verbose: bool = True) -> dict:
    """BASELINE config #1: MNIST-MLP smoke train (images/s + accuracy).

    The throughput loop rotates real dataset batches (cycling the
    loader, not hammering one cached batch) so the measured step is the
    one a notebook user runs; quality rides along as test accuracy
    after the timed epoch-and-a-half and gates vs_baseline — a fast
    wrong model must not score."""
    from kubeflow_tpu.models import mnist

    x_tr, y_tr, x_te, y_te = mnist.load_dataset()
    params = mnist.init(jax.random.key(0))
    lr = 0.1

    @jax.jit
    def step(params, x, y):
        (loss, _), grads = jax.value_and_grad(
            mnist.loss_and_accuracy, has_aux=True)(params, x, y)
        return jax.tree.map(lambda p, g: p - lr * g, params, grads), loss

    def batch_iter():
        epoch = 0
        while True:
            for xb, yb in mnist.batches(x_tr, y_tr, batch, seed=epoch):
                yield jnp.asarray(xb), jnp.asarray(yb)
            epoch += 1

    it = batch_iter()
    xb, yb = next(it)
    params, loss = step(params, xb, yb)  # compile
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        xb, yb = next(it)
        params, loss = step(params, xb, yb)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    images_per_sec = steps * batch / dt
    _, acc = mnist.loss_and_accuracy(
        params, jnp.asarray(x_te), jnp.asarray(y_te))
    acc = float(acc)
    gen = detect_generation()
    if verbose:
        print(f"# mnist steps={steps} batch={batch} "
              f"images/s={images_per_sec:.0f} test_acc={acc:.3f}",
              file=sys.stderr)
    return {
        "metric": f"mnist_train_images_per_sec[mlp,{gen}]",
        "value": round(images_per_sec, 1),
        "unit": "images/s",
        # quality gate, not a speed ratio: the smoke target is a model
        # that actually separates the classes (>= 0.90 on the held-out
        # split; the synthetic stand-in saturates ~0.95+)
        "vs_baseline": round(acc / 0.90, 4),
    }


def bench_vit(model: str, *, batch: int, steps: int, warmup: int = 2,
              verbose: bool = True) -> dict:
    """BASELINE config #2: ViT fine-tune throughput under the sharded
    Trainer (images/s + MFU). `model` is a kubeflow_tpu.models.vit
    CONFIGS key ("tiny" CPU twin / "vit-b16" the real v5e-1 config)."""
    if warmup < 1:
        # the first step is the compile; timing without one warm step
        # measures compilation, and `loss` below is bound in the
        # warmup loop
        raise ValueError(f"warmup must be >= 1, got {warmup}")
    from kubeflow_tpu.models import vit
    from kubeflow_tpu.parallel import MeshSpec, create_mesh
    from kubeflow_tpu.train import Trainer, TrainConfig

    cfg = vit.CONFIGS[model]
    n_devices = len(jax.devices())
    mesh = create_mesh(MeshSpec(data=1, fsdp=n_devices, tensor=1))
    batch = -(-batch // n_devices) * n_devices
    trainer = Trainer(
        mesh=mesh,
        # Trainer's CE loss is next-token over [b, s, vocab]; ViT emits
        # [b, classes] — a singleton seq dim makes the SAME Trainer
        # drive both (tests/test_models.py sharded-smoke wiring).
        apply_fn=lambda p, imgs: vit.apply(p, cfg, imgs)[:, None, :],
        init_fn=lambda k: vit.init(k, cfg),
        logical_axes=vit.param_logical_axes(cfg),
        train_config=TrainConfig(warmup_steps=10, total_steps=1000),
    )
    state = trainer.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.normal(size=(
        batch, cfg.image_size, cfg.image_size, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.num_classes, (batch, 1)),
                    jnp.int32)
    w = jnp.ones((batch, 1), jnp.float32)
    for _ in range(warmup):
        state, loss = trainer.step(state, imgs, y, w)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = trainer.step(state, imgs, y, w)
    float(loss)
    dt = time.perf_counter() - t0
    del state, trainer

    images_per_sec = batch * steps / dt / n_devices
    n_params = int(sum(np.prod(l.shape) for l in jax.tree.leaves(
        jax.eval_shape(lambda k: vit.init(k, cfg), jax.random.key(0)))))
    # 6*N per processed token (fwd+bwd matmuls) x seq tokens per image,
    # plus attention — same accounting as model_flops_per_token.
    seq = cfg.seq_len
    attn_flops = 12 * cfg.num_layers * cfg.num_heads * cfg.head_dim * seq
    flops_per_image = (6 * n_params + attn_flops) * seq
    gen = detect_generation()
    vs = _share_of_peak(images_per_sec * flops_per_image, PEAK_FLOPS, gen)
    if verbose:
        print(f"# vit model={model} batch={batch} devices={n_devices} "
              f"images/s={images_per_sec:.1f} mfu/0.40={vs}",
              file=sys.stderr)
    return {
        "metric": f"vit_train_images_per_sec_per_chip[{model},{gen}]",
        "value": round(images_per_sec, 2),
        "unit": "images/s/chip",
        "vs_baseline": vs,
    }


def first_compile_metric() -> dict:
    assert _first_compile_s is not None, "run a train bench first"
    return {
        "metric": "pod_to_first_xla_compile_seconds",
        "value": round(_first_compile_s, 2),
        "unit": "s",
        "vs_baseline": round(FIRST_COMPILE_BUDGET_S / _first_compile_s, 4),
    }


# flash4k runs LAST: its one earlier chip window ended with the device
# hung at seq 4096 (docs/perf-notes.md "Hardware status"); until that is
# reproduced or cleared (ROADMAP S5) the sections before it keep their
# numbers.
ALL_SECTIONS = ("train500m", "train1b", "train-zero", "train-goodput",
                "decode", "decode-int8", "decode-cont", "decode-paged",
                "decode-spill", "decode-spec-paged",
                "decode-paged-kernel", "decode-gemma", "serving-disagg",
                "scenario-replay", "mnist", "vit", "flash4k")
# Presets sized for a chip: not in the explicit [tiny,cpu] smoke sweep
# ci/bench_gate.py reads.
_TPU_ONLY_SECTIONS = ("train1b", "flash4k")
# Sections whose work runs in CPU-pinned child processes
# (loadtest/serving_loadtest.py replicas): nothing stamped `backend:
# tpu` may hold a CPU timing, and this process holds the chip.
_CPU_CHILD_SECTIONS = ("serving-disagg", "scenario-replay")


def _sweep_for(backend: str, wanted: list[str], p) -> list[str]:
    if backend == "cpu":
        sweep = [s for s in ALL_SECTIONS if s not in _TPU_ONLY_SECTIONS]
    else:
        sweep = [s for s in ALL_SECTIONS if s not in _CPU_CHILD_SECTIONS]
        if len(jax.devices()) < 4:
            sweep.remove("train-zero")
    if wanted:
        unavailable = [s for s in wanted if s not in sweep]
        if unavailable:
            p.error(f"--only entries {unavailable} do not run on this "
                    f"backend ({backend}, {len(jax.devices())} devices)")
        sweep = [s for s in sweep if s in wanted]
    return sweep


def _marker(name: str) -> dict:
    """Zero-valued artifact entry recording a section that raised — the
    run still exits non-zero (`_run_sweep`)."""
    return {"metric": name, "value": 0.0, "unit": "error",
            "vs_baseline": 0.0}


# Set by main() from --json-out.
_json_out_path: str | None = None


def _emit_result(headline: dict | None, extras: list[dict],
                 backend: str) -> int:
    """Print the single-JSON-line artifact, stamped with the device as
    JAX reports it."""
    assert headline is not None, "empty sweep"
    result = dict(headline)
    result["backend"] = backend
    result["device"] = device_stamp()
    if extras:
        result["extra_metrics"] = extras
    line = json.dumps(result)
    print(line)
    if _json_out_path:
        # same line, durably on disk — the machine-readable artifact
        # ci/bench_gate.py compares against the committed baseline
        with open(_json_out_path, "w") as f:
            f.write(line + "\n")
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default="",
                   help="comma-separated subset: train500m,train1b,"
                        "flash4k,decode,decode-int8,decode-cont,"
                        "decode-paged,decode-spill,decode-spec-paged,"
                        "decode-paged-kernel,scenario-replay (default: "
                        "full sweep for the backend)")
    p.add_argument("--json-only", action="store_true")
    p.add_argument("--json-out", default="",
                   help="also write the sweep's single JSON artifact "
                        "line to this path (the bench-gate input)")
    p.add_argument("--attribution", action="store_true",
                   help="run the step-anatomy attribution study instead "
                        "of the sweep: phase-by-phase breakdown of the "
                        "continuous batcher vs the one-shot decode scan "
                        "(the decode-cont gap, explained)")
    args = p.parse_args()
    if args.json_out:
        global _json_out_path
        _json_out_path = args.json_out

    compile_cache.enable()
    # One process, and it owns the chip: whatever backend JAX attached
    # is the backend of every number below. There is no probe and no
    # fallback — a TPU that fails to attach fails the run right here.
    backend = jax.default_backend()

    if args.attribution:
        # A debug study, not an artifact section: the numbers feed
        # docs/perf-notes.md, not the bench gate.
        if backend == "tpu":
            m = bench_attribution(
                "bench-500m-serve", slots=16, prompt_len=128,
                max_new=32, max_len=512, verbose=not args.json_only)
        else:
            m = bench_attribution(
                "tiny", slots=2, prompt_len=8, max_new=8, max_len=64,
                verbose=not args.json_only)
        return _emit_result(m, m.pop("extra_metrics", []), backend)

    wanted: list[str] = []
    if args.only:
        wanted = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [s for s in wanted if s not in ALL_SECTIONS]
        if unknown:
            p.error(f"unknown --only entries {unknown}; known: "
                    f"{list(ALL_SECTIONS)}")
    sweep = _sweep_for(backend, wanted, p)
    return _run_sweep(sweep, backend, json_only=args.json_only)


def _run_sweep(sweep: list[str], backend: str, *, json_only: bool) -> int:
    on_tpu = backend == "tpu"
    verbose = not json_only
    headline = None
    extras: list[dict] = []

    def emit(m: dict) -> None:
        nonlocal headline
        if headline is None:
            headline = m
        else:
            extras.append(m)

    failed: list[str] = []

    def guarded(label: str, fn) -> None:
        """A section that raises does not stop the ones after it, but it
        fails the run: the artifact keeps its marker and the exit code
        is non-zero."""
        try:
            emit(fn())
        except Exception as e:  # noqa: BLE001 — boundary: record, go on
            traceback.print_exc()
            print(f"# bench {label} FAILED: {e}", file=sys.stderr)
            failed.append(label)
            extras.append(_marker(f"{label}[failed]"))

    # Headline first: its first step is the process's first compile, so
    # pod-to-first-compile measures the real cold path.
    if "train500m" in sweep:
        def _train500m() -> dict:
            m = bench_train(
                TRAIN_PRESETS["tpu-v5e-1" if on_tpu else "tiny-cpu"],
                verbose=verbose)
            extras.append(first_compile_metric())
            return m

        guarded("train500m", _train500m)
    if "train1b" in sweep:
        guarded("train1b", lambda: bench_train(
            TRAIN_PRESETS["tpu-1b-bf16"], verbose=verbose))
    if "train-zero" in sweep:
        # ZeRO A/B over a data=4 mesh: sharded-optimizer throughput vs
        # the replicated baseline, plus the per-replica optimizer-byte
        # shard ratio (the elastic-training acceptance number, ~= 4).
        def _train_zero() -> dict:
            m = bench_train_zero(verbose=verbose)
            extras.extend(m.pop("extra_metrics", []))
            return m

        guarded("train-zero", _train_zero)
    if "train-goodput" in sweep:
        # Goodput ledger on the bench path: conservation asserted on
        # real clocks; the fraction itself stays informational.
        guarded("train-goodput",
                lambda: bench_train_goodput(verbose=verbose))
    if "flash4k" in sweep:
        guarded("flash4k", lambda: bench_train(
            TRAIN_PRESETS["tpu-flash-4k"], assert_flash=True,
            verbose=verbose))
    if "decode" in sweep:
        if on_tpu:
            # the prefill-subtracted measurement makes 127 decoded
            # tokens a clean steady-state sample
            guarded("decode", lambda: bench_decode(
                "bench-500m-serve", batch=16, prompt_len=128,
                max_new=128, max_len=512, verbose=verbose))
        else:
            # max_len=64 matches the decode-cont section below —
            # attention and cache traffic scale with max_len, so the
            # r04 comparison (static at 32 vs continuous at 64) charged
            # the slot engine for a 2x bigger cache, not its design.
            guarded("decode", lambda: bench_decode(
                "tiny", batch=2, prompt_len=8, max_new=8, max_len=64,
                verbose=verbose))
    if "decode-int8" in sweep:
        # Same decode, int8 block weights: the MBU denominator halves
        # (vs bf16), so tokens/s should rise toward the same roofline.
        if on_tpu:
            guarded("decode-int8", lambda: bench_decode(
                "bench-500m-serve", batch=16, prompt_len=128,
                max_new=128, max_len=512, int8=True, verbose=verbose))
        else:
            guarded("decode-int8", lambda: bench_decode(
                "tiny", batch=2, prompt_len=8, max_new=8, max_len=64,
                int8=True, verbose=verbose))
    if "decode-cont" in sweep:
        # Continuous slot engine at full occupancy, same shapes as
        # `decode`: the delta between the two metrics IS the measured
        # cost of per-slot cursors + chunked stepping.
        if on_tpu:
            guarded("decode-cont", lambda: bench_decode_continuous(
                "bench-500m-serve", slots=16, prompt_len=128, rounds=8,
                chunk=4, max_len=512, verbose=verbose))
        else:
            guarded("decode-cont", lambda: bench_decode_continuous(
                "tiny", slots=2, prompt_len=8, rounds=2, chunk=4,
                max_len=64, verbose=verbose))

        # TTFT under a long-prompt collision — the latency side of
        # the decode-cont story.
        if on_tpu:
            guarded("decode-cont-ttft", lambda: bench_decode_cont_ttft(
                "bench-500m-serve", slots=8, short_len=16,
                long_len=384, budget=64, max_len=512,
                block_size=64, verbose=verbose))
        else:
            guarded("decode-cont-ttft", lambda: bench_decode_cont_ttft(
                "tiny", slots=4, short_len=6, long_len=48,
                budget=8, max_len=64, block_size=8,
                verbose=verbose))
    if "decode-paged" in sweep:
        # Paged KV + radix prefix cache under a repeated-prompt
        # workload. The bench returns its cache-evidence metrics
        # (hit rate, prefilled-vs-reused tokens, KV HBM bytes) as
        # sub-entries; lift them into the artifact's extras alongside
        # the throughput number.
        def _paged() -> dict:
            if on_tpu:
                m = bench_decode_paged(
                    "bench-500m-serve", slots=8, prompt_len=128,
                    max_new=32, requests=24, max_len=512,
                    block_size=64, verbose=verbose)
            else:
                m = bench_decode_paged(
                    "tiny", slots=2, prompt_len=16, max_new=8,
                    requests=6, max_len=64, block_size=8,
                    verbose=verbose)
            extras.extend(m.pop("extra_metrics", []))
            return m

        guarded("decode-paged", _paged)
    if "decode-spill" in sweep:
        # Host-RAM spill tier A/B on an overflowing working set:
        # evict+recompute (tier off) vs spill+restore (tier on), same
        # pool geometry. Headline = tier-on re-request throughput;
        # the off/on p95 pair + speedup ride as extras.
        def _spill() -> dict:
            if on_tpu:
                # 12 prompts x 2 parked full blocks each (159 kv
                # tokens / 64) overflow the 16 usable blocks
                m = bench_decode_spill(
                    "bench-500m-serve", slots=2, prompt_len=128,
                    max_new=32, prompts=12, pool_blocks=17,
                    max_len=512, block_size=64, verbose=verbose)
            else:
                m = bench_decode_spill(
                    "tiny", slots=2, prompt_len=16, max_new=8,
                    prompts=8, pool_blocks=9, max_len=64,
                    block_size=8, verbose=verbose)
            extras.extend(m.pop("extra_metrics", []))
            return m

        guarded("decode-spill", _spill)
    if "decode-spec-paged" in sweep:
        # Speculative decoding on the paged continuous engine, A/B'd
        # in-function against the same batcher with speculation off.
        # Self-draft = the gamma-bound upper limit of the win; the
        # acceptance-rate extra is the knob a real draft scales it by.
        def _spec_paged() -> dict:
            if on_tpu:
                m = bench_decode_spec_paged(
                    "bench-500m-serve", slots=8, prompt_len=128,
                    max_new=32, requests=16, max_len=512,
                    block_size=64, gamma=4, verbose=verbose)
            else:
                m = bench_decode_spec_paged(
                    "tiny", slots=2, prompt_len=8, max_new=8,
                    requests=6, max_len=64, block_size=8, gamma=3,
                    verbose=verbose)
            extras.extend(m.pop("extra_metrics", []))
            return m

        guarded("decode-spec-paged", _spec_paged)
    if "decode-paged-kernel" in sweep:
        # XLA gather vs fused Pallas kernel over the same block pool
        # (ops-level, no engine). The CPU sweep asks for interpret
        # mode itself — tiny shapes keep the interpreter's per-block
        # Python cost bounded; the modeled HBM-byte entries are the
        # numbers that transfer to hardware.
        def _paged_kernel() -> dict:
            if on_tpu:
                m = bench_decode_paged_kernel(
                    b=16, n_q=16, n_kv=2, hd=128, block_size=64,
                    blocks_per_slot=32, iters=32, verbose=verbose)
            else:
                m = bench_decode_paged_kernel(
                    b=4, n_q=8, n_kv=2, hd=64, block_size=16,
                    blocks_per_slot=16, iters=8, interpret=True,
                    verbose=verbose)
            extras.extend(m.pop("extra_metrics", []))
            return m

        guarded("decode-paged-kernel", _paged_kernel)
    if "decode-gemma" in sweep:
        # BASELINE config #5 (Gemma-2B serving): same decode harness,
        # gemma family (GQA 8q/1kv, huge vocab — a different serving
        # shape class than the llama presets).
        if on_tpu:
            guarded("decode-gemma", lambda: bench_decode(
                "gemma-2b", batch=8, prompt_len=128, max_new=128,
                max_len=512, verbose=verbose))
        else:
            guarded("decode-gemma", lambda: bench_decode(
                "gemma-tiny", batch=2, prompt_len=8, max_new=8,
                max_len=64, verbose=verbose))
    if "serving-disagg" in sweep:
        # Disaggregated prefill/decode pools vs an equal-count
        # symmetric fleet, via the loadtest's subprocess A/B (the
        # replicas pin themselves to CPU regardless of backend). The
        # headline + speedup ratio feed the bench gate; parity and
        # zero-client-failure bars are enforced inside the run.
        def _disagg() -> dict:
            m = bench_serving_disagg(verbose=verbose)
            extras.extend(m.pop("extra_metrics", []))
            return m

        guarded("serving-disagg", _disagg)
    if "scenario-replay" in sweep:
        # Scenario-engine record/replay fidelity via the loadtest's
        # paired interleaved A/B (replicas pin themselves to CPU
        # regardless of backend). The headroom ratio feeds the bench
        # gate; the expect block, zero-client-failure, and
        # delta-within-budget bars are enforced inside the run.
        def _scenario() -> dict:
            m = bench_scenario_replay(verbose=verbose)
            extras.extend(m.pop("extra_metrics", []))
            return m

        guarded("scenario-replay", _scenario)
    if "mnist" in sweep:
        # BASELINE config #1 (MNIST-MLP smoke) — same section on every
        # backend; the metric label carries where it ran.
        guarded("mnist", lambda: bench_mnist(verbose=verbose))
    if "vit" in sweep:
        # BASELINE config #2 (ViT-B/16 fine-tune, v5e-1) + CPU twin.
        if on_tpu:
            guarded("vit", lambda: bench_vit(
                "vit-b16", batch=64, steps=10, verbose=verbose))
        else:
            guarded("vit", lambda: bench_vit(
                "tiny", batch=8, steps=5, verbose=verbose))

    if headline is None:
        print(f"# no section produced a result; failed: {failed}",
              file=sys.stderr)
        return 1
    _emit_result(headline, extras, backend)
    if failed:
        print(f"# sections failed: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
