#!/usr/bin/env python
"""chip_smoke.py's train phase: docs/user-guide.md's training script at
the `llama3-1b` widths, a few steps on one repeated batch.

    mesh_from_env() -> Trainer(bf16 params, chunked-CE loss) -> step x5

It runs on whatever devices JAX attached and says which; `chip_smoke.py`
starts it with JAX_PLATFORMS=tpu and rejects any other platform. Over
more than one device `mesh_from_env()` defaults to pure FSDP, and the
run checks that parameters really are spread over every device.

Widths are never cut. Depth is cut only where the training state of 16
layers does not fit the devices' memory, by the rule in `fit_depth`,
and the depth used is printed.

Prints progress lines, then one JSON object as its last line.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubeflow_tpu import compile_cache  # noqa: E402

SEQ = 2048
STEPS = 5
BATCH_PER_DEVICE = 2
# params, gradients and the two Adam moments, all in the param dtype
STATE_COPIES = 4
# the share of device memory the training state may take; the rest is
# activations, the optimizer's fp32 temporaries and XLA's own scratch
STATE_SHARE = 0.75


def fit_depth(cfg, n_devices: int, device_bytes: int | None) -> int:
    """The largest depth <= cfg.num_layers whose training state, sharded
    evenly over the devices (pure FSDP), fits STATE_SHARE of one
    device's memory. Full depth where the device does not report its
    memory."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import llama

    if device_bytes is None:
        return cfg.num_layers
    itemsize = jnp.dtype(cfg.param_dtype).itemsize
    for depth in range(cfg.num_layers, 0, -1):
        n = llama.num_params(dataclasses.replace(cfg, num_layers=depth))
        if STATE_COPIES * n * itemsize / n_devices \
                <= STATE_SHARE * device_bytes:
            return depth
    raise RuntimeError(
        f"not even one layer of training state fits {device_bytes} bytes")


def check_spread(params, n_devices: int) -> dict:
    """Parameters must live on every device, about 1/n each — not all on
    device 0. Returns the per-device byte counts."""
    import jax

    per_device: dict[int, int] = {}
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes)
    if len(per_device) != n_devices:
        raise AssertionError(
            f"parameters live on {len(per_device)} of {n_devices} "
            f"devices: {per_device}")
    embed = params["embed"]
    if len({s.device.id for s in embed.addressable_shards}) != n_devices \
            or embed.addressable_shards[0].data.nbytes * n_devices \
            != embed.nbytes:
        raise AssertionError(
            f"embed [{embed.shape}] is not split {n_devices} ways: "
            f"{embed.sharding}")
    worst = max(per_device.values())
    if worst > 1.1 * total / n_devices:
        raise AssertionError(
            f"a device holds {worst} of {total} parameter bytes; "
            f"expected about 1/{n_devices}")
    return {"total_bytes": total, "per_device_bytes": per_device}


def run(cfg, *, seq: int, steps: int, batch_per_device: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models import llama
    from kubeflow_tpu.ops import attention
    from kubeflow_tpu.parallel import mesh_from_env
    from kubeflow_tpu.train import TrainConfig, Trainer
    from kubeflow_tpu.train.trainer import chunked_cross_entropy_from_hidden
    from kubeflow_tpu.utils import device_stamp

    devices = jax.devices()
    n = len(devices)
    device = device_stamp()
    print(f"train: jax={jax.__version__} device={device}", flush=True)
    stats = devices[0].memory_stats() or {}
    depth = fit_depth(cfg, n, stats.get("bytes_limit"))
    full_depth = cfg.num_layers
    print(f"train: depth={depth} of {full_depth} "
          f"(device bytes_limit={stats.get('bytes_limit', 'not reported')})",
          flush=True)
    cfg = dataclasses.replace(cfg, num_layers=depth)

    mesh = mesh_from_env()
    print(f"train: mesh={dict(mesh.shape)}", flush=True)

    def chunked_loss(params, tokens, targets, mask):
        # the loss bench.py's bench_train uses: never materializes the
        # [b, s, vocab] fp32 logits
        h = llama.hidden(params, cfg, tokens)
        return chunked_cross_entropy_from_hidden(
            h, llama.unembed_matrix(params, cfg), targets, mask,
            num_chunks=16)

    trainer = Trainer(
        mesh=mesh,
        apply_fn=lambda p, t: llama.apply(p, cfg, t),
        init_fn=lambda k: llama.init(k, cfg),
        logical_axes=llama.param_logical_axes(cfg),
        train_config=TrainConfig(warmup_steps=2, total_steps=100),
        loss_fn=chunked_loss,
    )
    t0 = time.perf_counter()
    state = trainer.init(jax.random.key(0))
    jax.block_until_ready(state.params)
    init_s = time.perf_counter() - t0
    spread = check_spread(state.params, n)
    print(f"train: init {init_s:.1f}s, params "
          f"{spread['total_bytes'] / 2**30:.2f} GiB, per device "
          f"{sorted(spread['per_device_bytes'].values())}", flush=True)

    batch = batch_per_device * n
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)),
        jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)

    attention.reset_impl_counts()
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, loss = trainer.step(state, tokens, targets)
        losses.append(float(loss))       # device-to-host: the step ran
        step_s.append(round(time.perf_counter() - t0, 3))
        print(f"train: step {len(losses)} loss={losses[-1]:.4f} "
              f"{step_s[-1]:.2f}s", flush=True)
    counts = attention.impl_counts()
    peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")

    problems = []
    if not all(np.isfinite(losses)):
        problems.append(f"non-finite loss: {losses}")
    elif not losses[-1] < losses[0]:
        problems.append(f"loss did not fall: {losses}")
    return {
        "ok": not problems, "problems": problems, "phase": "train",
        "jax": jax.__version__, "device": device,
        "depth": depth, "full_depth": full_depth,
        "mesh": dict(mesh.shape), "batch": batch, "seq": seq,
        "losses": [round(x, 4) for x in losses],
        # step 1 includes trace + compile (or the cache read)
        "step_seconds": step_s, "init_seconds": round(init_s, 2),
        "impl_counts": counts,
        "param_bytes": spread["total_bytes"],
        "param_bytes_per_device": sorted(
            spread["per_device_bytes"].values()),
        "peak_bytes_in_use": peak,
    }


def main() -> int:
    compile_cache.enable()
    import jax.numpy as jnp

    from kubeflow_tpu.models import llama

    # the config __graft_entry__.entry() builds: llama3-1b, bf16 params
    cfg = dataclasses.replace(llama.LLAMA3_1B, param_dtype=jnp.bfloat16)
    result = run(cfg, seq=SEQ, steps=STEPS,
                 batch_per_device=BATCH_PER_DEVICE)
    if result["impl_counts"]["flash"] == 0:
        # seq 2048 must go through the Pallas flash kernel, forward and
        # backward (ops.attention's auto rule on TPU)
        result["problems"].append(
            f"flash kernel not traced: {result['impl_counts']}")
        result["ok"] = False
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
