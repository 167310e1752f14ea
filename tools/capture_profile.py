#!/usr/bin/env python
"""Capture an XLA profiler trace of N train steps (TensorBoard-ready).

The reference has no tracing at all (SURVEY.md §5); this is the TPU
replacement: `jax.profiler` traces written where TensorBoard's profile
plugin (and `xprof`) can read them — the tool the perf-notes roofline
arguments should be checked against on hardware.

    python tools/capture_profile.py --preset tpu-v5e-1 --steps 3 \
        --logdir /tmp/kftpu-profile

Reuses bench.py's presets. It traces the backend JAX attached and
refuses anything but a TPU unless --allow-cpu asks for a plumbing check
(run it with JAX_PLATFORMS=cpu).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="tpu-v5e-1",
                   choices=sorted(bench.TRAIN_PRESETS))
    p.add_argument("--steps", type=int, default=3,
                   help="traced steps (after untraced warmup/compile)")
    p.add_argument("--logdir", default="/tmp/kftpu-profile")
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args()

    import jax

    from kubeflow_tpu import compile_cache

    compile_cache.enable()
    backend = jax.default_backend()
    if backend != "tpu" and not args.allow_cpu:
        print(f"need a TPU backend (attached: {backend}); pass "
              "--allow-cpu for a plumbing check", file=sys.stderr)
        return 3

    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.train import Trainer, TrainConfig
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.parallel import MeshSpec, create_mesh
    from kubeflow_tpu.utils import profiling

    from kubeflow_tpu.train.trainer import (
        chunked_cross_entropy_from_hidden,
    )

    preset = bench.TRAIN_PRESETS[args.preset]
    cfg = bench.bench_configs()[preset.model]
    n = len(jax.devices())
    mesh = create_mesh(MeshSpec(data=1, fsdp=n, tensor=1))

    def chunked_loss(params, tokens, targets, mask):
        # same loss bench.bench_train times, so the trace matches the
        # measured program
        h = llama.hidden(params, cfg, tokens)
        return chunked_cross_entropy_from_hidden(
            h, llama.unembed_matrix(params, cfg), targets, mask,
            num_chunks=16)

    trainer = Trainer(
        mesh=mesh,
        apply_fn=lambda p_, t: llama.apply(p_, cfg, t),
        init_fn=lambda k: llama.init(k, cfg),
        logical_axes=llama.param_logical_axes(cfg),
        train_config=TrainConfig(warmup_steps=2, total_steps=100),
        loss_fn=chunked_loss,
    )
    state = trainer.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    (preset.batch, preset.seq)), jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)
    # compile + warm OUTSIDE the trace: the trace should show steady
    # steps, not one giant XLA compile block
    state, loss = trainer.step(state, toks, tgts)
    jax.block_until_ready(loss)
    with profiling.trace(args.logdir):
        for _ in range(args.steps):
            state, loss = trainer.step(state, toks, tgts)
        jax.block_until_ready(loss)
    print(f"trace written: {args.logdir} (backend={backend}, "
          f"preset={args.preset}, steps={args.steps}); open with "
          "TensorBoard's profile plugin")
    return 0


if __name__ == "__main__":
    sys.exit(main())
