#!/usr/bin/env python3
"""Kimi-Linear on the chip against its plain reference, finer than a
loss: at the benchmark configuration's widths and 8192 tokens, the
program's per-token target log-probabilities and the residual stream
after every layer, beside the float32 reference's
(`benchmarks/models/kimi_linear_reference.py`).

    chiprun -- python3 tools/compare_kimi_linear.py [--seed N] [--seq 8192]

A loss at random weights sits near ln(vocabulary) whatever the layers
do; these readings do not. For every layer three comparisons, each as
the largest absolute difference, the root-mean-square difference and
the relative L2 difference (|a - b| / |b|):

- `stream`: the program's own residual stream after the layer (errors
  of earlier layers included);
- `alone`: the layer given the reference's input, so that a layer
  kind's own error shows;
- `lower`: the reference itself with KDA's state and gates rounded to
  bfloat16 (`state_dtype`), against the float32 reference: the reading
  a tolerance has to refuse.

Prints one JSON object and writes it to
`chiprun_out/compare_kimi_linear.json`. TPU only, as the benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def differences(a, b) -> dict[str, float]:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    d = a - b
    return {"max_abs": float(jnp.max(jnp.abs(d))),
            "rms": float(jnp.sqrt(jnp.mean(jnp.square(d)))),
            "rel_l2": float(jnp.linalg.norm(d) / jnp.linalg.norm(b))}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="kimi-linear-48b-train")
    ap.add_argument("--seed", type=int, default=28)
    ap.add_argument("--seq", type=int, default=8192)
    args = ap.parse_args(argv)

    from kubeflow_tpu import compile_cache
    compile_cache.enable()
    import jax
    import jax.numpy as jnp

    from benchmarks import harness
    from benchmarks.models import kimi_linear as model
    from benchmarks.models import kimi_linear_reference as ref
    from kubeflow_tpu.models import kimi_linear as kl

    if jax.devices()[0].platform != "tpu":
        print("tools/compare_kimi_linear.py compares on a TPU only",
              file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    c = harness.read_json(os.path.join(
        ROOT, "benchmarks", "configs", args.config + ".json"))
    cfg = model.program_config(c)
    params = jax.jit(lambda k: kl.init(k, cfg))(model.rng_key(args.seed))
    tokens = jax.random.randint(
        jax.random.fold_in(model.rng_key(args.seed), 1), (args.seq,), 0,
        c["vocab_size"], jnp.int32)
    targets = jnp.roll(tokens, -1)

    block = jax.jit(kl._block, static_argnums=(0, 1))

    @jax.jit
    def target_logprobs(x, params):
        x = kl.rms_norm(x, params["final_norm"], cfg.norm_eps)
        lp = jax.nn.log_softmax(
            x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32))
        return jnp.take_along_axis(lp[0], targets[:, None], axis=-1)[:, 0]

    want = ref.layer_outputs(c, params, tokens)
    lower = ref.layer_outputs(c, params, tokens, state_dtype=jnp.bfloat16)
    x = kl.embed_lookup(params["embed"], tokens[None], cfg.dtype)
    layers = []
    for i, number in enumerate(cfg.layer_numbers):
        p = params["layers"][i]
        x, _ = block(cfg, number, x, p)
        alone, _ = block(cfg, number, want[i][None].astype(cfg.dtype), p)
        layers.append({
            "layer": number,
            "kind": ("kda" if cfg.is_kda(number) else "mla") + "+"
                    + ("dense" if cfg.is_dense(number) else "moe"),
            "stream": differences(x[0], want[i + 1]),
            "alone": differences(alone[0], want[i + 1]),
            "lower": differences(lower[i + 1], want[i + 1])})
    lp = target_logprobs(x, params)
    lp_want = ref.token_logprobs(c, params, tokens, targets)
    lp_lower = ref.token_logprobs(c, params, tokens, targets,
                                  state_dtype=jnp.bfloat16)
    out = {
        "device": jax.devices()[0].device_kind, "seed": args.seed,
        "seq": args.seq, "layers": layers,
        "target_logprobs": {
            "program": differences(lp, lp_want),
            "lower": differences(lp_lower, lp_want),
            "loss_program": float(-jnp.mean(lp)),
            "loss_reference": float(-jnp.mean(lp_want)),
            "loss_lower": float(-jnp.mean(lp_lower))},
        "seconds": time.perf_counter() - t0}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "compare_kimi_linear.json"),
              "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
