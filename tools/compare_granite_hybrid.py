#!/usr/bin/env python3
"""granite-4.0-h-micro at the benchmark's widths: the float32
reference beside itself with the recurrence (the state, the decay, the
outer product and the read-out) computed in a lower precision, on one
seeded prompt. The two readings PERF.md sets the cell's
`logprob_tolerance` between are the served path's (the benchmark's own
reference check) and these.

    chiprun -- python3 tools/compare_granite_hybrid.py [--seed N]

Prints one JSON line: the largest difference of a token's
log-probability from the float32 reference's, by `state_dtype`, and
for scale with one multiplier wrong.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tokens", type=int, default=716)
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmarks/configs/granite-4.0-h-micro-serve.json"))
    args = ap.parse_args()
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness, trafficgen
    from benchmarks.models import granite_hybrid as model

    config = harness.read_json(args.config)
    _, params = model.serving_engine(config, args.seed)
    rng = np.random.default_rng(trafficgen.seed_words(args.seed, 0x726566))
    ids = rng.integers(0, config["vocab_size"], args.tokens + 1).tolist()

    def logprobs(dtype):
        return np.asarray(model.reference_token_logprobs(
            config, params, ids[:-1], ids[1:], state_dtype=dtype))

    exact = logprobs(jnp.float32)
    out = {"device": jax.devices()[0].device_kind, "seed": args.seed,
           "tokens": args.tokens}
    for name in ("float16", "bfloat16", "float8_e4m3fn"):
        out[name] = float(np.max(np.abs(logprobs(jnp.dtype(name)) - exact)))
    # two faults of the mathematics, for scale: what the comparison
    # reads when a multiplier is wrong in the model under test
    faults = {"attention_multiplier": config["head_dim"] ** -0.5,
              "residual_multiplier": 1.0}
    for key, value in faults.items():
        wrong = np.asarray(model.reference_token_logprobs(
            dict(config, **{key: value}), params, ids[:-1], ids[1:]))
        out[f"{key}={value:g}"] = float(np.max(np.abs(wrong - exact)))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
