#!/usr/bin/env python
"""Remat-policy x batch sweep for the bench-500m preset on real TPU.

Full per-block remat costs ~+33% backward matmul FLOPs; chunked CE
freed the logit tensor's HBM, which may buy a cheaper policy
(models/llama.py remat_policy: "full" | "mlp" | "dots") or a bigger
batch. This sweep measures the actual tok/s winner so the bench preset
default can be chosen from data, not theory.

Run on a TPU host: `python tools/remat_sweep.py [variant,variant,...]`
Variants: b8-full (current default), b8-mlp, b4-dots, b8-dots,
b16-full, b16-mlp. Prints one line per variant and a summary dict.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402
from bench import Preset  # noqa: E402

VARIANTS = [
    ("b8-full", 8, "full"),
    ("b8-mlp", 8, "mlp"),
    ("b4-dots", 4, "dots"),
    ("b8-dots", 8, "dots"),
    ("b16-full", 16, "full"),
    ("b16-mlp", 16, "mlp"),
]

# --allow-cpu grid: the SAME harness end-to-end (variant loop, failure
# capture, RESULTS/BEST table) on shapes a CPU can finish — keeps the
# sweep's plumbing and output format checked without a chip.
CPU_VARIANTS = [
    ("b2-full", 2, "full"),
    ("b2-mlp", 2, "mlp"),
    ("b2-dots", 2, "dots"),
]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("variants", nargs="?", default="",
                   help="comma-separated subset of the variant grid")
    p.add_argument("--allow-cpu", action="store_true",
                   help="run the tiny-model CPU grid (harness "
                        "validation, not a perf measurement)")
    args = p.parse_args()

    # The sweep is only meaningful on TPU: refuse any other backend
    # unless --allow-cpu asks for the harness check.
    import jax

    from kubeflow_tpu import compile_cache

    compile_cache.enable()
    backend = jax.default_backend()
    if backend != "tpu" and not args.allow_cpu:
        print(f"remat_sweep needs a TPU backend (attached: {backend}); "
              "not running — see docs/perf-notes.md for the expected "
              "outcome model (pass --allow-cpu for a harness check)",
              file=sys.stderr)
        return 3

    on_tpu = backend == "tpu"
    model = "bench-500m" if on_tpu else "tiny"
    base = bench.bench_configs()[model]
    variants = VARIANTS if on_tpu else CPU_VARIANTS
    seq, steps, warmup = (2048, 10, 2) if on_tpu else (128, 3, 1)
    if args.variants:
        wanted = args.variants.split(",")
        known = {v[0] for v in variants}
        unknown = [w for w in wanted if w not in known]
        if unknown:
            print(f"unknown variants {unknown}; known: {sorted(known)}",
                  file=sys.stderr)
            return 2
        variants = [v for v in variants if v[0] in wanted]
    results = {}
    for name, batch, policy in variants:
        cfg = dataclasses.replace(base, remat_policy=policy)
        preset = Preset(name, batch=batch, seq=seq, steps=steps,
                        warmup=warmup, model=model)
        try:
            m = bench.bench_train(preset, config=cfg)
            results[name] = m["value"]
            print(f"{name}: {m['value']} tok/s/chip "
                  f"(mfu*2.5={m['vs_baseline']})", flush=True)
        except Exception as e:  # noqa: BLE001 — OOM variants report, not die
            print(f"{name}: FAILED {type(e).__name__}: {str(e)[:200]}",
                  flush=True)
    print("RESULTS:", results)
    if not results:
        print("no variant produced a result", file=sys.stderr)
        return 1
    best = max(results, key=results.get)
    print(f"BEST: {best} ({results[best]} tok/s/chip)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
