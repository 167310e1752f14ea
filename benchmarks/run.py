#!/usr/bin/env python3
"""The benchmark's entry point: one run of one cell, in one process.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Prints progress to stderr and, as the last line of stdout, one JSON
object (`correct`, `attempted`, `failed`, `metrics`, `device`, and with
`--trace 1` `breakdown`). Exits non-zero and prints no result unless
JAX attaches a TPU whose `device_kind` is in `peaks.json`, with at
least the chips the cell asks for: there is no CPU fallback.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmarks import harness

    cell = harness.load_cell(ROOT, args.workload)

    # the program's own rule for the compile cache: the directory the
    # environment names, else the fixed `<checkout>/.jax_cache`
    from kubeflow_tpu import compile_cache
    compile_cache.enable()
    import jax

    # where the environment names the directory, `enable()` leaves JAX's
    # one-second floor in place; without it every program is kept, so
    # that a second run finds them all and set-up is steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmarks/run.py measures on a TPU only; JAX attached "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    peaks = harness.peaks_for(devices[0].device_kind)
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX attached "
              f"{len(devices)}", file=sys.stderr)
        return 1

    line = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), peaks=peaks,
                            t_start=T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
