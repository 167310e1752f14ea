"""Kind of cell `train`: `Trainer.step` as `tools/smoke_train.py` calls
it, on a new batch of ids drawn from the seed on the host every step,
each step timed to the loss on the host.

One run: state on the devices from the seed -> the reference's loss on
the first batch and the initial parameters -> the first step (compiles,
or reads the cache; its loss is the one compared) -> a second step
(the steady program has run once) -> the measured window: steps until
`seconds` have passed, the rate taken over all of them to the end of
the last.
"""

from __future__ import annotations

import math
import statistics
import time

import numpy as np

from benchmarks import harness, trafficgen

# steps of the window a traced run profiles
TRACE_STEPS = 3


def fit_depth(layer_params: int, other_params: int, itemsize: int,
              max_depth: int, n_devices: int, device_bytes: int) -> int:
    """`tools/smoke_train.py`'s rule, copied: the largest depth whose
    training state — parameters, gradients and the two Adam moments,
    four copies in the parameter dtype, sharded evenly — fits 0.75 of
    one device's memory. A train configuration's depth is the number
    this gave on the chip, written into its file."""
    for depth in range(max_depth, 0, -1):
        n = depth * layer_params + other_params
        if 4 * n * itemsize / n_devices <= 0.75 * device_bytes:
            return depth
    raise ValueError(f"not one layer's training state fits {device_bytes}")


def run(cell: harness.Cell, model, *, seed: int, seconds: float,
        trace_dir: str | None, t_start: float, tamper=None) -> harness.Run:
    import jax
    import jax.numpy as jnp

    config, mix = cell.config, cell.traffic
    n_devices = len(jax.devices())
    batch = mix["sequences_per_chip"] * n_devices
    seq, vocab = mix["seq_len"], config["vocab_size"]
    rng = np.random.default_rng(trafficgen.seed_words(seed, 0x747261))

    def draw():
        tokens = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
        return tokens, np.roll(tokens, -1, axis=1)

    trainer = model.trainer(config)
    state = trainer.init(model.rng_key(seed))
    jax.block_until_ready(state.params)
    harness.log(t_start, "state on the devices")

    problems: list[str] = []
    tokens, targets = draw()
    ref_params = state.params if tamper is None else tamper(state.params)
    want = model.reference_loss(config, ref_params, tokens, targets)
    del ref_params

    def step(state, tokens, targets):
        t = time.perf_counter()
        state, loss = trainer.step(
            state, jnp.asarray(tokens), jnp.asarray(targets))
        loss = float(loss)                 # device-to-host: the step ran
        return state, loss, time.perf_counter() - t

    state, first_loss, _ = step(state, tokens, targets)
    tol = model.loss_tolerance(config)
    if not abs(first_loss - want) <= tol:
        problems.append(f"first step's loss {first_loss:.5f}, reference "
                        f"{want:.5f}: differ by more than {tol}")
    state, _, _ = step(state, *draw())
    harness.log(t_start, f"first step's loss {first_loss:.5f}, reference "
                         f"{want:.5f}; window opens: {seconds} s")

    profile = harness.Profile(trace_dir)
    losses, step_s = [], []
    trace = None
    with harness.CompileCount() as compiles:
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        profile.start()
        while True:
            state, loss, dt = step(state, *draw())
            losses.append(loss)
            step_s.append(dt)
            if len(losses) == TRACE_STEPS:
                trace = profile.stop()
            if time.perf_counter() - t0 >= seconds \
                    and len(losses) >= TRACE_STEPS:
                break
        elapsed = time.perf_counter() - t0
        compiled = compiles.n

    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    depth_by_rule = None if limit is None else fit_depth(
        model.layer_params(config), model.other_params(config),
        model.param_itemsize(config),
        config["reduced"]["num_hidden_layers"]["source"], n_devices, limit)
    bad = [x for x in losses if not math.isfinite(x)]
    if bad:
        problems.append(f"{len(bad)} non-finite losses")
    if compiled:
        problems.append(f"{compiled} programs compiled inside the window")
    tok_s_chip = len(losses) * batch * seq / elapsed / n_devices
    counters = {
        "steps": len(losses), "step_median_ms": 1e3 * statistics.median(step_s),
        "tokens_per_step_per_chip": batch * seq / n_devices, "seq_len": seq,
        "compiles_in_window": compiled,
    }
    return harness.Run(
        end_to_end={"train_tok_s": tok_s_chip, "setup_s": setup_s},
        counters=counters, attempted=len(losses), failed=len(bad),
        problems=problems, trace=trace,
        extra={"steps": len(losses), "window_s": elapsed,
               "first_loss": first_loss, "reference_loss": want,
               "last_loss": losses[-1], "batch": batch, "seq_len": seq,
               "step_median_ms": counters["step_median_ms"],
               "device_bytes_limit": limit,
               "depth_by_fit_rule": depth_by_rule})
