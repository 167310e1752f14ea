"""Sharded training: FSDP/TP train step over a (data, fsdp, tensor) mesh.

The TPU-idiomatic training recipe (scaling-book style):
  1. pick a Mesh (kubeflow_tpu.parallel.mesh),
  2. resolve logical param axes → NamedShardings (parallel.sharding),
  3. jit the step with in/out shardings; XLA inserts the all-gathers /
     reduce-scatters over ICI.
No hand-written collectives in the DP/FSDP/TP path — that is XLA's job.
Ring attention / EP (explicit collectives via shard_map) live in
kubeflow_tpu.parallel and compose with this trainer.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
import weakref
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu import obs
from kubeflow_tpu.controlplane.metrics import Counter, Gauge
from kubeflow_tpu.parallel import moe as moe_lib
from kubeflow_tpu.parallel import sharding as sharding_lib
from kubeflow_tpu.parallel.sharding import ShardingRules

Params = Any


def estimate_step_flops(n_params: int, tokens: int) -> float:
    """Model FLOPs for one train step: the standard 6·N·T estimate
    (2·N·T forward + 4·N·T backward) over all processed tokens. This is
    MODEL flops — the numerator of MFU — not hardware flops: attention
    quadratic terms and rematerialization are deliberately excluded, so
    MFU stays comparable across implementations (the scaling-book
    convention the paper's goodput accounting uses)."""
    return 6.0 * float(n_params) * float(tokens)


def _masked_mean(
    nll: jnp.ndarray,                 # [b, s] per-position losses
    mask: jnp.ndarray | None,         # [b, s] float/bool, 0 = ignore
) -> jnp.ndarray:
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@jax.named_scope("loss")
def cross_entropy_loss(
    logits: jnp.ndarray,   # [b, s, vocab] fp32
    targets: jnp.ndarray,  # [b, s] int32
    mask: jnp.ndarray | None = None,  # [b, s] float/bool, 0 = ignore
) -> jnp.ndarray:
    """Mean next-token cross entropy over valid positions."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return _masked_mean(logz - gold, mask)


@jax.named_scope("loss")
def chunked_cross_entropy_from_hidden(
    hidden: jnp.ndarray,    # [b, s, D] final (normed) hidden states
    head: jnp.ndarray,      # [D, vocab] unembedding matrix
    targets: jnp.ndarray,   # [b, s] int32
    mask: jnp.ndarray | None = None,
    *,
    num_chunks: int = 8,
) -> jnp.ndarray:
    """CE without materializing the full [b, s, vocab] fp32 logits.

    The logit tensor is the single largest activation of a big-vocab
    training step (batch 8 x seq 2048 x 32k vocab = 2 GB fp32, doubled
    by its cotangent). Flash-attention's trick applies to the softmax
    over vocab too: scan over vocab CHUNKS, keep the online
    (max, sumexp, gold-logit) running stats, and `jax.checkpoint` the
    chunk body so the backward pass recomputes each chunk's logits
    instead of storing them. Peak logit memory drops num_chunks-fold;
    HBM traffic for the step's biggest tensor drops with it.

    Numerics match `cross_entropy_loss(hidden @ head, ...)` to fp32
    rounding (same online-softmax algebra as ops/pallas/flash_attention).
    """
    b, s, d = hidden.shape
    vocab = head.shape[1]
    # Largest divisor of vocab <= requested: never silently degrade to
    # one full-vocab chunk (that would materialize exactly the logits
    # this function exists to avoid).
    requested = num_chunks
    num_chunks = max(1, min(num_chunks, vocab))
    while vocab % num_chunks:
        num_chunks -= 1
    if num_chunks == 1 and requested > 1 and vocab > 4096:
        logging.getLogger(__name__).warning(
            "chunked CE running UNCHUNKED: vocab %d shares no divisor "
            "<= the requested chunk count %d — full [b, s, vocab] "
            "logits will materialize", vocab, requested)
    chunk = vocab // num_chunks
    hidden = hidden.astype(jnp.float32)
    offsets = (jnp.arange(num_chunks, dtype=jnp.int32) * chunk)

    @jax.checkpoint
    def body(carry, off):
        m, acc, gold = carry
        # Slice the head in its NATIVE dtype and cast per chunk: an
        # fp32 copy of the whole [D, vocab] head as a scan operand
        # would itself cost ~half the memory the chunking saves.
        head_c = jax.lax.dynamic_slice(head, (0, off), (d, chunk))
        logits_c = hidden @ head_c.astype(jnp.float32)  # [b, s, chunk]
        m_c = jnp.max(logits_c, axis=-1)
        new_m = jnp.maximum(m, m_c)
        acc = (acc * jnp.exp(m - new_m)
               + jnp.sum(jnp.exp(logits_c - new_m[..., None]), axis=-1))
        local = targets - off
        in_chunk = (local >= 0) & (local < chunk)
        picked = jnp.take_along_axis(
            logits_c, jnp.clip(local, 0, chunk - 1)[..., None], axis=-1
        )[..., 0]
        gold = gold + jnp.where(in_chunk, picked, 0.0)
        return (new_m, acc, gold), None

    init = (
        jnp.full((b, s), -jnp.inf, jnp.float32),
        jnp.zeros((b, s), jnp.float32),
        jnp.zeros((b, s), jnp.float32),
    )
    (m, acc, gold), _ = jax.lax.scan(body, init, offsets)
    return _masked_mean((m + jnp.log(acc)) - gold, mask)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    # Gradient accumulation: split each step's batch into this many
    # microbatches and average their grads (mask-weighted, fp32
    # accumulator) before ONE optimizer update — the peak-activation
    # memory of a batch/grad_accum step at the optimizer behavior of
    # the full batch. 1 = off.
    grad_accum: int = 1
    # adamw (2x-params moments) or adafactor (factored second moment —
    # the classic TPU memory saver: 8B-model Adam state is 64 GB fp32,
    # Adafactor's is ~params/row+col factors).
    optimizer: str = "adamw"
    # ZeRO-style optimizer partitioning: moments that mirror a param
    # additionally shard over the data axis (parallel.sharding.
    # zero_extend_sharding), so each data-parallel replica holds ~1/N
    # of the optimizer state and XLA lowers the update to
    # reduce-scatter(grads) + sharded update + all-gather(params)
    # instead of N redundant full updates. Exact no-op on data=1
    # meshes. Off reproduces plain mirrored (replicated-over-data)
    # moments — the bench A/B baseline.
    zero_optimizer: bool = True


class TrainState:
    """Minimal pytree train state (params, opt_state, step)."""

    def __init__(self, params, opt_state, step):
        self.params = params
        self.opt_state = opt_state
        self.step = step

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


def make_optimizer(
    tc: TrainConfig,
    freeze_labels: Params | None = None,
) -> optax.GradientTransformation:
    """AdamW with warmup-cosine. `freeze_labels` (a params-shaped tree
    of "train"/"freeze") carves the tree into a trained group and a
    frozen one whose updates are zero AND whose optimizer state is
    empty — for LoRA that empty state is the point: adapter moments
    are ~1000x smaller than full-model moments."""
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=tc.learning_rate,
        warmup_steps=tc.warmup_steps,
        decay_steps=max(tc.total_steps, tc.warmup_steps + 1),
        end_value=tc.learning_rate * 0.1,
    )
    if tc.optimizer == "adamw":
        inner = optax.adamw(schedule, b1=tc.b1, b2=tc.b2,
                            weight_decay=tc.weight_decay)
    elif tc.optimizer == "adafactor":
        # factored second moment: the non-mirroring factor leaves fall
        # through _opt_state_shardings' path+shape match and replicate,
        # which is exactly right — they are O(rows+cols), not O(params)
        inner = optax.adafactor(
            learning_rate=schedule, weight_decay_rate=tc.weight_decay
            or None)
    else:
        raise ValueError(f"unknown optimizer {tc.optimizer!r} "
                         "(adamw | adafactor)")
    opt = optax.chain(
        optax.clip_by_global_norm(tc.grad_clip),
        inner,
    )
    if freeze_labels is None:
        return opt
    return optax.multi_transform(
        {"train": opt, "freeze": optax.set_to_zero()}, freeze_labels)


class Trainer:
    """Builds sharded init/step functions for a model on a mesh.

    `apply_fn(params, tokens) -> logits`; `init_fn(rng) -> params`;
    `logical_axes`: pytree of logical axis tuples matching params.
    """

    @obs.startup_span("startup.trainer")
    def __init__(
        self,
        *,
        mesh: Mesh,
        apply_fn: Callable[..., jnp.ndarray],
        init_fn: Callable[[jax.Array], Params],
        logical_axes: Params,
        rules: ShardingRules = sharding_lib.LLAMA_RULES,
        train_config: TrainConfig = TrainConfig(),
        loss_fn: Callable[..., jnp.ndarray] | None = None,
        freeze_labels: Params | None = None,
        tracer=None,
        registry=None,
    ):
        """`loss_fn(params, tokens, targets, mask) -> scalar` overrides
        the default apply_fn→cross-entropy pipeline — e.g.
        `chunked_cross_entropy_from_hidden` over `llama.hidden`, which
        skips materializing the [b, s, vocab] logits entirely. It may
        return `(scalar, aux)` instead: `aux` is a dict of arrays of
        counts (summed over the microbatches of `grad_accum`) that
        leaves the jitted step beside the loss and stays on the device
        as `last_aux`; `step()` still returns `(state, loss)`. The
        first step whose `aux` carries `moe_load` ([layers, held
        experts], `parallel.moe.routed_experts`' loads) puts the gauges
        `moe_held_assignments` and `moe_max_over_mean_load` into the
        registry, set when it is rendered and not before: no step
        waits for them.
        `freeze_labels` (params-shaped "train"/"freeze" tree) freezes a
        subtree with no optimizer state (see make_optimizer)."""
        self.mesh = mesh
        self.apply_fn = apply_fn
        self.init_fn = init_fn
        self.rules = rules
        self.tc = train_config
        self.loss_fn = loss_fn
        self.optimizer = make_optimizer(train_config, freeze_labels)

        self.param_shardings = sharding_lib.shard_pytree_specs(
            rules, logical_axes, mesh
        )
        # Optimizer state shards like the params it mirrors; scalars replicate.
        params_shapes = jax.eval_shape(init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32))
        opt_shapes = jax.eval_shape(self.optimizer.init, params_shapes)
        self.opt_shardings = _opt_state_shardings(
            opt_shapes, params_shapes, self.param_shardings, mesh
        )
        if train_config.zero_optimizer:
            self.opt_shardings = jax.tree_util.tree_map(
                lambda leaf, sh: sharding_lib.zero_extend_sharding(
                    sh, getattr(leaf, "shape", ())),
                opt_shapes, self.opt_shardings)
        self.state_shardings = TrainState(
            self.param_shardings, self.opt_shardings, NamedSharding(mesh, P())
        )
        # Abstract state tree (ShapeDtypeStructs), the public handle for
        # checkpoint restore targets — keeps callers off _init.
        self.state_shapes = TrainState(
            params_shapes, opt_shapes,
            jax.ShapeDtypeStruct((), jnp.int32),
        )
        # Batch splits over every data-parallel axis the mesh actually
        # has: the hybrid multi-slice mesh adds an outer "dcn" axis
        # (cross-slice pure DP — one grad all-reduce over DCN per step).
        batch_axes = tuple(
            a for a in ("dcn", "data", "fsdp") if a in mesh.axis_names
        )
        self.batch_sharding = NamedSharding(mesh, P(batch_axes, None))

        self._jit_init = jax.jit(self._init, out_shardings=self.state_shardings)
        # Warm-start builder (init_from_params): cached so sweeps that
        # fine-tune from many checkpoints compile it once.
        self._jit_build_state = jax.jit(
            self._build_state,
            in_shardings=(self.param_shardings,),
            out_shardings=self.state_shardings,
        )
        self._jit_step = jax.jit(
            self._step,
            in_shardings=(self.state_shardings, self.batch_sharding,
                          self.batch_sharding, self.batch_sharding),
            out_shardings=(self.state_shardings, NamedSharding(mesh, P()),
                           NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )
        # Obs bridge (spans + /metrics histograms). The Trainer has no
        # natural registry owner, so the process defaults apply unless a
        # caller injects shared ones; get_or_create keeps many Trainers
        # in one process (sweeps, tests) on the same series.
        self.tracer = tracer if tracer is not None else obs.DEFAULT_TRACER
        reg = registry if registry is not None else obs.default_registry()
        self.step_seconds = obs.get_or_create_histogram(
            reg, "train_step_seconds",
            "train step wall time: dispatch only once compiled (jit is "
            "async — use StepTimer(ready=...) for device step time); the "
            "first call blocks on trace+compile")
        self.compile_seconds = obs.get_or_create_histogram(
            reg, "train_compile_seconds",
            "first-step trace+compile+execute wall time (the north-star "
            "pod-to-first-compile component this process controls)",
            buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                     120.0, 300.0, 600.0))
        self._stepped = False
        # Step-anatomy plane (ISSUE 8): the SAME PhaseProfiler the
        # serving batcher uses, with the training anatomy — `step`
        # (the jit call) and `host_gap` (wall between consecutive
        # steps: input pipeline, checkpointing, logging). Goodput for
        # a trainer is step-time over (step + host_gap).
        # In the JAX profiler's trace the phase is the span `train.step`
        # (the dispatch); the host gap is the gap between two of them.
        self.profiler = obs.PhaseProfiler(
            phases=obs.TRAIN_PHASES,
            annotate=jax.profiler.TraceAnnotation)
        self.phase_seconds = obs.get_or_create_histogram(
            reg, "train_step_phase_seconds",
            "Wall time per training phase: step (jit dispatch; the "
            "first call blocks through compile) and host_gap (time "
            "between consecutive steps)")
        for _p in obs.TRAIN_PHASES:
            self.phase_seconds.seed(phase=_p)

        def _on_phase(phase, seconds, tokens):
            if seconds is not None:
                self.phase_seconds.observe(seconds, phase=phase)

        self.profiler.on_phase = _on_phase
        # Compile-watch over the jitted step: a batch/seq shape change
        # mid-run is a retrace the owner should know about (it stalls
        # every replica for the compile) — counted per fn from the
        # step's own dispatch cache, with a `recompile` span naming
        # the program and the seconds of its stages. The step is
        # called as it is: the watch wraps nothing.
        self.recompiles = reg.get("train_recompiles_total")
        if self.recompiles is None:
            self.recompiles = Counter(
                "train_recompiles_total",
                "Entries of the jitted train step's dispatch cache "
                "past its first (a new batch shape: a retrace)", reg)
        self._compile_watch = obs.CompileWatch(
            tracer=self.tracer,
            on_recompile=lambda fn, program: self.recompiles.inc(fn=fn))
        self._compile_watch.watch(self._jit_step, "train_step")
        self.recompiles.inc(0, fn="train_step")
        _scrape_refreshes(reg, self._compile_watch)
        obs.bind_startup_gauge(reg, "train_startup_seconds")
        self._last_step_end: float | None = None
        # what the last step's loss_fn counted, still on the device
        self.last_aux: dict[str, jax.Array] = {}
        self._registry = reg
        self._moe_load: list | None = None     # see _export_moe_load

    def _export_moe_load(self, load: jax.Array) -> None:
        """A step's expert loads -> the gauges of `moe_lib.LOAD_GAUGES`,
        created by the first step that brings any and set from the
        newest whenever the registry is rendered. A registry outlives
        its trainers (the process default does), so the collector holds
        the loads alone, not the trainer: after the trainer has gone it
        reads its last step's once more and leaves the registry."""
        if self._moe_load is None:
            reg = self._registry
            gauges = {name: reg.get(name) or Gauge(name, help_, reg)
                      for name, help_ in moe_lib.LOAD_GAUGES.items()}
            newest = self._moe_load = [load]
            alive = weakref.ref(self)

            def collect():
                for name, value in moe_lib.load_stats(newest[0]).items():
                    gauges[name].set(value)
                return alive() is not None

            reg.register_collector(collect)
        self._moe_load[0] = load

    def _build_state(self, params: Params) -> TrainState:
        return TrainState(params, self.optimizer.init(params),
                          jnp.zeros((), jnp.int32))

    def _init(self, rng: jax.Array) -> TrainState:
        return self._build_state(self.init_fn(rng))

    def _step(self, state: TrainState, tokens, targets, mask):
        def loss_fn(params, toks, tgts, m):
            """-> (loss, aux); `aux` is empty unless `self.loss_fn`
            returns one."""
            if self.loss_fn is not None:
                out = self.loss_fn(params, toks, tgts, m)
                return out if isinstance(out, tuple) else (out, {})
            logits = self.apply_fn(params, toks)
            return cross_entropy_loss(logits, tgts, m), {}

        acc = self.tc.grad_accum
        if acc <= 1:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, tokens, targets, mask)
        else:
            # lax.scan over microbatches: ONE compiled micro-step,
            # peak activations 1/acc of the full batch. Each micro
            # loss is a masked MEAN, so grads/losses are re-weighted
            # by the micro's mask mass — mathematically identical to
            # the full-batch step (summation order aside), which the
            # parity test pins to tight tolerance.
            b = tokens.shape[0]
            mb = b // acc
            split = lambda a: a.reshape(acc, mb, *a.shape[1:])  # noqa: E731
            xs = (split(tokens), split(targets), split(mask))
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)

            def micro(carry, x):
                gsum, lsum, wsum = carry
                toks, tgts, m = x
                (l_, aux_), g_ = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, toks, tgts, m)
                w = jnp.sum(m.astype(jnp.float32))
                gsum = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) * w, gsum, g_)
                return (gsum, lsum + l_.astype(jnp.float32) * w,
                        wsum + w), aux_

            (gsum, lsum, wsum), aux = jax.lax.scan(
                micro, (g0, jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.float32)), xs)
            aux = jax.tree.map(lambda a: jnp.sum(a, axis=0), aux)
            denom = jnp.maximum(wsum, 1.0)
            grads = jax.tree.map(
                lambda g, p: (g / denom).astype(p.dtype), gsum,
                state.params)
            loss = lsum / denom
        with jax.named_scope("optimizer"):
            updates, opt_state = self.optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss, aux

    @obs.startup_span("startup.trainer")
    def init(self, rng: jax.Array) -> TrainState:
        with jax.set_mesh(self.mesh):
            return self._jit_init(rng)

    @obs.startup_span("startup.trainer")
    def init_from_params(self, params: Params) -> TrainState:
        """Warm-start: fresh optimizer state around EXISTING params
        (fine-tuning from a checkpoint). Params are a jit argument, not
        a closure constant — closing over an 8B tree would bake it into
        the executable."""
        with jax.set_mesh(self.mesh):
            return self._jit_build_state(params)

    @property
    def param_count(self) -> int:
        """Total trainable parameter count, from the abstract state
        tree (no device math) — the N in the 6·N·T step-FLOPs
        estimate."""
        total = 0
        for leaf in jax.tree.leaves(self.state_shapes.params):
            shape = getattr(leaf, "shape", None)
            if shape is None:
                continue
            n = 1
            for d in shape:
                n *= d
            total += n
        return total

    def step_flops(self, batch: int, seq: int) -> float:
        """Model FLOPs one `step()` call spends on a [batch, seq]
        token block (6·N·T) — what the elastic worker feeds the
        GoodputLedger for MFU/tokens-per-second accounting."""
        return estimate_step_flops(self.param_count, batch * seq)

    def opt_state_bytes(self, *, per_replica: bool = True) -> int:
        """Optimizer-state footprint in bytes: global, or what a single
        device actually holds (`per_replica`) — the number ZeRO drives
        down ~data-axis-fold while the global total stays fixed."""
        total = 0
        shapes = jax.tree.leaves(self.state_shapes.opt_state)
        shardings = jax.tree.leaves(
            self.opt_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding))
        for leaf, sh in zip(shapes, shardings):
            shape = getattr(leaf, "shape", None)
            if shape is None:
                continue
            nbytes = leaf.dtype.itemsize
            for d in shape:
                nbytes *= d
            if per_replica:
                ways = 1
                for axis in sharding_lib._spec_axes(sh.spec):
                    ways *= self.mesh.shape.get(axis, 1)
                nbytes = -(-nbytes // max(ways, 1))  # ceil per-shard
            total += nbytes
        return total

    def _dispatch(self, state, tokens, targets, mask, compiling):
        with self.tracer.span("train.step", batch=int(tokens.shape[0]),
                              compile=compiling):
            with jax.set_mesh(self.mesh):
                with self.profiler.phase(
                        "step", tokens=int(tokens.shape[0])
                        * int(tokens.shape[1])):
                    state, loss, self.last_aux = self._jit_step(
                        state, tokens, targets, mask)
        return state, loss

    def step(self, state: TrainState, tokens, targets, mask=None):
        if mask is None:
            mask = jnp.ones_like(tokens, dtype=jnp.float32)
        if self.tc.grad_accum > 1 \
                and tokens.shape[0] % self.tc.grad_accum:
            raise ValueError(
                f"batch {tokens.shape[0]} not divisible by grad_accum "
                f"{self.tc.grad_accum}")
        # No added blocking: steady-state timings measure dispatch (the
        # async-dispatch pipelining is the perf contract). The FIRST call
        # is synchronous through trace+compile, so it alone is a
        # meaningful wall measurement → train_compile_seconds.
        compiling = not self._stepped
        t0 = time.perf_counter()
        if self._last_step_end is not None:
            # Everything between consecutive step() calls — input
            # pipeline, checkpoint writes, eval, logging — is the
            # trainer's host gap.
            self.profiler.record("host_gap", t0 - self._last_step_end)
        if compiling:
            with obs.compile_ledger().span("startup.first_step"):
                state, loss = self._dispatch(state, tokens, targets, mask,
                                             compiling)
        else:
            state, loss = self._dispatch(state, tokens, targets, mask,
                                         compiling)
        if "moe_load" in self.last_aux:
            self._export_moe_load(self.last_aux["moe_load"])
        dt = time.perf_counter() - t0
        self._last_step_end = time.perf_counter()
        self.step_seconds.observe(dt)
        if compiling:
            self._stepped = True
            self.compile_seconds.observe(dt)
        return state, loss


def _scrape_refreshes(reg, watch) -> None:
    """A render of `reg` is when `watch` re-reads its function's cache,
    so that `train_recompiles_total` is current in every scrape; the
    collector leaves with its trainer."""
    alive = weakref.ref(watch)

    def collect():
        watch = alive()
        if watch is not None:
            watch.counts()
        return watch is not None

    reg.register_collector(collect)


def _opt_state_shardings(opt_shapes, params_shapes, param_shardings, mesh):
    """Opt-state leaves that mirror a param (optax mu/nu are copies of the
    param pytree) get that param's sharding; everything else (step counts,
    scalars) is replicated.

    Matching is by tree-path suffix + shape, NOT shape alone: for e.g.
    Llama-8B, wq [L, 4096, 4096] and wo [L, 4096, 4096] share a shape but
    have transposed shardings — a shape-only match would silently shard
    wo's adam moments wrong and force per-step resharding over ICI.
    """
    param_by_path: dict[tuple, Any] = {}
    flat_params = jax.tree.leaves_with_path(params_shapes)
    flat_shard = jax.tree.leaves(param_shardings)
    for (path, leaf), sh in zip(flat_params, flat_shard):
        param_by_path[tuple(str(p) for p in path)] = (leaf.shape, sh)

    replicated = NamedSharding(mesh, P())
    max_suffix = max((len(p) for p in param_by_path), default=0)

    def pick(opt_path, leaf):
        if not hasattr(leaf, "shape") or len(leaf.shape) == 0:
            return replicated
        keys = tuple(str(p) for p in opt_path)
        # Longest path-suffix of the opt leaf that names a param leaf.
        for n in range(min(len(keys), max_suffix), 0, -1):
            hit = param_by_path.get(keys[-n:])
            if hit is not None:
                shape, sh = hit
                if shape == leaf.shape:
                    return sh
                break
        return replicated

    return jax.tree_util.tree_map_with_path(pick, opt_shapes)
