"""Pure-JAX inference engine: static-shape KV cache, scan decode.

TPU constraints drive the design (pallas guide / XLA semantics):
- The KV cache is a fixed [L, b, max_len, n_kv, hd] buffer; prefill and
  decode write into it with `dynamic_update_slice`. No dynamic shapes —
  one compile per (batch, max_len) bucket, reused across requests.
- Decode is a single `lax.scan` over token steps: one trace, one
  compile, no per-token Python dispatch.
- Attention over the cache masks invalid slots by position (kv_mask), so
  the same `dot_product_attention` op serves train and serve.

Llama and Gemma share a block param schema (wq/wk/wv/wo, w_gate/w_up/
w_down, attn_norm/mlp_norm, final_norm, embed); a `Family` adapter
captures the differences (gate activation, embedding scale, tied head),
so one engine serves both families.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.obs.compiles import startup_span
from kubeflow_tpu.ops.attention import dot_product_attention
from kubeflow_tpu.ops.embedding import embed_lookup
from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.rotary import apply_rope, rope_frequencies
from kubeflow_tpu.ops.ssd import causal_conv, ssd_chunked, ssd_step
from kubeflow_tpu.serving.quant import qdot

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Family:
    """Model-family adapter for the shared llama/gemma block schema.

    `mlp` overrides the block's FFN half entirely (signature
    `(cfg, layer_params, normed_h) -> delta`): the MoE family routes
    through experts there while the attention half, KV cache, and
    sampling machinery stay shared."""

    name: str
    gate_act: Callable[[jnp.ndarray], jnp.ndarray]
    scale_embed: bool          # multiply embeddings by sqrt(hidden)
    mlp: Callable[..., jnp.ndarray] | None = None
    # Each layer's kind in model order, "attention" or "mamba"; None
    # where every layer attends. A model with recurrent layers keeps
    # its attention layers under params["blocks"] and the rest under
    # params["mamba_blocks"], each stacked in model order
    # (`scan_layers`), and is served by `ContinuousBatcher` alone.
    layer_kinds: tuple[str, ...] | None = None
    rotary: bool = True        # False: no positional encoding at all
    # Fixed multipliers (the Granite families'): on the embeddings, on
    # each half-block's output before the residual add, on q k^T (None:
    # head_dim ** -0.5), and what the logits are divided by.
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None
    logits_scaling: float = 1.0

    @property
    def recurrent(self) -> bool:
        return (self.layer_kinds is not None
                and "mamba" in self.layer_kinds)


LLAMA_FAMILY = Family("llama", jax.nn.silu, scale_embed=False)
GEMMA_FAMILY = Family(
    "gemma", lambda x: jax.nn.gelu(x, approximate=True), scale_embed=True
)


def _moe_serving_mlp(cfg, p, h: jnp.ndarray) -> jnp.ndarray:
    """Dropless MoE FFN for decode (models/llama_moe.py block schema:
    router [D,E] + per-expert SwiGLU stacks [E,D,M]). Training's
    capacity factor trades dropped tokens for load balance; serving
    must never drop — capacity_factor = E/k makes capacity equal the
    token count, and a token occupies at most one slot per expert, so
    every assignment fits. Decode token counts are tiny (batch x 1),
    so the [T, E, T] dispatch tensors cost nothing."""
    import dataclasses as _dc

    from kubeflow_tpu.parallel import moe as moe_lib

    mcfg = _dc.replace(
        cfg.moe_config(),
        capacity_factor=cfg.num_experts / cfg.top_k)
    params = {k: p[k].astype(cfg.dtype)
              for k in ("router", "w_gate", "w_up", "w_down")}
    y, _aux = moe_lib.moe_mlp(params, h, mcfg)
    return y


MOE_LLAMA_FAMILY = Family(
    "llama-moe", jax.nn.silu, scale_embed=False, mlp=_moe_serving_mlp)


def granite_hybrid_family(cfg) -> Family:
    """The family of one `models.granite_hybrid.GraniteHybridConfig`:
    its layer pattern and its four multipliers are the config's own."""
    return Family(
        "granite-hybrid", jax.nn.silu, scale_embed=False,
        layer_kinds=tuple(cfg.layer_types), rotary=False,
        embed_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_len: int = 1024        # cache bucket; one compile per value
    temperature: float = 0.0   # 0 = greedy
    top_k: int = 0             # keep k highest-logit tokens; 0 = off
    top_p: float = 1.0         # nucleus: smallest set w/ cum prob >= p
    # When set, sequences that emit EOS keep emitting EOS for the rest of
    # the (fixed-length) scan, so callers can trim on first EOS.
    eos_token: int | None = None


def _per_row(v: jnp.ndarray) -> jnp.ndarray:
    """[] stays scalar; [b] gains a trailing axis to broadcast against
    [b, vocab] logits (per-row sampling knobs)."""
    return v[..., None] if getattr(v, "ndim", 0) >= 1 else v


def scaled_filtered_logits(logits: jnp.ndarray,
                           sp: "SamplingParams") -> jnp.ndarray:
    """Temperature-scale then top-k/top-p filter — the ONE definition of
    the sampled distribution's logits, shared by the engine's sampler
    and the speculative verifier (a drifted copy there would silently
    break speculative decoding's target-law exactness). The cond skips
    the filter's argsorts when every row has both knobs off
    (temperature-only sampling keeps its pre-filter cost)."""
    scaled = logits.astype(jnp.float32) / jnp.maximum(
        _per_row(sp.temperature), 1e-6)
    return jax.lax.cond(
        jnp.any((sp.top_k > 0) | (sp.top_p < 1.0)),
        lambda s: filter_logits(s, _per_row(sp.top_k),
                                _per_row(sp.top_p)),
        lambda s: s, scaled)


class SamplingParams(NamedTuple):
    """Sampling knobs as TRACED values: requests with different
    temperature/top_k/top_p reuse one compiled decode scan (static
    shapes, dynamic values — recompiling a 30s scan per slider move
    would be the wrong TPU trade). Each field is a scalar [] or a
    per-row [batch] vector, so ONE batch can mix greedy and sampled
    rows with different knobs (the dynamic batcher relies on this)."""

    temperature: jnp.ndarray   # []/[b] f32; <= 0 means greedy
    top_k: jnp.ndarray         # []/[b] i32; 0 disables
    top_p: jnp.ndarray         # []/[b] f32; >= 1 disables


def filter_logits(logits: jnp.ndarray, top_k: jnp.ndarray,
                  top_p: jnp.ndarray) -> jnp.ndarray:
    """Mask logits outside the top-k set and the top-p nucleus to -inf.

    Both knobs are dynamic. HF-style order: the caller temperature-
    scales first, then k, then p (computed on the softmax of what
    remains representable — scaling changes the nucleus, as it should).
    """
    vocab = logits.shape[-1]
    # Decide in the sorted domain, scatter the mask back through the
    # inverse permutation. (Comparing original-domain probs against a
    # sorted-domain cutoff would be ulp-fragile: softmax sums in a
    # different order on each side, and one ulp can empty the nucleus.)
    order = jnp.argsort(-logits, axis=-1)           # descending
    desc = jnp.take_along_axis(logits, order, axis=-1)
    idx = jnp.arange(vocab)
    # top-k: the first k sorted positions. k=0 -> keep all.
    keep_desc = jnp.where(top_k > 0, idx < top_k, True)
    # top-p: the smallest prefix of descending probs whose mass reaches
    # p, over the distribution REMAINING after top-k (HF sequential
    # semantics: k filters, renormalize, then the nucleus) — the first
    # surviving token always stays; p>=1 keeps all.
    probs_desc = jnp.where(keep_desc, jax.nn.softmax(desc, axis=-1), 0.0)
    probs_desc = probs_desc / jnp.sum(probs_desc, axis=-1, keepdims=True)
    before = jnp.cumsum(probs_desc, axis=-1) - probs_desc
    keep_desc &= before < top_p
    inv = jnp.argsort(order, axis=-1)
    keep = jnp.take_along_axis(
        jnp.broadcast_to(keep_desc, logits.shape), inv, axis=-1)
    return jnp.where(keep, logits, -jnp.inf)


class DecodeState:
    """KV cache + cursor, a pytree (jit-carryable).

    `pad` marks cache slots holding left-pad keys (excluded from
    attention); `offset` is each row's pad count, so a token in slot i
    has LOGICAL position i - offset (what rope sees). Both stay zero
    for unpadded batches — the variable-length path costs nothing when
    unused."""

    def __init__(self, k, v, length, pad=None, offset=None):
        self.k = k              # [L, b, max_len, n_kv, hd]
        self.v = v
        self.length = length    # [] int32 — filled slots
        # Only touch k.shape when defaulting: tree_unflatten passes all
        # five children, whose leaves may be non-arrays mid-transform
        # (jax.tree.map over dtypes etc.).
        if pad is None:
            pad = jnp.zeros((k.shape[1], k.shape[2]), bool)
        if offset is None:
            offset = jnp.zeros((k.shape[1],), jnp.int32)
        self.pad = pad
        self.offset = offset

    def tree_flatten(self):
        return (self.k, self.v, self.length, self.pad, self.offset), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    DecodeState, DecodeState.tree_flatten, DecodeState.tree_unflatten
)


def _plain_proj(cfg):
    def proj(name, h, w):
        return qdot(h, w, cfg.dtype)
    return proj


def _residual(fam: Family, delta: jnp.ndarray) -> jnp.ndarray:
    """A half-block's output as it joins the residual stream."""
    if fam.residual_multiplier == 1.0:
        return delta
    return delta * jnp.asarray(fam.residual_multiplier, delta.dtype)


def mlp_half(cfg, fam: Family, p, x, proj):
    """The feed-forward half of a block, whatever its first half was:
    norm, gated MLP (or the family's own), residual add."""
    h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        if fam.mlp is not None:
            return x + _residual(fam, fam.mlp(cfg, p, h))
        gate = fam.gate_act(proj("w_gate", h, p["w_gate"]))
        ff = gate * proj("w_up", h, p["w_up"])
        return x + _residual(fam, proj("w_down", ff, p["w_down"]))


def transformer_block(cfg, fam: Family, p, x, rope_positions, inv_freq,
                      write_kv, attn, proj=None):
    """One decoder block on `x` [b, s, h]: norms, QKV/output projections,
    rotary, gated MLP. The KV-cache write policy and the attention call
    are injected: prefill writes a contiguous [s]-slice at one shared
    scalar cursor (`_forward_cached`), the continuous-batching engine
    scatters a single step per row at per-slot cursors
    (serving/continuous.py). `proj(name, h, w)` optionally wraps every
    block matmul — multi-LoRA serving adds its per-row low-rank delta
    there (serving/multilora.py) — and defaults to the plain matmul.
    Keeping every matmul/norm/activation in ONE function is what makes
    the serving paths provably the same model — a drifted copy would
    silently change logits.

    The parts carry the scope names a device trace is read by
    (`attn_proj`, `kv_write`, `mlp`; `rms_norm` opens `norm` and the
    attention call its kernel's own, in ops/), the same as the
    training forward's (models/llama.py). Scopes are HLO metadata: they change
    no compiled instruction."""
    if proj is None:
        proj = _plain_proj(cfg)

    b, s = x.shape[:2]
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    with jax.named_scope("attn_proj"):
        # The barrier keeps the head split's layout on the activation:
        # XLA's layout assignment otherwise carries the reshape's
        # preferred layout back through the product to the stacked
        # weight, and the programs transpose wq/wk/wv (whole, once a
        # decode dispatch; a slice a layer, before the product can
        # start) where wo and the MLP read theirs in place (PERF.md
        # section 6, PR 35; tests/test_tpu_compile.py reads the
        # compiled text).
        q, k, v = jax.lax.optimization_barrier(
            tuple(proj(name, h, p[name]) for name in ("wq", "wk", "wv")))
        q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        if fam.rotary:
            q = apply_rope(q, rope_positions, inv_freq)
            k = apply_rope(k, rope_positions, inv_freq)
        if fam.attention_multiplier is not None:
            # the attention calls scale q k^T by head_dim ** -0.5: q
            # carries the rest (a power of two at Granite's sizes)
            q = q * jnp.asarray(
                fam.attention_multiplier * cfg.head_dim ** 0.5, q.dtype)
    with jax.named_scope("kv_write"):
        k_cache, v_cache = write_kv(k, v)
    out = attn(q, k_cache, v_cache)
    with jax.named_scope("attn_proj"):
        x = x + _residual(
            fam, proj("wo", out.reshape(b, s, cfg.q_dim), p["wo"]))
    return mlp_half(cfg, fam, p, x, proj), (k_cache, v_cache)


class RecurrentState(NamedTuple):
    """The recurrent state of every slot at every Mamba layer, in
    `cfg.state_dtype`: the second cache kind of a continuous batch,
    beside the paged KV pool (`serving/continuous.py` `SlotState.rec`)."""

    conv: jnp.ndarray     # [Lm, S, K - 1, conv_dim] — last conv inputs
    ssm: jnp.ndarray      # [Lm, S, n_heads, d_head, d_state]


def mamba_block(cfg, fam: Family, p, x, rec: RecurrentState, li, slots,
                n_valid, proj=None):
    """One Mamba-2 block on `x` [b, s, h], from and to the rows' state
    in `rec`: layer `li` (an int32 scalar) of slots `slots` ([b] int32;
    None where x's rows are all the slots, in order). `n_valid` [b]
    says how many of a row's `s` tokens count: the rest are padding,
    and a row with none stands still (its state is written back bit
    for bit, or, where `slots` names it, not written at all: a slot
    listed twice has its real row in the same scatter).
    -> (x, rec), the state after the valid tokens, written in place.

    The recurrence runs in float32 whatever the state is stored in
    (`ops/ssd.py`); one token a row takes the direct update (scope
    `ssm_update`, a decode step), a slice the chunked form (`ssd_scan`);
    each scope holds the state's read and its write. The other scopes:
    `ssm_proj` (the projections in and out), `short_conv` (with its
    tail's read and write), `norm`; the feed-forward half is
    `mlp_half`, the attention block's own."""
    if proj is None:
        proj = _plain_proj(cfg)
    b, s = x.shape[:2]
    nh, hd = cfg.mamba_n_heads, cfg.mamba_d_head
    ng, ns = cfg.mamba_n_groups, cfg.mamba_d_state
    f32 = jnp.float32
    if slots is None:
        def read(state):
            return state[li]

        def write(state, new):
            return state.at[li].set(new.astype(state.dtype))
    else:
        put = jnp.where(n_valid > 0, slots, rec.ssm.shape[1])

        def read(state):
            return state[li, slots]

        def write(state, new):
            return state.at[li, put].set(new.astype(state.dtype),
                                         mode="drop")

    h = rms_norm(x, p["ssm_norm"], cfg.norm_eps)
    with jax.named_scope("ssm_proj"):
        z, xbc, dt = (proj(name, h, p[name])
                      for name in ("w_z", "w_xbc", "w_dt"))
    with jax.named_scope("short_conv"):
        xbc, tail = causal_conv(xbc, read(rec.conv), p["conv_w"],
                                p["conv_b"], n_valid)
        conv = write(rec.conv, tail)
        xbc = jax.nn.silu(xbc)
    xs = xbc[..., :cfg.d_inner].reshape(b, s, nh, hd)
    bm = xbc[..., cfg.d_inner:cfg.d_inner + ng * ns].reshape(b, s, ng, ns)
    cm = xbc[..., cfg.d_inner + ng * ns:].reshape(b, s, ng, ns)
    valid = jnp.arange(s)[None, :] < n_valid[:, None]
    dt = jnp.where(
        valid[..., None],
        jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32)), 0.0)
    a = -jnp.exp(p["A_log"].astype(f32))
    if s == 1:
        with jax.named_scope("ssm_update"):
            y, state = ssd_step(xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                p["D"], read(rec.ssm))
            y, ssm = y[:, None], write(rec.ssm, state)
    else:
        with jax.named_scope("ssd_scan"):
            y, state = ssd_chunked(xs, dt, a, bm, cm, p["D"], read(rec.ssm))
            ssm = write(rec.ssm, state)
    with jax.named_scope("norm"):
        # gated RMSNorm over the whole inner width, the gate before
        # the norm, in float32
        y = (y.astype(cfg.dtype).reshape(b, s, cfg.d_inner).astype(f32)
             * jax.nn.silu(z.astype(f32)))
    y = rms_norm(y, p["gate_norm"], cfg.norm_eps).astype(cfg.dtype)
    with jax.named_scope("ssm_proj"):
        x = x + _residual(fam, proj("w_out", y, p["w_out"]))
    return mlp_half(cfg, fam, p, x, proj), RecurrentState(conv, ssm)


def _layer_plan(kinds: tuple[str, ...]):
    """-> (periods, runs): the shortest pattern `kinds` repeats, as
    runs of one kind [(kind, count), ...], and how often it repeats."""
    n = len(kinds)
    period = next(p for p in range(1, n + 1)
                  if n % p == 0 and kinds == kinds[:p] * (n // p))
    runs: list[list] = []
    for kind in kinds[:period]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return n // period, [tuple(r) for r in runs]


def scan_layers(cfg, fam: Family, params, carry, attention_layer,
                mamba_layer=None, adapters=None):
    """Every block of the model over `carry`, in model order, as loops
    and not as unrolled layers. `attention_layer(carry, (p, [ab,] li))`
    and `mamba_layer(carry, (p, li))` are scan bodies: `p` is one
    layer's parameters and `li` its index in its own kind's stack
    (which is the layer axis of that kind's cache). -> the carry.

    Where every layer attends this is the one `lax.scan` over
    `params["blocks"]` the serving paths always ran. With
    `fam.layer_kinds` it is a scan over the pattern's periods, and
    inside a period a scan over each run of one kind, every layer's
    parameters indexed out of its kind's stack where they lie."""
    if fam.layer_kinds is None:
        ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        xs = ((params["blocks"], ids) if adapters is None
              else (params["blocks"], adapters, ids))
        return jax.lax.scan(attention_layer, carry, xs)[0]
    periods, runs = _layer_plan(fam.layer_kinds)
    stacks = {"attention": params["blocks"],
              "mamba": params.get("mamba_blocks")}
    bodies = {"attention": attention_layer, "mamba": mamba_layer}
    per_period = {kind: sum(c for k, c in runs if k == kind)
                  for kind in bodies}

    def one(carry, kind, li):
        p = jax.tree.map(
            lambda w: jax.lax.dynamic_index_in_dim(w, li, 0, keepdims=False),
            stacks[kind])
        return bodies[kind](carry, (p, li))[0]

    def period(carry, pi):
        seen = dict.fromkeys(bodies, 0)
        for kind, count in runs:
            first = pi * per_period[kind] + seen[kind]
            if count == 1:
                carry = one(carry, kind, first)
            else:
                carry, _ = jax.lax.scan(
                    lambda c, j, kind=kind, first=first:
                        (one(c, kind, first + j), None),
                    carry, jnp.arange(count, dtype=jnp.int32))
            seen[kind] += count
        return carry, None

    if periods == 1:
        return period(carry, jnp.int32(0))[0]
    return jax.lax.scan(period, carry,
                        jnp.arange(periods, dtype=jnp.int32))[0]


class InferenceEngine:
    """Batched greedy/temperature generation for a llama-family model.

    `cfg` is the model's LlamaConfig/GemmaConfig (shared field names).
    Jitted entry points are cached per (batch, prompt_len, max_new).
    """

    @startup_span("startup.engine")
    def __init__(self, params: Params, cfg, family: Family,
                 engine_config: EngineConfig = EngineConfig(),
                 adapter_pack=None):
        self.params = params
        self.cfg = cfg
        self.family = family
        self.ec = engine_config
        # Multi-LoRA: serving/multilora.AdapterPack of K resident
        # fine-tunes; requests select per row (id 0 = plain base).
        self.adapter_pack = adapter_pack
        # Params flow through every jitted entry point as an ARGUMENT
        # (deliberately NOT donated — self.params is reused every call).
        # Closing over self.params would embed the whole tree into the
        # lowered module as literal constants — at 500M params that is
        # a ~1 GB MLIR module whose TPU compile runs past 10 minutes
        # (measured: 75 s just to lower), vs seconds when the compiler
        # sees only shapes.
        self._generate_jit = jax.jit(
            self._generate, static_argnames=("max_new",)
        )

    # -- model internals ---------------------------------------------------

    @property
    def kv_layers(self) -> int:
        """Layers that keep K and V: the layer axis of every KV cache."""
        kinds = self.family.layer_kinds
        return (self.cfg.num_layers if kinds is None
                else kinds.count("attention"))

    @property
    def mamba_layers(self) -> int:
        """Layers that keep a recurrent state a slot; 0 for most models."""
        return (self.family.layer_kinds or ()).count("mamba")

    @jax.named_scope("embed")
    def _embed(self, params, tokens):
        cfg = self.cfg
        # Mesh-aware (ops.embedding): a gather is fine single-chip, but a
        # sharded 256k-vocab Gemma table must contract via one-hot or the
        # SPMD partitioner replicates the full table per step.
        x = embed_lookup(params["embed"], tokens, cfg.dtype)
        if self.family.scale_embed:
            x = x * jnp.asarray(cfg.hidden_size ** 0.5, cfg.dtype)
        if self.family.embed_multiplier != 1.0:
            x = x * jnp.asarray(self.family.embed_multiplier, cfg.dtype)
        return x

    @jax.named_scope("head")
    def _head(self, params, x):
        tied = "lm_head" not in params
        head = params["embed"].T if tied else params["lm_head"]
        logits = x.astype(jnp.float32) @ head.astype(jnp.float32)
        if self.family.logits_scaling != 1.0:
            logits = logits / self.family.logits_scaling
        return logits

    def _forward_cached(self, params, tokens, state: DecodeState, *,
                        prompt_mask=None, return_all: bool = False,
                        adapters=None, adapter_ids=None):
        """Run [b, s] tokens starting at state.length; returns
        (last-position logits [b, vocab], updated state) — or all
        positions' logits [b, s, vocab] with return_all (speculative
        decoding scores every drafted position in one pass).

        `prompt_mask` [b, s] bool (False = pad) enables variable-length
        rows in one batch. Pads must be LEFT-aligned (the final column
        is what the next-token logits read) — pad slots are excluded
        from every later attention and rope sees logical positions
        (slot - pad count), so a padded row computes exactly what the
        unpadded prompt would.

        `params` is threaded as an argument, never closed over — see
        the constructor note on compile-time cost."""
        cfg, fam = self.cfg, self.family
        if fam.recurrent:
            raise NotImplementedError(
                f"{fam.name} has recurrent layers, whose state the dense "
                "cache does not hold: serve it through ContinuousBatcher "
                "(a recurrent state per slot beside the paged pool)")
        b, s = tokens.shape
        start = state.length
        # Slot positions order the cache for causal masking; rope gets
        # logical positions (slot - offset) so padding never shifts a
        # token's rotary phase.
        positions = start + jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))
        pad, offset = state.pad, state.offset
        if prompt_mask is not None:
            offset = offset + jnp.sum(
                ~prompt_mask, axis=1, dtype=jnp.int32)
            pad = jax.lax.dynamic_update_slice(
                pad, ~prompt_mask, (0, start))
        rope_positions = jnp.maximum(positions - offset[:, None], 0)
        inv_freq = rope_frequencies(cfg.head_dim, theta=cfg.rope_theta)
        kv_positions = jnp.broadcast_to(
            jnp.arange(self.ec.max_len, dtype=jnp.int32)[None, :],
            (b, self.ec.max_len))
        kv_valid = (kv_positions < (start + s)) & ~pad

        x = self._embed(params, tokens)

        # The KV cache rides the layer scan as CARRY, not as scanned
        # xs/ys: stacking per-layer cache slices as scan outputs made
        # XLA materialize a fresh copy of the ENTIRE cache every
        # forward call — on a decode step that doubled HBM traffic
        # (full-cache write next to the unavoidable full-cache read),
        # capping decode MBU at ~half the roofline. Carried buffers
        # updated via dynamic_update_slice stay in place (the canonical
        # while-loop aliasing pattern), so the only cache WRITE per
        # step is the s new rows per layer.
        def layer(carry, scanned):
            x, k_all, v_all = carry
            if adapters is None:
                p, li = scanned
                proj = None
            else:
                from kubeflow_tpu.serving.multilora import lora_proj
                p, ab, li = scanned
                proj = lora_proj(ab, adapter_ids,
                                 self.adapter_pack.scaling, cfg)
            cell = {}

            def write_kv(k, v):
                k2 = jax.lax.dynamic_update_slice(
                    k_all, k[None].astype(k_all.dtype),
                    (li, 0, start, 0, 0))
                v2 = jax.lax.dynamic_update_slice(
                    v_all, v[None].astype(v_all.dtype),
                    (li, 0, start, 0, 0))
                cell["k"], cell["v"] = k2, v2
                return (jax.lax.dynamic_index_in_dim(
                            k2, li, 0, keepdims=False),
                        jax.lax.dynamic_index_in_dim(
                            v2, li, 0, keepdims=False))

            def attn(q, kc, vc):
                # contiguous_positions: this cache's cell index IS the
                # token position (kv_positions = arange(max_len)), the
                # declaration the fused decode kernel dispatches on
                return dot_product_attention(
                    q, kc, vc, positions, kv_positions,
                    causal=True, kv_mask=kv_valid,
                    window=getattr(cfg, "sliding_window", None),
                    contiguous_positions=True)

            x, _ = transformer_block(
                cfg, fam, p, x, rope_positions, inv_freq, write_kv,
                attn, proj)
            return (x, cell["k"], cell["v"]), None

        n_layers = cfg.num_layers
        layer_ids = jnp.arange(n_layers, dtype=jnp.int32)
        xs = ((params["blocks"], layer_ids) if adapters is None
              else (params["blocks"], adapters, layer_ids))
        (x, k_new, v_new), _ = jax.lax.scan(
            layer, (x, state.k, state.v), xs)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x if return_all else x[:, -1])
        return logits, DecodeState(k_new, v_new, start + s, pad, offset)

    # -- public API --------------------------------------------------------

    def init_state(self, batch: int) -> DecodeState:
        cfg = self.cfg
        shape = (cfg.num_layers, batch, self.ec.max_len,
                 cfg.num_kv_heads, cfg.head_dim)
        return DecodeState(
            jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype),
            jnp.zeros((), jnp.int32))

    def kv_cache_bytes(self, batch: int, cells: int | None = None) -> int:
        """KV-cache HBM for `batch` rows of `cells` cache cells (K+V,
        all layers; defaults to max_len — the dense worst case). The
        common yardstick for the paged bench and the observability
        docs: the dense engine always pays batch * max_len, the paged
        pool pays blocks_in_use * block_size."""
        cfg = self.cfg
        if cells is None:
            cells = self.ec.max_len
        itemsize = jnp.dtype(cfg.dtype).itemsize
        return (2 * self.kv_layers * batch * cells
                * cfg.num_kv_heads * cfg.head_dim * itemsize)

    @jax.named_scope("sample")
    def _sample(self, logits, rng, sp: SamplingParams):
        """-> (tokens [b], logprobs [b]). The logprob is the chosen
        token's log-softmax under the RAW model distribution
        (temperature/filters don't rescale it — OpenAI convention).
        Computed UNCONDITIONALLY by design: the O(b·vocab) pass is <1%
        of the O(b·hidden·vocab) head matmul that produced the logits
        at real vocab/hidden sizes (tiny-CPU A/Bs exaggerate it), and
        a jit-static opt-in flag would double the warmed compile set
        of every serving entry point for that <1%."""
        # lax.cond, not jnp.where: an all-greedy decode must not pay
        # the sampled branch's full-vocab argsorts/cumsum/categorical
        # per step (256k vocab on Gemma) just to discard the result.
        # Mixed batches take the sampled branch and select per row.
        def greedy(_):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def sampled(_):
            drawn = jax.random.categorical(
                rng, scaled_filtered_logits(logits, sp),
                axis=-1).astype(jnp.int32)
            return jnp.where(sp.temperature > 0.0, drawn, greedy(None))

        tok = jax.lax.cond(
            jnp.any(sp.temperature > 0.0), sampled, greedy, None)
        raw = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        lp = jnp.take_along_axis(raw, tok[:, None], axis=-1)[:, 0]
        return tok, lp

    def _resolve_sampling(
        self, temperature, top_k, top_p, rng: jax.Array | None,
        batch: int | None = None,
    ) -> tuple[SamplingParams, jax.Array]:
        """EngineConfig defaulting + validation + default-rng policy,
        shared with SpeculativeEngine so the two paths cannot drift.
        Each knob is a scalar or a per-row vector (mixed batches)."""
        temperature = np.asarray(
            self.ec.temperature if temperature is None else temperature,
            np.float32)
        top_k = np.asarray(
            self.ec.top_k if top_k is None else top_k, np.int64)
        top_p = np.asarray(
            self.ec.top_p if top_p is None else top_p, np.float32)
        for name, arr in (("temperature", temperature), ("top_k", top_k),
                          ("top_p", top_p)):
            if arr.ndim > 1:
                raise ValueError(f"{name} must be scalar or 1-D, "
                                 f"got shape {arr.shape}")
            if (arr.ndim == 1 and batch is not None
                    and len(arr) != batch):
                raise ValueError(
                    f"{name} has {len(arr)} entries for a batch of "
                    f"{batch}")
        if (top_k < 0).any():
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if (top_k >= 2**31).any():
            # validated as int64 above, stored int32 below: without this
            # check a library caller's huge top_k would silently wrap
            # negative (the HTTP server range-checks; the Python API
            # must reject identically)
            raise ValueError(f"top_k must be < 2**31, got {top_k}")
        if not ((0.0 < top_p) & (top_p <= 1.0)).all():
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if any(a.ndim == 1 for a in (temperature, top_k, top_p)):
            # one vector -> all vectors: [] vs [b] are different jit
            # signatures, and mixed combos would compile 2^3 variants
            n = (batch if batch is not None else max(
                a.shape[0] for a in (temperature, top_k, top_p)
                if a.ndim == 1))
            temperature = np.broadcast_to(temperature, (n,))
            top_k = np.broadcast_to(top_k, (n,))
            top_p = np.broadcast_to(top_p, (n,))
        sp = SamplingParams(
            temperature=jnp.asarray(temperature, jnp.float32),
            top_k=jnp.asarray(top_k, jnp.int32),
            top_p=jnp.asarray(top_p, jnp.float32),
        )
        if rng is None:
            if (temperature > 0.0).any():
                # Fresh entropy per request — a constant default key
                # would make every "sampled" completion identical; 63
                # seed bits keep birthday collisions out of reach while
                # staying inside np.int64 (jax.random.key rejects
                # Python ints >= 2**63).
                rng = jax.random.key(
                    int.from_bytes(os.urandom(8), "little") >> 1)
            else:
                # greedy: the cond's sampled branch never runs, so the
                # constant key is never drawn from at runtime
                rng = jax.random.key(0)
        return sp, rng

    def _prefill_sample(self, params, prompt, state, rng,
                        sp: SamplingParams, prompt_mask,
                        adapters=None, adapter_ids=None):
        """Prefill + sample token #1 (and its logprob). Shared head of
        generate and generate_stream so both follow the same rng
        discipline."""
        eos = self.ec.eos_token
        rng, sub = jax.random.split(rng)  # use-once key discipline
        logits, state = self._forward_cached(
            params, prompt, state, prompt_mask=prompt_mask,
            adapters=adapters, adapter_ids=adapter_ids)
        first, lp = self._sample(logits, sub, sp)
        done = (first == eos) if eos is not None else jnp.zeros(
            first.shape, bool)
        return state, first, rng, done, lp

    def _decode_chunk(self, params, state, tok, rng, done,
                      sp: SamplingParams, *, length: int,
                      adapters=None, adapter_ids=None):
        """`length` decode steps from carry. Returns the new carry, the
        [b, length] tokens and their logprobs (logprob entries past a
        row's first EOS describe the pre-forcing sampled token and are
        undefined for callers). The ONE step body both entry points
        scan over — stream-vs-oneshot equality is by construction."""
        eos = self.ec.eos_token

        def step(carry, _):
            state, tok, rng, done = carry
            rng, sub = jax.random.split(rng)
            logits, state = self._forward_cached(
                params, tok[:, None], state,
                adapters=adapters, adapter_ids=adapter_ids)
            nxt, lp = self._sample(logits, sub, sp)
            if eos is not None:
                # Sequences past EOS emit EOS forever (static shapes —
                # the scan always runs `length` steps; callers trim).
                nxt = jnp.where(done, jnp.asarray(eos, nxt.dtype), nxt)
                done = done | (nxt == eos)
            return (state, nxt, rng, done), (nxt, lp)

        (state, tok, rng, done), (rest, lps) = jax.lax.scan(
            step, (state, tok, rng, done), None, length=length)
        return (state, tok, rng, done, jnp.moveaxis(rest, 0, 1),
                jnp.moveaxis(lps, 0, 1))

    def _generate(self, params, prompt, state, rng, sp: SamplingParams,
                  prompt_mask, *, max_new: int,
                  adapters=None, adapter_ids=None):
        state, first, rng, done, lp1 = self._prefill_sample(
            params, prompt, state, rng, sp, prompt_mask,
            adapters, adapter_ids)
        state, _, _, _, rest, lps = self._decode_chunk(
            params, state, first, rng, done, sp, length=max_new - 1,
            adapters=adapters, adapter_ids=adapter_ids)
        toks = jnp.concatenate([first[:, None], rest], axis=1)
        lps = jnp.concatenate([lp1[:, None], lps], axis=1)
        return toks, lps, state

    def generate(
        self,
        prompt_tokens: jnp.ndarray,   # [b, s] int32
        *,
        max_new: int = 32,
        rng: jax.Array | None = None,
        temperature: float | None = None,
        top_k: int | None = None,
        top_p: float | None = None,
        prompt_mask: jnp.ndarray | None = None,  # [b, s] bool, False=pad
        prefill_chunk: int | None = None,
        adapter: "str | list[str] | None" = None,
        return_logprobs: bool = False,
    ) -> jnp.ndarray:
        """Generate `max_new` tokens after the prompt. Returns [b, max_new]
        (post-hoc EOS trimming is the caller's job — shapes stay static).

        temperature/top_k/top_p default from EngineConfig; per-call
        overrides are dynamic (no recompile across values).
        `prompt_mask` batches variable-length prompts: pads LEFT-aligned
        (False entries), each row decodes as if it were unpadded.
        `prefill_chunk` prefills long prompts in fixed slices (see
        prefill_chunked) — same tokens, chunk-bounded compile shapes
        and activation memory. `adapter` (needs an adapter_pack) picks
        a resident LoRA fine-tune — one name for the whole batch or
        one per row; ''/None rows decode the plain base.
        `return_logprobs` returns (tokens, logprobs): each chosen
        token's raw-model log-softmax (entries past a row's first EOS
        are undefined)."""
        sp, rng, prompt_mask, state = self._prep(
            prompt_tokens, max_new, rng, temperature, top_k, top_p,
            prompt_mask)
        adapters = adapter_ids = None
        if adapter is not None:
            if self.adapter_pack is None:
                raise ValueError("no adapter_pack loaded on this engine")
            names = ([adapter] * prompt_tokens.shape[0]
                     if isinstance(adapter, str) else list(adapter))
            if len(names) != prompt_tokens.shape[0]:
                raise ValueError(
                    f"{len(names)} adapter names for a batch of "
                    f"{prompt_tokens.shape[0]}")
            adapters = self.adapter_pack.blocks
            adapter_ids = jnp.asarray(
                [self.adapter_pack.resolve(n) for n in names], jnp.int32)
        if prefill_chunk is None:
            toks, lps, _ = self._generate_jit(
                self.params, prompt_tokens, state, rng, sp, prompt_mask,
                max_new=max_new, adapters=adapters,
                adapter_ids=adapter_ids)
            return (toks, lps) if return_logprobs else toks
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        b, n = prompt_tokens.shape
        pad = (-n) % prefill_chunk
        if n + pad + max_new > self.ec.max_len:
            raise ValueError(
                f"chunk-padded prompt {n + pad} + max_new {max_new} "
                f"exceeds cache bucket {self.ec.max_len}")
        if pad:
            prompt_tokens = jnp.concatenate(
                [jnp.zeros((b, pad), prompt_tokens.dtype),
                 prompt_tokens], axis=1)
            prompt_mask = jnp.concatenate(
                [jnp.zeros((b, pad), bool), prompt_mask], axis=1)
        state, first, rng, done, lp1 = self.prefill_chunked(
            self.params, prompt_tokens, state, rng, sp, prompt_mask,
            chunk=prefill_chunk, adapters=adapters,
            adapter_ids=adapter_ids)
        _, _, _, _, rest, lps = self._chunk_jit(
            self.params, state, first, rng, done, sp,
            length=max_new - 1, adapters=adapters,
            adapter_ids=adapter_ids)
        toks = jnp.concatenate([first[:, None], rest], axis=1)
        if return_logprobs:
            return toks, jnp.concatenate([lp1[:, None], lps], axis=1)
        return toks

    def _prep(self, prompt_tokens, max_new, rng, temperature, top_k,
              top_p, prompt_mask):
        """Shared validation + sampling/state setup for both entry
        points."""
        b, s = prompt_tokens.shape
        if s + max_new > self.ec.max_len:
            raise ValueError(
                f"prompt {s} + max_new {max_new} exceeds cache bucket "
                f"{self.ec.max_len}")
        if prompt_mask is not None:
            if prompt_mask.shape != (b, s):
                raise ValueError(
                    f"prompt_mask shape {prompt_mask.shape} != {(b, s)}")
            m = np.asarray(prompt_mask, bool)
            if not (np.sort(m, axis=1) == m).all() or not m[:, -1].all():
                raise ValueError(
                    "prompt_mask pads must be LEFT-aligned (False... "
                    "then True...) with a real final token per row")
            prompt_mask = jnp.asarray(m)
        else:
            prompt_mask = jnp.ones((b, s), bool)
        sp, rng = self._resolve_sampling(temperature, top_k, top_p, rng,
                                         batch=b)
        return sp, rng, prompt_mask, self.init_state(b)

    def generate_stream(
        self,
        prompt_tokens: jnp.ndarray,   # [b, s] int32
        *,
        max_new: int = 32,
        chunk: int = 8,
        rng: jax.Array | None = None,
        temperature: float | None = None,
        top_k: int | None = None,
        top_p: float | None = None,
        prompt_mask: jnp.ndarray | None = None,
    ):
        """Yield [b, <=chunk] numpy token chunks as they decode.

        Same sampling law AND same rng split discipline as generate():
        with equal arguments the concatenated stream equals generate()'s
        prefix exactly (the shared _prefill_sample/_decode_chunk pair is
        the proof). Unlike generate(), the stream stops early once every
        row has hit EOS — a stream's length is allowed to be dynamic.
        Compiled programs per prompt shape: prefill, the full chunk,
        and one tail per distinct (max_new-1) % chunk — bounded by
        `chunk` total, never one per max_new value.

        Validation is eager (this is a plain method returning an inner
        generator): bad arguments raise HERE, not at first next().
        """
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        sp, rng, prompt_mask, state = self._prep(
            prompt_tokens, max_new, rng, temperature, top_k, top_p,
            prompt_mask)

        def _iter():
            state_, tok, rng_, done, _ = self._prefill_jit(
                self.params, prompt_tokens, state, rng, sp, prompt_mask)
            yield np.asarray(tok)[:, None]
            emitted = 1
            while emitted < max_new:
                if self.ec.eos_token is not None and bool(
                        np.asarray(done).all()):
                    return
                n = min(chunk, max_new - emitted)
                state_, tok, rng_, done, rest, _ = self._chunk_jit(
                    self.params, state_, tok, rng_, done, sp, length=n)
                yield np.asarray(rest)
                emitted += n

        return _iter()

    @functools.cached_property
    def _prefill_jit(self):
        return jax.jit(self._prefill_sample)

    @functools.cached_property
    def _chunk_jit(self):
        return jax.jit(self._decode_chunk, static_argnames=("length",))

    @functools.cached_property
    def _forward_jit(self):
        return jax.jit(self._forward_cached)

    def _score(self, params, tokens, state, prompt_mask):
        logits, _ = self._forward_cached(
            params, tokens, state, prompt_mask=prompt_mask,
            return_all=True)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        # token i is predicted by position i-1: shift, gather, and
        # zero the pad positions so a masked row scores only its tokens
        tgt = tokens[:, 1:]
        got = jnp.take_along_axis(lp[:, :-1], tgt[:, :, None],
                                  axis=-1)[:, :, 0]
        return got * prompt_mask[:, 1:].astype(jnp.float32)

    @functools.cached_property
    def _score_jit(self):
        return jax.jit(self._score)

    def score(self, tokens: jnp.ndarray,
              prompt_mask: jnp.ndarray | None = None) -> jnp.ndarray:
        """Teacher-forced scoring: log P(token_i | tokens_<i) for every
        position past the first, [b, s-1] fp32 (pad positions 0) — the
        perplexity/eval path (lm-eval style), no decoding. One forward,
        `return_all` logits, no cache reuse across calls."""
        b, s = tokens.shape
        if s < 2:
            raise ValueError("scoring needs at least 2 tokens")
        if s > self.ec.max_len:
            raise ValueError(
                f"sequence {s} exceeds cache bucket {self.ec.max_len}")
        if prompt_mask is None:
            prompt_mask = jnp.ones((b, s), bool)
        return self._score_jit(self.params, tokens, self.init_state(b),
                               prompt_mask)

    def prefill_chunked(self, params, prompt, state, rng,
                        sp: SamplingParams, prompt_mask, *, chunk: int,
                        adapters=None, adapter_ids=None):
        """Prefill in fixed `chunk`-token slices through the
        incremental cache, then sample token #1 from the final slice.

        Long-context serving's standard shape-bounding move: a 32k
        prompt compiles ONE [b, chunk] program instead of one program
        (and one activation working set) per long-prompt bucket —
        chunk i attends the cache filled by chunks 0..i-1, which is
        exactly what `_forward_cached` computes. The final slice goes
        through `_prefill_sample`, so the rng discipline and sampled
        law equal the one-shot prefill bit for bit (earlier slices
        never consume rng). Rows whose pads span whole early slices
        are safe: a fully-masked row attends nothing (finite NEG_INF
        masking, no NaN) and its garbage positions are never sampled —
        only the final slice's last column is.

        `prompt` width must be a multiple of `chunk` (callers left-pad
        and extend `prompt_mask` accordingly)."""
        b, n = prompt.shape
        if n % chunk:
            raise ValueError(f"prompt width {n} not a multiple of "
                             f"chunk {chunk} (left-pad first)")
        for i in range(n // chunk - 1):
            sl = slice(i * chunk, (i + 1) * chunk)
            _, state = self._forward_jit(
                params, prompt[:, sl], state,
                prompt_mask=prompt_mask[:, sl],
                adapters=adapters, adapter_ids=adapter_ids)
        return self._prefill_jit(
            params, prompt[:, n - chunk:], state, rng, sp,
            prompt_mask[:, n - chunk:],
            adapters=adapters, adapter_ids=adapter_ids)
