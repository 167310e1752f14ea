"""Kimi-Linear (`model_type` `kimi_linear`): a decoder whose layers are
of more than one kind.

Pre-norm residual blocks. The attention of a layer is either **KDA**
(Kimi Delta Attention, `ops/kda.py`: a gated delta rule with a decay
per channel, fed by short causal convolutions) or **MLA without
positions** (latent attention, `mla_use_nope`: keys and values come
up from a 512-wide latent, 64 more key dimensions are shared by all
heads, nothing is rotated; `q`/`k` heads of 192 beside `v` heads of
128 go through the same flash kernel every other model uses). The
feed-forward of the leading `first_k_dense_replace` layers is a dense
SwiGLU; every later layer adds one shared expert to the routed experts
this chip holds (`parallel/moe.py` `routed_experts`: sigmoid scores
over all `num_experts`, top-k, renormalised and scaled, nothing
dropped).

Which layer is which is read from the config, by the published layer
numbers (1-based): `kda_layers`, `full_attn_layers`,
`first_k_dense_replace`. A config holds layers `first_layer ..
first_layer + num_layers - 1` of that pattern and the experts
`experts_held = (first, count)` of each expert layer: the whole model,
or the share of it that one chip of a deployment holds. The layers are
unrolled (a list of per-layer dicts, each kind with its own
parameters): five or twenty-seven blocks of two kinds do not stack
into one `lax.scan`. Each is under `jax.checkpoint` and rematerialised
whole in the backward pass, but for a KDA layer's scan, whose output
and segment states are kept (`_layer`).

Parameters and activations are `param_dtype` / `dtype` (bf16 by
default); KDA's state, its gates and their cumulative sums, the norms,
the router's scores and the softmax are float32.

This module trains (`Trainer` through `init`, `hidden`, `apply`,
`unembed_matrix`, `param_logical_axes`). Serving it needs a recurrent
state per slot beside a latent paged pool, which `serving/` does not
have yet (ROADMAP).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops import kda as kda_ops
from kubeflow_tpu.ops.attention import dot_product_attention
from kubeflow_tpu.ops.embedding import embed_lookup
from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.parallel import moe as moe_lib
from kubeflow_tpu.parallel.sharding import with_sharding_constraint as wsc

Params = dict[str, Any]

_PUBLISHED_FULL_ATTN = (4, 8, 12, 16, 20, 24, 27)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216          # the dense layers' SwiGLU
    moe_intermediate_size: int = 1024      # each expert's, the shared one's
    # the layers held, by their published numbers (1-based)
    first_layer: int = 1
    num_layers: int = 27
    kda_layers: tuple[int, ...] = tuple(
        n for n in range(1, 28) if n not in _PUBLISHED_FULL_ATTN)
    full_attn_layers: tuple[int, ...] = _PUBLISHED_FULL_ATTN
    first_k_dense_replace: int = 1
    # KDA
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_gate_rank: int = 128               # of the two low-rank gates
    kda_segment: int = 1024                # tokens rematerialised together
    # MLA without positions
    num_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64             # shared by the heads, not rotated
    v_head_dim: int = 128
    attention_impl: str = "auto"           # ops.attention's `impl`
    # experts
    num_experts: int = 256                 # the router's outputs
    num_experts_per_token: int = 8
    routed_scaling_factor: float = 2.446
    experts_held: tuple[int, int] = (0, 256)   # (first, count) held here
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        for n in self.layer_numbers:
            if (n in self.kda_layers) == (n in self.full_attn_layers):
                raise ValueError(
                    f"layer {n} has to be in exactly one of kda_layers "
                    f"and full_attn_layers")
        first, count = self.experts_held
        if not 0 <= first <= first + count <= self.num_experts or not count:
            raise ValueError(
                f"experts_held {self.experts_held} is no range of the "
                f"{self.num_experts} experts")

    @property
    def layer_numbers(self) -> range:
        return range(self.first_layer, self.first_layer + self.num_layers)

    def is_kda(self, number: int) -> bool:
        return number in self.kda_layers

    def is_dense(self, number: int) -> bool:
        return number <= self.first_k_dense_replace

    @property
    def kda_dim(self) -> int:
        return self.kda_num_heads * self.kda_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def routing(self) -> moe_lib.RoutedConfig:
        return moe_lib.RoutedConfig(
            num_experts=self.num_experts, top_k=self.num_experts_per_token,
            scale=self.routed_scaling_factor)


# five layers in the published pattern (KDA+dense, KDA, KDA, MLA, KDA),
# 4 of 16 experts held: the CPU tests' size
KIMI_LINEAR_TINY = KimiLinearConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_layers=5, kda_num_heads=2,
    kda_head_dim=32, kda_gate_rank=16, kda_segment=128, num_heads=2,
    kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, num_experts=16, num_experts_per_token=4,
    experts_held=(4, 4), dtype=jnp.float32, param_dtype=jnp.float32)


# -- parameters -----------------------------------------------------------

def _swiglu_axes(lead=()):
    return {"w_gate": (*lead, "embed", "mlp"), "w_up": (*lead, "embed", "mlp"),
            "w_down": (*lead, "mlp", "embed")}


def _layer_logical_axes(cfg: KimiLinearConfig, number: int) -> Params:
    if cfg.is_kda(number):
        attn = {
            "wq": ("embed", "heads"), "wk": ("embed", "heads"),
            "wv": ("embed", "heads"),
            "conv_q": (None, "heads"), "conv_k": (None, "heads"),
            "conv_v": (None, "heads"),
            "w_fa": ("embed", None), "w_fb": (None, "heads"),
            "a_log": (None,), "dt_bias": ("heads",),
            "w_beta": ("embed", None),
            "w_ga": ("embed", None), "w_gb": (None, "heads"),
            "o_norm": (None,), "wo": ("heads", "embed"),
        }
    else:
        attn = {
            "wq": ("embed", "heads"), "w_kva": ("embed", None),
            "kv_norm": (None,), "w_kvb": (None, "heads"),
            "wo": ("heads", "embed"),
        }
    if cfg.is_dense(number):
        ffn = _swiglu_axes()
    else:
        # the held experts stay whole on a chip: they are this chip's
        # share already, and a grouped product is not GSPMD's to split
        ffn = {"shared": _swiglu_axes(),
               "experts": {"router": ("embed", None),
                           "w_gate": (None, "embed", None),
                           "w_up": (None, "embed", None),
                           "w_down": (None, None, "embed")}}
    return {"attn_norm": ("embed",), "attn": attn,
            "ffn_norm": ("embed",), "ffn": ffn}


def param_logical_axes(cfg: KimiLinearConfig) -> Params:
    return {
        "embed": ("vocab", "embed"),
        "layers": [_layer_logical_axes(cfg, n) for n in cfg.layer_numbers],
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def _init_layer(rng, cfg: KimiLinearConfig, number: int) -> Params:
    keys = iter(jax.random.split(rng, 24))
    pd = cfg.param_dtype
    D = cfg.hidden_size
    # every projection that writes into the residual stream starts at
    # 1 / sqrt(2 x the model's depth) of fan-in scale (the scaled
    # initialisation of deep pre-norm stacks); see `init`
    depth = len(cfg.kda_layers) + len(cfg.full_attn_layers)
    out_scale = (2 * depth) ** -0.5

    def dense(shape, fan_in, scale=1.0):
        return (jax.random.truncated_normal(
            next(keys), -2, 2, shape, jnp.float32)
            * scale * fan_in ** -0.5).astype(pd)

    def swiglu(width, lead=()):
        return {"w_gate": dense((*lead, D, width), D),
                "w_up": dense((*lead, D, width), D),
                "w_down": dense((*lead, width, D), width, out_scale)}

    if cfg.is_kda(number):
        H, dk, C = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_dim
        r, taps = cfg.kda_gate_rank, cfg.short_conv_kernel_size
        # the decay's scale and step as the gated-delta-rule family
        # initialises them: A in [1, 16], softplus(dt_bias) log-uniform
        # in [1e-3, 1e-1]
        a = jax.random.uniform(next(keys), (H,), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(
            next(keys), (C,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        attn = {
            "wq": dense((D, C), D), "wk": dense((D, C), D),
            "wv": dense((D, C), D),
            "conv_q": dense((taps, C), taps), "conv_k": dense((taps, C), taps),
            "conv_v": dense((taps, C), taps),
            "w_fa": dense((D, r), D), "w_fb": dense((r, C), r),
            "a_log": jnp.log(a).astype(pd),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
            "w_beta": dense((D, H), D),
            "w_ga": dense((D, r), D), "w_gb": dense((r, C), r),
            "o_norm": jnp.zeros((dk,), pd),
            "wo": dense((C, D), C, out_scale),
        }
    else:
        n, rank = cfg.num_heads, cfg.kv_lora_rank
        attn = {
            "wq": dense((D, n * cfg.qk_head_dim), D),
            "w_kva": dense((D, rank + cfg.qk_rope_head_dim), D),
            "kv_norm": jnp.zeros((rank,), pd),
            "w_kvb": dense(
                (rank, n * (cfg.qk_nope_head_dim + cfg.v_head_dim)), rank),
            "wo": dense((n * cfg.v_head_dim, D), n * cfg.v_head_dim,
                        out_scale),
        }
    if cfg.is_dense(number):
        ffn = swiglu(cfg.intermediate_size)
    else:
        held = cfg.experts_held[1]
        ffn = {"shared": swiglu(cfg.moe_intermediate_size),
               "experts": {"router": dense((D, cfg.num_experts), D),
                           **swiglu(cfg.moe_intermediate_size, (held,))}}
    return {"attn_norm": jnp.zeros((D,), pd), "attn": attn,
            "ffn_norm": jnp.zeros((D,), pd), "ffn": ffn}


def init(rng: jax.Array, cfg: KimiLinearConfig) -> Params:
    """Truncated-normal weights at fan-in scale, but for two choices
    that keep a token's own embedding the larger part of its residual
    stream at initialisation: the embedding has unit variance, and the
    projections that write into the stream are scaled down by the
    depth (`_init_layer`). With everything at fan-in scale and an
    embedding of D^-1/2, KDA's positive feature maps (silu, then a
    norm) put one vector that all tokens share into every stream, the
    router's scores follow it, and every token of a batch picks the
    same experts (PERF.md section 6, PR 28)."""
    k_embed, k_head = jax.random.split(jax.random.fold_in(rng, 0))
    D, V, pd = cfg.hidden_size, cfg.vocab_size, cfg.param_dtype

    def table(key, shape, scale):
        return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
                * scale).astype(pd)

    return {
        "embed": table(k_embed, (V, D), 1.0),
        "layers": [_init_layer(jax.random.fold_in(rng, n), cfg, n)
                   for n in cfg.layer_numbers],
        "final_norm": jnp.zeros((D,), pd),
        "lm_head": table(k_head, (D, V), D ** -0.5),
    }


def num_params(cfg: KimiLinearConfig) -> int:
    shapes = jax.eval_shape(lambda k: init(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(int(leaf.size) for leaf in jax.tree.leaves(shapes))


# -- the layers -------------------------------------------------------------

def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True) + 1e-6)


def _swiglu(h, p, dtype):
    gate = jax.nn.silu(h @ p["w_gate"].astype(dtype))
    ff = wsc(gate * (h @ p["w_up"].astype(dtype)), ("batch", "seq", "act_mlp"))
    return ff @ p["w_down"].astype(dtype)


def kda_attention(cfg: KimiLinearConfig, h, p):
    """h: the normed input [b, s, D] -> the layer's attention output."""
    b, s, _ = h.shape
    H, dk, dt = cfg.kda_num_heads, cfg.kda_head_dim, cfg.dtype
    f32 = jnp.float32

    def branch(w, taps):
        with jax.named_scope("attn_proj"):
            x = h @ p[w].astype(dt)
        return jax.nn.silu(kda_ops.short_conv(x, p[taps])).reshape(b, s, H, dk)

    q = (_l2norm(branch("wq", "conv_q")) * dk ** -0.5).astype(dt)
    k = _l2norm(branch("wk", "conv_k")).astype(dt)
    v = branch("wv", "conv_v")
    with jax.named_scope("attn_proj"):
        f = ((h @ p["w_fa"].astype(dt)) @ p["w_fb"].astype(dt)).astype(f32)
        log_a = (-jnp.exp(p["a_log"].astype(f32))[:, None]
                 * jax.nn.softplus(f + p["dt_bias"].astype(f32)).reshape(
                     b, s, H, dk))
        beta = jax.nn.sigmoid((h @ p["w_beta"].astype(dt)).astype(f32))
        gate = jax.nn.sigmoid(
            ((h @ p["w_ga"].astype(dt)) @ p["w_gb"].astype(dt)).astype(f32))
    q, k, v = (wsc(x, ("batch", "seq", "act_heads", None)) for x in (q, k, v))
    o, _ = kda_ops.kda(q, k, v, log_a, beta, segment=cfg.kda_segment)
    o = rms_norm(o, p["o_norm"], cfg.norm_eps).reshape(b, s, H * dk)
    with jax.named_scope("attn_proj"):
        return (o * gate.astype(dt)) @ p["wo"].astype(dt)


def mla_attention(cfg: KimiLinearConfig, h, p):
    """Latent attention without positions: nothing is rotated."""
    b, s, _ = h.shape
    n, dt = cfg.num_heads, cfg.dtype
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("attn_proj"):
        q = (h @ p["wq"].astype(dt)).reshape(b, s, n, nope + rope)
        kva = h @ p["w_kva"].astype(dt)
        latent, k_shared = kva[..., :cfg.kv_lora_rank], kva[..., cfg.kv_lora_rank:]
        kv = (rms_norm(latent, p["kv_norm"], cfg.norm_eps)
              @ p["w_kvb"].astype(dt)).reshape(b, s, n, nope + dv)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_shared[:, :, None, :], (b, s, n, rope))], -1)
        v = kv[..., nope:]
        q = wsc(q, ("batch", "seq", "act_heads", None))
        k = wsc(k, ("batch", "seq", "act_heads", None))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    attn = dot_product_attention(q, k, v, positions, positions, causal=True,
                                 impl=cfg.attention_impl,
                                 contiguous_positions=True)
    with jax.named_scope("attn_proj"):
        return attn.reshape(b, s, n * dv) @ p["wo"].astype(dt)


def moe_ffn(cfg: KimiLinearConfig, h, p):
    """-> (the shared expert's output plus the held experts', the held
    experts' loads [held] int32)."""
    b, s, D = h.shape
    with jax.named_scope("shared_expert"):
        shared = _swiglu(h, p["shared"], cfg.dtype)
    routed, load = moe_lib.routed_experts(
        p["experts"], h.reshape(b * s, D), cfg.routing, cfg.experts_held)
    return shared + routed.reshape(b, s, D), load


def _block(cfg: KimiLinearConfig, number: int, x, p):
    """One layer -> (x, its held experts' loads, or None)."""
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    attend = kda_attention if cfg.is_kda(number) else mla_attention
    x = wsc(x + attend(cfg, h, p["attn"]), ("batch", "seq", "act_embed"))
    h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    if cfg.is_dense(number):
        with jax.named_scope("mlp"):
            y, load = _swiglu(h, p["ffn"], cfg.dtype), None
    else:
        y, load = moe_ffn(cfg, h, p["ffn"])
    return wsc(x + y, ("batch", "seq", "act_embed")), load


# One object for all layers: what JAX derives from a jitted call under a
# checkpoint (`ops.kda`'s scans) it caches by the policy's identity, and
# with a policy of its own each layer would derive and lower them again.
_KEEP_THE_SCAN = jax.checkpoint_policies.save_only_these_names(*kda_ops.SAVED)


def _layer(cfg: KimiLinearConfig, number: int):
    """`_block` as a training step runs it: all its backward finds
    kept of its forward is a KDA layer's scan (its output and segment
    states, which that scan's own backward starts from); every other
    value, and every other layer whole, is recomputed."""
    return jax.checkpoint(lambda x, p: _block(cfg, number, x, p),
                          policy=_KEEP_THE_SCAN)


def hidden_and_load(params: Params, cfg: KimiLinearConfig, tokens):
    """tokens [b, s] -> (the final normed hidden [b, s, D] in
    `cfg.dtype`, the held experts' loads [expert layers, held] int32)."""
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype)
        x = wsc(x, ("batch", "seq", "act_embed"))
    loads = []
    for number, p in zip(cfg.layer_numbers, params["layers"], strict=True):
        x, load = _layer(cfg, number)(x, p)
        if load is not None:
            loads.append(load)
    held = cfg.experts_held[1]
    load = jnp.stack(loads) if loads else jnp.zeros((0, held), jnp.int32)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), load


def hidden(params: Params, cfg: KimiLinearConfig, tokens) -> jnp.ndarray:
    return hidden_and_load(params, cfg, tokens)[0]


def unembed_matrix(params: Params, cfg: KimiLinearConfig) -> jnp.ndarray:
    return params["lm_head"]


def apply(params: Params, cfg: KimiLinearConfig, tokens) -> jnp.ndarray:
    """Forward pass -> logits [b, s, vocab] (fp32)."""
    x = hidden(params, cfg, tokens)
    with jax.named_scope("head"):
        logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
        return wsc(logits, ("batch", "seq", "act_vocab"))
