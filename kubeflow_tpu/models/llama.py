"""Llama-3 family, TPU-first.

Functional implementation: parameters are a nested dict pytree with all
transformer blocks *stacked* on a leading "layers" axis so the forward
pass is a single `jax.lax.scan` over layers — one trace/compile of the
block regardless of depth, which keeps XLA compile time flat and lets
`jax.checkpoint` rematerialize per-block (HBM-for-FLOPs trade per
SURVEY.md §2b / pallas guide).

Sharding: every param leaf has logical axes (see `param_logical_axes`);
the FSDP/TP layout comes from kubeflow_tpu.parallel.sharding rules, not
from the model code.

Reference parity note: the reference control plane launches notebooks that
*run* models but contains none (SURVEY.md §2b). This module provides the
flagship model for BASELINE.json config "Llama-3-8B FSDP via
jax.distributed on v5e-16".
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops.attention import dot_product_attention
from kubeflow_tpu.ops.embedding import embed_lookup
from jax.ad_checkpoint import checkpoint_name

from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.rotary import apply_rope, rope_frequencies
from kubeflow_tpu.parallel.sharding import with_sharding_constraint as wsc

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # Sliding-window attention (Mistral-style): each position attends
    # its last `sliding_window` tokens. None = full causal. Applied to
    # every layer; both the XLA and Pallas paths honor it, and the
    # flash kernel skips out-of-band blocks entirely.
    sliding_window: int | None = None
    dtype: Any = jnp.bfloat16      # activation dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    # What the per-block jax.checkpoint keeps (HBM) vs recomputes (FLOPs):
    #   "full" — keep only block boundaries; bwd reruns the whole block
    #            fwd (~+2N matmul FLOPs, the classic 8N/6N = 33% tax).
    #   "mlp"  — additionally keep the three MLP matmul outputs
    #            (gate/up/down — 82% of a block's matmul FLOPs at Llama
    #            shapes) so bwd only reruns the attention side.
    #   "dots" — keep every matmul output (jax dots_with_no_batch_dims
    #            policy); bwd reruns just elementwise + the flash kernel.
    # Picked per preset by HBM headroom: chunked CE (train.trainer) freed
    # the logit tensor, which is what makes "mlp"/"dots" fit on one chip.
    remat_policy: str = "full"

    def __post_init__(self):
        if self.remat_policy not in _REMAT_POLICIES:
            raise ValueError(
                f"remat_policy {self.remat_policy!r} unknown "
                f"(choose from {sorted(_REMAT_POLICIES)})")

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


# Remat save-policies, keyed by LlamaConfig.remat_policy (factories so
# import never touches jax state).
_REMAT_POLICIES = {
    "full": lambda: None,
    "mlp": lambda: jax.checkpoint_policies.save_only_these_names(
        "mlp_gate", "mlp_up", "mlp_down"),
    "dots": lambda: jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


# BASELINE.json flagship + scaled-down siblings for single-chip benches and
# CPU tests. Sizes follow the Llama-3 family shape recipe.
LLAMA3_8B = LlamaConfig()
LLAMA3_1B = LlamaConfig(
    hidden_size=2048, intermediate_size=8192, num_layers=16,
    num_heads=16, num_kv_heads=8, head_dim=128,
)
LLAMA_TINY = LlamaConfig(
    vocab_size=512, hidden_size=128, intermediate_size=384, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=32, dtype=jnp.float32, remat=False,
)

CONFIGS = {"llama3-8b": LLAMA3_8B, "llama3-1b": LLAMA3_1B, "tiny": LLAMA_TINY}


def param_logical_axes(cfg: LlamaConfig) -> Params:
    """Logical axis names per param leaf (layers axis leads block params)."""
    block = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),     # [L, D, n_q * hd]
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    axes: Params = {
        "embed": ("vocab", "embed"),
        "blocks": block,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init(rng: jax.Array, cfg: LlamaConfig) -> Params:
    """Initialize params (truncated-normal fan-in scaling)."""
    keys = iter(jax.random.split(rng, 16))
    pd = cfg.param_dtype

    def dense(key, shape, fan_in):
        return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(pd)

    L, D = cfg.num_layers, cfg.hidden_size
    params: Params = {
        "embed": dense(next(keys), (cfg.vocab_size, D), D),
        "blocks": {
            "attn_norm": jnp.zeros((L, D), pd),
            "wq": dense(next(keys), (L, D, cfg.q_dim), D),
            "wk": dense(next(keys), (L, D, cfg.kv_dim), D),
            "wv": dense(next(keys), (L, D, cfg.kv_dim), D),
            "wo": dense(next(keys), (L, cfg.q_dim, D), cfg.q_dim),
            "mlp_norm": jnp.zeros((L, D), pd),
            "w_gate": dense(next(keys), (L, D, cfg.intermediate_size), D),
            "w_up": dense(next(keys), (L, D, cfg.intermediate_size), D),
            "w_down": dense(next(keys), (L, cfg.intermediate_size, D),
                            cfg.intermediate_size),
        },
        "final_norm": jnp.zeros((D,), pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(keys), (D, cfg.vocab_size), D)
    return params


def _attention_half(cfg, x, p, positions, inv_freq, kv_mask,
                    contiguous_positions=False):
    """Attention sub-block + residual (shared by the dense, pipelined,
    and MoE models — cfg needs the llama attention attrs only)."""
    b, s, D = x.shape
    # the parts carry the serving block's scope names
    # (serving/engine.py `transformer_block`); `rms_norm` and the
    # attention call open their own
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    with jax.named_scope("attn_proj"):
        q = (h @ p["wq"].astype(cfg.dtype)).reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = (h @ p["wk"].astype(cfg.dtype)).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        v = (h @ p["wv"].astype(cfg.dtype)).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        q = wsc(q, ("batch", "seq", "act_heads", None))
        k = wsc(k, ("batch", "seq", "act_kv_heads", None))
    attn = dot_product_attention(q, k, v, positions, positions,
                                 causal=True, kv_mask=kv_mask,
                                 window=cfg.sliding_window,
                                 contiguous_positions=contiguous_positions)
    with jax.named_scope("attn_proj"):
        attn = attn.reshape(b, s, cfg.q_dim)
        x = x + attn @ p["wo"].astype(cfg.dtype)
        return wsc(x, ("batch", "seq", "act_embed"))


def _block(cfg: LlamaConfig, x, layer_params, positions, inv_freq, kv_mask,
           contiguous_positions=False):
    """One transformer block. x: [b, s, D] in cfg.dtype."""
    p = layer_params
    x = _attention_half(cfg, x, p, positions, inv_freq, kv_mask,
                        contiguous_positions)

    h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    # checkpoint_name is inert unless cfg.remat_policy == "mlp" selects
    # these tensors as the save set (see _REMAT_POLICIES).
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(
            checkpoint_name(h @ p["w_gate"].astype(cfg.dtype), "mlp_gate"))
        up = checkpoint_name(h @ p["w_up"].astype(cfg.dtype), "mlp_up")
        ff = wsc(gate * up, ("batch", "seq", "act_mlp"))
        x = x + checkpoint_name(ff @ p["w_down"].astype(cfg.dtype),
                                "mlp_down")
        return wsc(x, ("batch", "seq", "act_embed"))


# Mesh-aware lookup (gather on trivial meshes, one-hot MXU contraction
# under sharding) now lives in ops.embedding — serving shares it.
_embed_lookup = embed_lookup


def hidden(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,              # [b, s] int32
    positions: jnp.ndarray | None = None,
    kv_mask: jnp.ndarray | None = None,  # [b, s] bool, False = padding
) -> jnp.ndarray:
    """Forward pass through the blocks → final NORMED hidden [b, s, D]
    in cfg.dtype. Callers that don't need full logits (the chunked-CE
    training loss) stop here; `apply` adds the unembedding."""
    b, s = tokens.shape
    contiguous = positions is None  # safe to use index-masked flash kernel
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    inv_freq = rope_frequencies(cfg.head_dim, theta=cfg.rope_theta)

    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
        x = wsc(x, ("batch", "seq", "act_embed"))

    block_fn = lambda x, lp: (
        _block(cfg, x, lp, positions, inv_freq, kv_mask,
               contiguous_positions=contiguous), None)
    if cfg.remat:
        block_fn = jax.checkpoint(
            block_fn, policy=_REMAT_POLICIES[cfg.remat_policy]())
    x, _ = jax.lax.scan(block_fn, x, params["blocks"])

    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def unembed_matrix(params: Params, cfg: LlamaConfig) -> jnp.ndarray:
    """[D, vocab] unembedding (the tied table transposed, or lm_head)."""
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def apply(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,              # [b, s] int32
    positions: jnp.ndarray | None = None,
    kv_mask: jnp.ndarray | None = None,  # [b, s] bool, False = padding
) -> jnp.ndarray:
    """Forward pass → logits [b, s, vocab] (fp32)."""
    x = hidden(params, cfg, tokens, positions, kv_mask)
    with jax.named_scope("head"):
        head = unembed_matrix(params, cfg)
        logits = x.astype(jnp.float32) @ head.astype(jnp.float32)
        return wsc(logits, ("batch", "seq", "act_vocab"))


def num_params(cfg: LlamaConfig) -> int:
    shapes = jax.eval_shape(lambda k: init(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(int(jnp.prod(jnp.array(l.shape))) for l in jax.tree.leaves(shapes))
