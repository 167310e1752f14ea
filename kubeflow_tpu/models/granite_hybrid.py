"""Granite-4.0-H (`model_type` granitemoehybrid without experts): Mamba-2
blocks with a position-free GQA block every tenth layer, a shared
SwiGLU after each, and four fixed multipliers (embedding, residual,
attention, logits).

Serving only: the forward pass lives in `serving/engine.py`
(`transformer_block` for the attention layers, `mamba_block` for the
rest, `scan_layers` over both) and runs under `ContinuousBatcher`,
which keeps a recurrent state per slot beside the paged KV pool. This
module holds the sizes, the parameters and their initialisation.

Parameters are stacked BY KIND, so that the layer loop is a scan over
the periods of `layer_types` and not one unrolled block a layer:
`blocks` holds the attention layers (`transformer_block`'s schema,
`[n_attention, ...]`), `mamba_blocks` the Mamba-2 layers
(`[n_mamba, ...]`), each in model order.

Against the published checkpoint's layout: the input projection is
three matrices (`w_z`, `w_xbc`, `w_dt`) where `in_proj` is their
concatenation, `w_gate`/`w_up` are the halves of `input_linear`, and
every norm weight is stored less one (`ops.norms.rms_norm` scales by
1 + w). The mathematics is the same.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192      # shared_intermediate_size
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    layer_types: tuple[str, ...] = _PERIOD * 4
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16          # activations
    param_dtype: Any = jnp.bfloat16
    # what a slot's conv tail and SSM state are STORED in between
    # dispatches (the update itself is float32): the model's dtype, as
    # the published implementation's cache allocates them
    state_dtype: Any = None

    def __post_init__(self):
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}; a layer "
                             "is 'mamba' or 'attention'")
        if self.state_dtype is None:
            object.__setattr__(self, "state_dtype", self.dtype)

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def num_attention_layers(self) -> int:
        return self.layer_types.count("attention")

    @property
    def num_mamba_layers(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels of the short convolution: x, B and C."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state


GRANITE_4_0_H_MICRO = GraniteHybridConfig()
# one period is enough to hold both kinds and both orders of them
GRANITE_HYBRID_TINY = GraniteHybridConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_heads=4,
    num_kv_heads=2, head_dim=16,
    layer_types=("mamba", "mamba", "attention", "mamba"),
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
    dtype=jnp.float32, param_dtype=jnp.float32)

CONFIGS = {"granite-4.0-h-micro": GRANITE_4_0_H_MICRO,
           "tiny": GRANITE_HYBRID_TINY}


def _mlp_shapes(cfg) -> dict[str, tuple[tuple[int, ...], int]]:
    d, i = cfg.hidden_size, cfg.intermediate_size
    return {"w_gate": ((d, i), d), "w_up": ((d, i), d),
            "w_down": ((i, d), i)}


def matmul_shapes(cfg: GraniteHybridConfig):
    """-> (attention block, mamba block): name -> (shape, fan-in) of
    every matrix of one layer."""
    d = cfg.hidden_size
    attention = {"wq": ((d, cfg.q_dim), d), "wk": ((d, cfg.kv_dim), d),
                 "wv": ((d, cfg.kv_dim), d), "wo": ((cfg.q_dim, d), cfg.q_dim),
                 **_mlp_shapes(cfg)}
    mamba = {"w_z": ((d, cfg.d_inner), d), "w_xbc": ((d, cfg.conv_dim), d),
             "w_dt": ((d, cfg.mamba_n_heads), d),
             "w_out": ((cfg.d_inner, d), cfg.d_inner), **_mlp_shapes(cfg)}
    return attention, mamba


def init(rng: jax.Array, cfg: GraniteHybridConfig) -> Params:
    """Truncated-normal fan-in scaling for the matrices, as
    `models/llama.py`. The recurrence's own parameters as the published
    Mamba-2 code draws them: A uniform in [1, 16] a head (`A_log` its
    logarithm), the time step log-uniform in [1e-3, 1e-1] (`dt_bias`
    its inverse softplus), D ones; a normal draw makes the state vanish
    or blow up. The convolution as a depthwise Conv1d's default
    (uniform within 1 / sqrt(K))."""
    keys = iter(jax.random.split(rng, 32))
    pd = cfg.param_dtype
    la, lm = cfg.num_attention_layers, cfg.num_mamba_layers
    h, k = cfg.mamba_n_heads, cfg.mamba_d_conv

    def dense(shape, fan_in):
        return (jax.random.truncated_normal(next(keys), -2, 2, shape,
                                            jnp.float32)
                * fan_in ** -0.5).astype(pd)

    def stack(n, shapes):
        return {name: dense((n,) + shape, fan_in)
                for name, (shape, fan_in) in shapes.items()}

    def zeros(*shape):
        return jnp.zeros(shape, pd)

    attention, mamba = matmul_shapes(cfg)
    bound = k ** -0.5
    dt = jnp.exp(jax.random.uniform(
        next(keys), (lm, h), jnp.float32, math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "embed": dense((cfg.vocab_size, cfg.hidden_size), cfg.hidden_size),
        "blocks": {
            **stack(la, attention),
            "attn_norm": zeros(la, cfg.hidden_size),
            "mlp_norm": zeros(la, cfg.hidden_size),
        },
        "mamba_blocks": {
            **stack(lm, mamba),
            "ssm_norm": zeros(lm, cfg.hidden_size),
            "mlp_norm": zeros(lm, cfg.hidden_size),
            "gate_norm": zeros(lm, cfg.d_inner),
            "conv_w": jax.random.uniform(
                next(keys), (lm, k, cfg.conv_dim), jnp.float32,
                -bound, bound).astype(pd),
            "conv_b": jax.random.uniform(
                next(keys), (lm, cfg.conv_dim), jnp.float32,
                -bound, bound).astype(pd),
            # the three a head stay float32: they feed exponentials
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (lm, h), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((lm, h), jnp.float32),
        },
        "final_norm": zeros(cfg.hidden_size),
    }


def num_params(cfg: GraniteHybridConfig) -> int:
    """From the shapes `init` makes, without making them."""
    shapes = jax.eval_shape(lambda k: init(k, cfg),
                            jax.ShapeDtypeStruct((), jax.random.key(0).dtype))
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes))
