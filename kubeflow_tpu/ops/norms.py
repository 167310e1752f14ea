"""Normalization ops."""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("norm")
def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-6) -> jnp.ndarray:
    """LayerNorm (mean-centered) in fp32 accumulation, cast back.

    ViT-style: weight multiplies, bias adds; ones/zeros init is identity.
    """
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


@jax.named_scope("norm")
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm in fp32 accumulation, cast back to input dtype. Opens
    the scope `norm`, by which a device trace finds it.

    XLA fuses this into neighboring ops; no kernel needed. Computed in
    float32 regardless of activation dtype (bf16-safe). Uses the Llama
    convention of a (1 + w) scale so zero-init weights are identity.
    """
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(dtype)
