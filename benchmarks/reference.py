"""Plain reference for the `llama` model class (Mistral-7B and its
kin): pre-norm decoder, grouped-query attention with rotary positions,
SwiGLU feed-forward, untied head. Straight `jax.numpy` in float32
under `default_matmul_precision("highest")`: one sequence at a time, no
kernel, no cache, no batching, and nothing imported from the program.
Parameters arrive in the program's layout and dtype and are upcast one
layer at a time, so the reference never holds a second copy of the
model.

Departures from the published description, both the program's storage
conventions and not its mathematics:

- an RMSNorm scale is stored minus one (zero-initialised), so the
  scale applied is `1 + w`;
- the projections are stored `[in, out]` and the blocks stacked on a
  leading layer axis.

Rotary positions use the half-split pairing (`rotate_half`), as the
published Hugging Face implementation does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: [t, heads, hd] at positions 0..t-1."""
    t, _, hd = x.shape
    half = hd // 2
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "hd", "theta", "eps", "window"))
def _layer(x, lp, *, n_heads, n_kv, hd, theta, eps, window):
    """One block on x: [t, hidden] float32; `lp` is the layer's slice of
    the stacked parameters, upcast here."""
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    t = x.shape[0]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = _rope((h @ lp["wq"]).reshape(t, n_heads, hd), theta)
    k = _rope((h @ lp["wk"]).reshape(t, n_kv, hd), theta)
    v = (h @ lp["wv"]).reshape(t, n_kv, hd)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    visible = j <= i
    if window is not None:
        visible &= j > i - window
    scores = jnp.where(visible[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(t, n_heads * hd) @ lp["wo"]
    h = _rms_norm(x, lp["mlp_norm"], eps)
    ff = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
    return x + ff @ lp["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    return jax.nn.log_softmax(x @ head.astype(jnp.float32), axis=-1)


def logprobs(config: dict, params, tokens) -> jax.Array:
    """Log-probabilities [t, vocab] of the next token after each of the
    `t` positions of one sequence."""
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the llama reference computes SwiGLU (silu) only")
    static = dict(
        n_heads=config["num_attention_heads"],
        n_kv=config["num_key_value_heads"], hd=config["head_dim"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        window=config.get("sliding_window"))
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for layer in range(config["num_hidden_layers"]):
            lp = jax.tree.map(lambda a, layer=layer: a[layer],
                              params["blocks"])
            x = _layer(x, lp, **static)
        head = (params["embed"].T if config["tie_word_embeddings"]
                else params["lm_head"])
        return _head(x, params["final_norm"], head, eps=static["eps"])


def token_logprobs(config: dict, params, tokens, targets) -> jax.Array:
    """[t]: the reference's log-probability of `targets[i]` after
    `tokens[:i + 1]`."""
    lp = logprobs(config, params, tokens)
    targets = jnp.asarray(targets, jnp.int32)
    return jnp.take_along_axis(lp, targets[:, None], axis=-1)[:, 0]


def loss(config: dict, params, batch, targets) -> float:
    """Mean next-token cross-entropy over every position of every
    sequence of `batch` [b, t], one sequence at a time."""
    total, count = 0.0, 0
    for seq, tgt in zip(batch, targets):
        lp = token_logprobs(config, params, seq, tgt)
        total += float(-jnp.sum(lp))
        count += int(lp.shape[0])
    return total / count
