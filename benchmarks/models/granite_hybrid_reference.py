"""Plain reference for the `granite_hybrid` model class
(granite-4.0-h-micro, `model_type` granitemoehybrid with no experts):
pre-norm blocks whose first half is either Mamba-2 or grouped-query
attention without any positional encoding, each followed by the shared
SwiGLU, with the four fixed multipliers of the Granite families.

    h = embedding_multiplier * E[tok]
    h = h + residual_multiplier * mix(RMSNorm(h))         (every layer)
    h = h + residual_multiplier * W_down(silu(W_gate n) * W_up n),
        n = RMSNorm(h)
    logits = RMSNorm(h) E^T / logits_scaling

    attention: softmax over the causal q k^T * attention_multiplier
        (1/64 here, not head_dim ** -0.5), no rotary, no bias
    mamba:  [z, xBC, dt] = W_in u;  xBC = silu(conv1d(xBC)) (depthwise,
        causal, kernel 4, bias);  [x, B, C] = xBC
        dt = softplus(dt + dt_bias);  a_t = exp(dt_t * A),  A = -exp(A_log)
        S_t = a_t S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t
        out = W_out RMSNorm(y * silu(z))       (gate before the norm,
        one norm over the whole inner width: n_groups is 1)

Straight `jax.numpy` in float32 under
`default_matmul_precision("highest")`, one sequence at a time, nothing
imported from the program: the recurrence is the line above, one
`lax.scan` step a token, from a zero state — no chunks, no carried
state, no cache; attention is a masked softmax over the whole
sequence. Parameters arrive in the program's layout and dtype and are
upcast a layer at a time, so the reference fits beside the replica.

Departures from `transformers`' GraniteMoeHybrid, all of them the
program's storage conventions and none its mathematics:

- an RMSNorm scale is stored minus one (zero-initialised), so the scale
  applied is `1 + w` (the layer norms, the final norm, the gated norm);
- projections are stored `[in, out]`; `in_proj` is stored as its three
  column blocks `w_z`, `w_xbc`, `w_dt` and `input_linear` as its halves
  `w_gate`, `w_up`; the convolution's taps are `[4, channels]` with tap
  3 on the current token;
- the layers are stacked by kind, the attention layers under `blocks`
  and the Mamba layers under `mamba_blocks`, each in model order;
- `time_step_limit` is (0, inf), the published default: dt is not
  clamped (the configuration file's `assumed`).

`state_dtype` exists for one reading: what the model gives when the
recurrence (the state, the decay, the outer product and the read-out)
is computed in a lower precision than the configuration states (PERF.md
section 6). The benchmark never passes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _mlp(x, lp, eps, residual):
    h = _rms_norm(x, lp["mlp_norm"], eps)
    ff = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
    return x + residual * (ff @ lp["w_down"])


def _upcast(lp):
    return jax.tree.map(lambda a: a.astype(jnp.float32), lp)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "hd", "scale", "eps", "residual"))
def _attention_layer(x, lp, *, n_heads, n_kv, hd, scale, eps, residual):
    """x: [t, hidden] float32; `lp` one attention layer's parameters."""
    lp = _upcast(lp)
    t = x.shape[0]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = (h @ lp["wq"]).reshape(t, n_heads, hd)
    k = jnp.repeat((h @ lp["wk"]).reshape(t, n_kv, hd), n_heads // n_kv, 1)
    v = jnp.repeat((h @ lp["wv"]).reshape(t, n_kv, hd), n_heads // n_kv, 1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(visible[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + residual * (attn.reshape(t, n_heads * hd) @ lp["wo"])
    return _mlp(x, lp, eps, residual)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "hd", "n_groups", "n_state", "eps", "residual",
    "state_dtype"))
def _mamba_layer(x, lp, *, n_heads, hd, n_groups, n_state, eps, residual,
                 state_dtype):
    lp = _upcast(lp)
    t = x.shape[0]
    d_inner = n_heads * hd
    u = _rms_norm(x, lp["ssm_norm"], eps)
    z, xbc, dt = u @ lp["w_z"], u @ lp["w_xbc"], u @ lp["w_dt"]
    taps = lp["conv_w"]                              # [K, channels]
    k = taps.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, xbc.shape[1]), jnp.float32), xbc])
    xbc = jax.nn.silu(lp["conv_b"] + sum(
        padded[j:j + t] * taps[j] for j in range(k)))
    xs = xbc[:, :d_inner].reshape(t, n_heads, hd)
    bs = xbc[:, d_inner:d_inner + n_groups * n_state].reshape(
        t, n_groups, n_state)
    cs = xbc[:, d_inner + n_groups * n_state:].reshape(
        t, n_groups, n_state)
    bs = jnp.repeat(bs, n_heads // n_groups, axis=1)  # [t, H, N]
    cs = jnp.repeat(cs, n_heads // n_groups, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])          # [t, H]
    decay = jnp.exp(dt * -jnp.exp(lp["A_log"]))

    def step(S, tok):
        a, d, x_t, b_t, c_t = (v.astype(state_dtype) for v in tok)
        S = (a[:, None, None] * S
             + (d[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return S, jnp.sum(S * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((n_heads, hd, n_state), state_dtype),
        (decay, dt, xs, bs, cs))
    y = y.astype(jnp.float32) + lp["D"][None, :, None] * xs
    y = _rms_norm(y.reshape(t, d_inner) * jax.nn.silu(z),
                  lp["gate_norm"], eps)
    x = x + residual * (y @ lp["w_out"])
    return _mlp(x, lp, eps, residual)


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(x, final_norm, embed, *, eps, scaling):
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    return jax.nn.log_softmax(
        x @ embed.astype(jnp.float32).T / scaling, axis=-1)


def logprobs(config: dict, params, tokens, *,
             state_dtype=jnp.float32) -> jax.Array:
    """Log-probabilities [t, vocab] of the next token after each of the
    `t` positions of one sequence."""
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference computes SwiGLU (silu) only")
    if not config["tie_word_embeddings"]:
        raise ValueError("the reference's head is the tied table")
    eps = float(config["rms_norm_eps"])
    residual = float(config["residual_multiplier"])
    tokens = jnp.asarray(tokens, jnp.int32)
    seen = {"attention": 0, "mamba": 0}
    with jax.default_matmul_precision("highest"):
        x = (params["embed"][tokens].astype(jnp.float32)
             * float(config["embedding_multiplier"]))
        for kind in config["layer_types"]:
            stack = params["blocks" if kind == "attention"
                           else "mamba_blocks"]
            lp = jax.tree.map(lambda a, i=seen[kind]: a[i], stack)
            seen[kind] += 1
            if kind == "attention":
                x = _attention_layer(
                    x, lp, n_heads=config["num_attention_heads"],
                    n_kv=config["num_key_value_heads"],
                    hd=config["head_dim"],
                    scale=float(config["attention_multiplier"]),
                    eps=eps, residual=residual)
            else:
                x = _mamba_layer(
                    x, lp, n_heads=config["mamba_n_heads"],
                    hd=config["mamba_d_head"],
                    n_groups=config["mamba_n_groups"],
                    n_state=config["mamba_d_state"], eps=eps,
                    residual=residual, state_dtype=state_dtype)
        return _head(x, params["final_norm"], params["embed"], eps=eps,
                     scaling=float(config["logits_scaling"]))


def token_logprobs(config: dict, params, tokens, targets, *,
                   state_dtype=jnp.float32) -> jax.Array:
    """[t]: the reference's log-probability of `targets[i]` after
    `tokens[:i + 1]`."""
    lp = logprobs(config, params, tokens, state_dtype=state_dtype)
    targets = jnp.asarray(targets, jnp.int32)
    return jnp.take_along_axis(lp, targets[:, None], axis=-1)[:, 0]
