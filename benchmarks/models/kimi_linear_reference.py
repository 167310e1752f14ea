"""Plain reference for the `kimi_linear` model class
(Kimi-Linear-48B-A3B): pre-norm blocks whose attention is either KDA
(Kimi Delta Attention: a gated delta rule with a decay per channel) or
latent attention without positions (MLA, `mla_use_nope`), and whose
feed-forward is a dense SwiGLU in the leading layer and one shared
expert plus the routed experts this chip holds in every later one.

Straight `jax.numpy` in float32 under
`default_matmul_precision("highest")`, one sequence at a time, nothing
imported from the program: KDA is the recurrence itself, one
`lax.scan` step a token; MLA a masked softmax a block of query rows at
a time (a whole `[32, 8192, 8192]` float32 score tensor is 8.6 GB); the
expert layer a loop over the held experts, each applied to every token
and weighted by the router (zero where the token did not choose it).
Parameters arrive in the program's layout and dtype and are upcast a
layer at a time.

Departures from the published description, all of them the program's
storage conventions and none its mathematics:

- an RMSNorm scale is stored minus one (zero-initialised), so the scale
  applied is `1 + w` (the layer norms, the latent norm, KDA's per-head
  output norm);
- projections are stored `[in, out]`, the short convolution's taps
  `[4, channels]` with tap 3 on the current token, the routed experts
  stacked `[held, in, out]`;
- the layers are a list (layer 1 first), each a dict of its own kind's
  parameters;
- the router's selection bias is not stored: it is zero here
  (the configuration file's `assumed`).

`state_dtype` exists for one reading: what the model gives when KDA's
state and its gates are kept in a lower precision than the
configuration states (PERF.md section 6). The benchmark never passes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_ROWS = 1024          # MLA scores are computed this many rows at a time


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def short_conv(x, taps):
    """Depthwise causal convolution over time. x: [t, c]; taps: [n, c];
    y[t] = sum_j taps[j] * x[t - (n - 1) + j], zeros before the start."""
    n = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((n - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j] * padded[j:j + x.shape[0]] for j in range(n))


def kda_recurrence(q, k, v, log_a, beta, state_dtype=jnp.float32):
    """The gated delta rule, a token a step. q, k, log_a: [t, h, dk];
    v: [t, h, dv]; beta: [t, h]. -> (o [t, h, dv], S [h, dk, dv]).

        S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
    """
    h, dk = q.shape[1:]
    dv = v.shape[-1]

    def step(s, x):
        q_t, k_t, v_t, la_t, b_t = x
        s = jnp.exp(la_t)[..., None] * s.astype(jnp.float32)
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = (s + k_t[..., None] * u[:, None, :]).astype(state_dtype)
        return s, jnp.einsum("hkv,hk->hv", s.astype(jnp.float32), q_t)

    s0 = jnp.zeros((h, dk, dv), state_dtype)
    s, o = jax.lax.scan(step, s0, (q, k, v, log_a, beta))
    return o, s.astype(jnp.float32)


def kda_gates(x, p, n_heads, dk, state_dtype=jnp.float32):
    """-> (log_a [t, h, dk], beta [t, h]) of a KDA layer on its normed
    input x [t, hidden]."""
    t = x.shape[0]
    f = (x @ p["w_fa"]) @ p["w_fb"] + p["dt_bias"]
    log_a = (-jnp.exp(p["a_log"])[None, :, None]
             * jax.nn.softplus(f).reshape(t, n_heads, dk))
    beta = jax.nn.sigmoid(x @ p["w_beta"])
    return (log_a.astype(state_dtype).astype(jnp.float32),
            beta.astype(state_dtype).astype(jnp.float32))


def kda_attention(x, p, *, n_heads, dk, dv, eps, state_dtype=jnp.float32):
    t = x.shape[0]

    def branch(w, taps):
        return jax.nn.silu(short_conv(x @ w, taps))

    q = _l2norm(branch(p["wq"], p["conv_q"]).reshape(t, n_heads, dk))
    k = _l2norm(branch(p["wk"], p["conv_k"]).reshape(t, n_heads, dk))
    v = branch(p["wv"], p["conv_v"]).reshape(t, n_heads, dv)
    log_a, beta = kda_gates(x, p, n_heads, dk, state_dtype)
    o, _ = kda_recurrence(q * dk ** -0.5, k, v, log_a, beta, state_dtype)
    gate = jax.nn.sigmoid((x @ p["w_ga"]) @ p["w_gb"])
    o = _rms_norm(o, p["o_norm"], eps).reshape(t, n_heads * dv) * gate
    return o @ p["wo"]


def mla_attention(x, p, *, n_heads, nope, rope, dv, rank, eps):
    """Latent attention without positions: nothing is rotated."""
    t = x.shape[0]
    q = (x @ p["wq"]).reshape(t, n_heads, nope + rope)
    kva = x @ p["w_kva"]
    c, k_r = kva[:, :rank], kva[:, rank:]
    kv = (_rms_norm(c, p["kv_norm"], eps) @ p["w_kvb"]).reshape(
        t, n_heads, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_r[:, None, :], (t, n_heads, rope))], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
    cols = jnp.arange(t)[None, :]
    out = []
    for start in range(0, t, QUERY_ROWS):
        rows = jnp.arange(start, min(start + QUERY_ROWS, t))[:, None]
        scores = jnp.einsum("qhd,khd->hqk", q[rows[:, 0]], k) * scale
        scores = jnp.where((cols <= rows)[None], scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd",
                              jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out).reshape(t, n_heads * dv) @ p["wo"]


def router_weights(h, router, *, top_k, scale):
    """[t, experts]: the weight each expert's output gets for each
    token, zero for the experts the token did not choose. Sigmoid
    scores, the `top_k` largest (the selection bias is zero), their
    scores renormalised to sum to one, times `scale`."""
    s = jax.nn.sigmoid(h @ router)
    top, idx = jax.lax.top_k(s, top_k)
    w = scale * top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(w)


def routed_experts(h, p, *, held, top_k, scale):
    """What the experts `held = (first, count)` add: a loop over them.
    What the absent experts would add is left out."""
    first, count = held
    w = router_weights(h, p["router"], top_k=top_k, scale=scale)
    y = jnp.zeros_like(h)
    for e in range(count):
        y += w[:, first + e, None] * _swiglu(
            h, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return y


def moe_ffn(h, p, *, held, top_k, scale):
    return (_swiglu(h, *(p["shared"][n] for n in ("w_gate", "w_up", "w_down")))
            + routed_experts(h, p["experts"], held=held, top_k=top_k,
                             scale=scale))


def static_config(c: dict) -> dict:
    """The sizes a layer needs, from the configuration file's keys."""
    lin = c["linear_attn_config"]
    first = c.get("first_layer", 1)
    return dict(
        n_layers=c["num_hidden_layers"], first_layer=first,
        kda_layers=tuple(lin["kda_layers"]),
        dense_layers=c["first_k_dense_replace"],
        kda=dict(n_heads=lin["num_heads"], dk=lin["head_dim"],
                 dv=lin["head_dim"], eps=float(c["rms_norm_eps"])),
        mla=dict(n_heads=c["num_attention_heads"],
                 nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                 dv=c["v_head_dim"], rank=c["kv_lora_rank"],
                 eps=float(c["rms_norm_eps"])),
        moe=dict(held=tuple(c["experts_held"]),
                 top_k=c["num_experts_per_token"],
                 scale=float(c["routed_scaling_factor"])),
        eps=float(c["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=(
    "is_kda", "is_dense", "kda", "mla", "moe", "eps", "state_dtype"))
def _layer(x, lp, *, is_kda, is_dense, kda, mla, moe, eps,
           state_dtype=jnp.float32):
    """One block on x: [t, hidden] float32. `kda`, `mla` and `moe` are
    tuples of (name, value) pairs so that they hash."""
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    h = _rms_norm(x, lp["attn_norm"], eps)
    if is_kda:
        x = x + kda_attention(h, lp["attn"], **dict(kda),
                              state_dtype=state_dtype)
    else:
        x = x + mla_attention(h, lp["attn"], **dict(mla))
    h = _rms_norm(x, lp["ffn_norm"], eps)
    if is_dense:
        return x + _swiglu(h, *(lp["ffn"][n]
                                for n in ("w_gate", "w_up", "w_down")))
    return x + moe_ffn(h, lp["ffn"], **dict(moe))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    return jax.nn.log_softmax(x @ head.astype(jnp.float32), axis=-1)


def layer_outputs(config: dict, params, tokens, state_dtype=jnp.float32):
    """The residual stream [t, hidden] after each layer of one
    sequence, embedding first."""
    s = static_config(config)
    outs = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        outs.append(x)
        for i, lp in enumerate(params["layers"]):
            number = s["first_layer"] + i
            x = _layer(
                x, lp, is_kda=number in s["kda_layers"],
                is_dense=number <= s["dense_layers"],
                kda=tuple(s["kda"].items()), mla=tuple(s["mla"].items()),
                moe=tuple(s["moe"].items()), eps=s["eps"],
                state_dtype=state_dtype)
            outs.append(x)
    return outs


def logprobs(config: dict, params, tokens, state_dtype=jnp.float32):
    """Log-probabilities [t, vocab] of the next token after each of the
    `t` positions of one sequence."""
    x = layer_outputs(config, params, tokens, state_dtype)[-1]
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["lm_head"],
                     eps=float(config["rms_norm_eps"]))


def token_logprobs(config: dict, params, tokens, targets,
                   state_dtype=jnp.float32):
    """[t]: the reference's log-probability of `targets[i]` after
    `tokens[:i + 1]`."""
    lp = logprobs(config, params, tokens, state_dtype)
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
