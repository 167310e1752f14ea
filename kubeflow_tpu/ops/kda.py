"""KDA (Kimi Delta Attention): the gated delta rule with a decay per
channel, in its chunked (WY) form, and the short causal convolution
that feeds it.

Per head, with state S in R^{dk x dv} (S_0 = 0):

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

A token a step that is a scan of `t` rank-one updates. In chunks of C
tokens it is matrix products: with G_t the cumulative log-decay inside
a chunk and u_t = beta_t (v_t - (Diag(a_t) S_{t-1})^T k_t) the value the
delta rule really writes,

    (I + A) U = beta * (V - (K * e^G) S_0),
        A_ti = beta_t sum_c k_tc k_ic e^(G_tc - G_ic),  i < t
    O       = (Q * e^G) S_0 + B U,
        B_ti = sum_c q_tc k_ic e^(G_tc - G_ic),        i <= t
    S_C     = Diag(e^G_C) S_0 + (K * e^(G_C - G))^T U

so only the three products with S_0 are sequential (a scan over
chunks); the triangular system is solved for every chunk at once, by
forward substitution inside 16 x 16 diagonal blocks and block by block
across them.

**No positive exponent.** e^(G_t - G_i) is never split into e^G_t and
e^-G_i: a channel that decays by e^-100 inside a chunk would overflow
the second. A and B are built a block of 16 rows at a time: against
earlier blocks as a product of two factors taken from the block's
first token, e^(G_t - R_n) and e^(R_n - G_i), both exponents <= 0; in
the diagonal block directly from the masked differences.

**Memory.** The diagonal blocks' e^(G_t - G_i) is a `[16, 16, dk]`
tensor a block of 16 tokens a head. So the sequence runs in segments
(`segment` tokens, a `lax.scan`): only one segment's intermediates are
live, forward or backward.

**Backward.** The scan over segments has its own (`jax.custom_vjp`):
the forward keeps its inputs and the state after every segment, the
backward walks the segments in reverse, runs each again from the state
it started from and differentiates it by autodiff (the same `_segment`:
the same mathematics in the same precision), carrying the state's
cotangent back. So differentiating runs the forward twice. A caller
whose `jax.checkpoint` keeps the names `SAVED` (the scan's output and
those states) rematerialises without running it a third time
(`models/kimi_linear.py`). Reverse mode only: `jax.jvp`, `jacfwd` and
a second derivative through `kda` raise, as through any
`jax.custom_vjp`. A caller that drops the final state still gives the
backward a cotangent for every segment's state (zeros, float32, the
size of the states: 33.5 MB at 2 x 8192 tokens in 8 segments).

**Precision.** The state, the cumulative sums, the decay factors and
the triangular solve are float32; the large products take their
operands in the activations' dtype (`q.dtype`) and accumulate in
float32. Everything runs under the scope `kda`. XLA ops only: a Pallas
kernel is a later change.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

CHUNK = 64                 # tokens a chunk of the scan
SUB = 16                   # rows of a diagonal block
# `checkpoint_name`s of what the scan's backward needs of its forward
# beside the inputs, and of what a caller reads of it: its output and
# the state after every segment
SAVED = ("kda_o", "kda_states")
_HIGHEST = jax.lax.Precision.HIGHEST


@jax.named_scope("short_conv")
def short_conv(x: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution over time. x: [b, t, c]; taps:
    [n, c]; y[t] = sum_j taps[j] * x[t - (n - 1) + j], zeros before the
    start (tap n - 1 is on the current token)."""
    n = taps.shape[0]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    taps = taps.astype(x.dtype)
    return sum(taps[j] * padded[:, j:j + t] for j in range(n))


def _solve_blocks(a_off, a_diag, rhs):
    """X with (I + A) X = rhs, A strictly lower triangular and given in
    blocks of SUB rows: `a_off` [..., nb, SUB, C] holds block row n
    against the columns of the earlier blocks (zero from its own block
    on), `a_diag` [..., nb, SUB, SUB] its own block, strictly lower.
    rhs: [..., C, w]. All float32."""
    *lead, nb, _, _ = a_diag.shape
    # the inverse of each diagonal block, a row at a time:
    # T[t] = e_t - sum_{i<t} a[t, i] T[i]. With the blocks on the last
    # axis every step is one dense elementwise pass (a [.., 16, 16]
    # block a tile would leave seven eighths of each tile empty).
    ad = jnp.moveaxis(a_diag.reshape(-1, SUB, SUB), 0, -1)   # [t, i, N]
    eye = jnp.eye(SUB, dtype=ad.dtype)
    rows = []
    for t in range(SUB):
        r = jnp.broadcast_to(eye[t][:, None], ad.shape[1:])
        if t:
            r = r - jnp.sum(ad[t, :t, None, :] * jnp.stack(rows), axis=0)
        rows.append(r)
    t_diag = jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(
        *lead, nb, SUB, SUB)
    # block forward substitution
    rhs4 = rhs.reshape(*lead, nb, SUB, rhs.shape[-1])
    xs = []
    for n in range(nb):
        r = rhs4[..., n, :, :]
        if n:
            r = r - jnp.einsum("...ti,...iw->...tw",
                               a_off[..., n, :, :n * SUB],
                               jnp.concatenate(xs, axis=-2),
                               precision=_HIGHEST)
        xs.append(jnp.einsum("...ti,...iw->...tw", t_diag[..., n, :, :], r,
                             precision=_HIGHEST))
    return jnp.concatenate(xs, axis=-2)


def _decay_products(q, k, g_cum, g_excl):
    """sum_c x_tc k_ic e^(G_tc - G_ic) for i <= t, x = q and x = k, in
    blocks of SUB rows -> (q_off, q_diag, k_off, k_diag): `*_off`
    [..., nb, SUB, C] is block row n against the columns of the earlier
    blocks (zero from its own block on), `*_diag` [..., nb, SUB, SUB]
    its own block (zero above the diagonal). Inputs are [..., C, dk];
    `g_cum` the inclusive and `g_excl` the exclusive cumulative
    log-decay. No exponent is positive."""
    cd = q.dtype
    *lead, c_len, dk = k.shape
    nb = c_len // SUB

    def blocks(x):
        return x.reshape(*lead, nb, SUB, x.shape[-1])

    g4 = blocks(g_cum)
    ref = blocks(g_excl)[..., :, 0, :]              # [..., nb, dk]: R_n
    # rows of block n against every earlier block, through R_n
    row_scale = jnp.exp(g4 - ref[..., :, None, :])
    i_block = jnp.arange(c_len) // SUB
    earlier = i_block[None, :] < jnp.arange(nb)[:, None]      # [nb, C]
    col_exp = jnp.where(earlier[..., None],
                        ref[..., :, None, :] - g_cum[..., None, :, :],
                        -jnp.inf)
    k_cols = (k[..., None, :, :] * jnp.exp(col_exp)).astype(cd)

    def off_diagonal(x):
        rows = (blocks(x) * row_scale).astype(cd)
        return jnp.einsum("...ntd,...nid->...nti", rows, k_cols,
                          preferred_element_type=jnp.float32)

    # the diagonal blocks, directly: a fused multiply-exp-reduce
    t_idx = jnp.arange(SUB)
    lower = t_idx[:, None] >= t_idx[None, :]
    diff = jnp.where(lower[..., None],
                     g4[..., :, None, :] - g4[..., None, :, :], -jnp.inf)
    decay = jnp.exp(diff)                            # [..., nb, SUB, SUB, dk]
    k4 = blocks(k).astype(jnp.float32)

    def diagonal(x):
        x4 = blocks(x).astype(jnp.float32)
        return jnp.sum(x4[..., :, None, :] * k4[..., None, :, :] * decay,
                       axis=-1)

    return off_diagonal(q), diagonal(q), off_diagonal(k), diagonal(k)


# `_segment` and the two scans over it are jitted so that one trace
# serves them all: the forward and the backward of every layer of a
# model (they call at one shape) share `_segment`'s, which is most of
# what a step's first call spends in Python (`_solve_blocks` alone is a
# loop of 16 rows and 4 blocks), and the backward differentiates its
# jaxpr whole, not operation by operation as a second trace would
# (5 s of a training cell's set-up: PERF.md section 6, PR 32;
# `tests/test_kimi_linear.py` counts the loops a step lowers to).
@jax.jit
def _segment(state, xs):
    """One segment of `n` chunks. state: [b, h, dk, dv] float32; xs:
    q, k [b, h, n, C, dk], v [b, h, n, C, dv], g [b, h, n, C, dk]
    float32 log-decays, beta [b, h, n, C] float32."""
    q, k, v, g, beta = xs
    cd = q.dtype
    f32 = jnp.float32
    b, h, n, c_len, dk = k.shape
    nb = c_len // SUB
    g_cum = jnp.cumsum(g, axis=-2)
    q_off, q_diag, k_off, k_diag = _decay_products(q, k, g_cum, g_cum - g)
    beta4 = beta.reshape(b, h, n, nb, SUB, 1)
    strictly = jnp.tril(jnp.ones((SUB, SUB), bool), -1)
    decay_in = jnp.exp(g_cum)                        # from the chunk's start
    k32, v32 = k.astype(f32), v.astype(f32)
    x = _solve_blocks(
        beta4 * k_off, jnp.where(strictly, beta4 * k_diag, 0.0),
        beta[..., None] * jnp.concatenate([k32 * decay_in, v32], axis=-1))
    w, u0 = x[..., :dk].astype(cd), x[..., dk:]
    q_in = (q.astype(f32) * decay_in).astype(cd)
    g_last = g_cum[..., -1:, :]
    k_out = (k32 * jnp.exp(g_last - g_cum)).astype(cd)
    decay_out = jnp.exp(g_last[..., 0, :])           # [b, h, n, dk]
    q_off, q_diag = q_off.astype(cd), q_diag.astype(cd)

    def chunk(s, c):
        w_c, u0_c, q_c, q_off_c, q_diag_c, k_c, d_c = c
        s_cd = s.astype(cd)
        u = u0_c - jnp.einsum("bhtk,bhkv->bhtv", w_c, s_cd,
                              preferred_element_type=f32)
        u_cd = u.astype(cd)
        within = (
            jnp.einsum("bhnti,bhiv->bhntv", q_off_c, u_cd,
                       preferred_element_type=f32)
            + jnp.einsum("bhnti,bhniv->bhntv", q_diag_c,
                         u_cd.reshape(b, h, nb, SUB, -1),
                         preferred_element_type=f32))
        o = jnp.einsum("bhtk,bhkv->bhtv", q_c, s_cd,
                       preferred_element_type=f32) \
            + within.reshape(b, h, c_len, -1)
        s = d_c[..., None] * s + jnp.einsum(
            "bhtk,bhtv->bhkv", k_c, u_cd, preferred_element_type=f32)
        return s, o

    per_chunk = jax.tree.map(
        lambda t: jnp.moveaxis(t, 2, 0),
        (w, u0, q_in, q_off, q_diag, k_out, decay_out))
    state, o = jax.lax.scan(chunk, state, per_chunk)
    return state, jnp.moveaxis(o, 0, 2).astype(cd)   # [b, h, n, C, dv]


@jax.jit
def _forward(xs):
    """Every segment in turn from a zero state -> (o [n_seg, b, h, n,
    C, dv], the state after each segment [n_seg, b, h, dk, dv]
    float32)."""
    q, _, v = xs[:3]
    state = jnp.zeros((*q.shape[1:3], q.shape[-1], v.shape[-1]), jnp.float32)

    def body(state, x):
        state, o = _segment(state, x)
        return state, (o, state)

    return jax.lax.scan(body, state, xs)[1]


@jax.jit
@jax.named_scope("kda")
def _backward(xs, states, d_o, d_states):
    """-> the cotangents of `xs`, given `_forward`'s results' (see the
    module docstring). Opens the scope itself: nothing says a backward
    rule is traced under the scope its forward was called in."""
    started = jnp.concatenate([jnp.zeros_like(states[:1]), states[:-1]])

    def body(d_state, x):
        seg, s_in, d_o_seg, d_s_out = x
        _, pull = jax.vjp(_segment, s_in, seg)
        return pull((d_state + d_s_out, d_o_seg))

    return jax.lax.scan(body, jnp.zeros_like(states[0]),
                        (xs, started, d_o, d_states), reverse=True)[1]


def _named_forward(xs):
    """`_forward`, its outputs named: outside the jitted call, where a
    `jax.checkpoint` around the caller sees the names."""
    o, states = _forward(xs)
    return checkpoint_name(o, SAVED[0]), checkpoint_name(states, SAVED[1])


_segments = jax.custom_vjp(_named_forward)


def _segments_fwd(xs):
    o, states = _named_forward(xs)
    return (o, states), (xs, states)


_segments.defvjp(
    _segments_fwd,
    lambda residuals, cotangents: (_backward(*residuals, *cotangents),))


@jax.named_scope("kda")
def kda(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
        log_a: jnp.ndarray, beta: jnp.ndarray, *, segment: int = 1024):
    """q, k, log_a: [b, t, h, dk]; v: [b, t, h, dv]; beta: [b, t, h].
    `log_a` <= 0 is the log of the per-channel decay. -> (o [b, t, h,
    dv] in `q.dtype`, the final state [b, h, dk, dv] float32). `t` has
    to be a multiple of `CHUNK`; `segment` is cut to a divisor of `t`."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if t % CHUNK:
        raise ValueError(
            f"kda wants a length that is a multiple of its chunk, {CHUNK}; "
            f"got {t}")
    n_chunks = t // CHUNK
    per_seg = max(1, min(segment // CHUNK, n_chunks))
    while n_chunks % per_seg:
        per_seg -= 1
    n_seg = n_chunks // per_seg

    def split(x):          # [b, t, h, d] -> [n_seg, b, h, per_seg, C, d]
        x = x.reshape(b, n_seg, per_seg, CHUNK, h, x.shape[-1])
        return jnp.transpose(x, (1, 0, 4, 2, 3, 5))

    xs = (split(q), split(k), split(v), split(log_a.astype(jnp.float32)),
          split(beta.astype(jnp.float32)[..., None])[..., 0])
    o, states = _segments(xs)
    o = jnp.transpose(o, (1, 0, 3, 4, 2, 5)).reshape(b, t, h, dv)
    return o, states[-1]
