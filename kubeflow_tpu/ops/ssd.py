"""The Mamba-2 recurrence (state-space duality) with a carried state,
and the short causal convolution that feeds it.

Per head, with state S in R^{P x N} and a scalar decay a head a token:

    a_t = exp(dt_t * A),  A < 0
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

One token (`ssd_step`) is that line. A slice of tokens (`ssd_chunked`)
runs in chunks of `CHUNK`: with G_t the cumulative log-decay inside a
chunk (G_t = sum_{i<=t} dt_i A <= 0, falling),

    Y  = ((C B^T) * L) (dt * X) + e^G * (C S_0),   L_ti = e^(G_t - G_i), i <= t
    S' = e^(G_last) S_0 + (e^(G_last - G) * dt * X)^T B

so only the products with S_0 are sequential (a scan over chunks).

**No positive exponent.** Every exponent is a difference G_t - G_i with
i <= t, masked before `exp`; e^G_t and e^-G_i are never formed apart.

**A carried state.** Both forms take the state they start from and
return the one they end with, so a sequence may be fed in slices and
then a token at a time. A token with `dt = 0` decays nothing and writes
nothing: padding past a row's valid tokens, and a row that is to stand
still, are `dt = 0` (the callers' mask), and the state that comes out
is that of the valid tokens alone. `causal_conv` carries the last
`K - 1` inputs of the valid tokens the same way.

**Precision.** float32 throughout: the state, the cumulative sums, the
decays, and every product (`precision=HIGHEST`: on a TPU a float32
product otherwise multiplies in bfloat16). XLA ops only; a Pallas
kernel is a later change.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# tokens a chunk of `ssd_chunked`: the [CHUNK, CHUNK] decay matrix a
# head is the quadratic part, the state products the linear one
CHUNK = 64

_HI = jax.lax.Precision.HIGHEST


def _heads(t: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """B or C `[..., G, N]` -> `[..., H, N]`: a group's vector serves
    its H / G heads."""
    g = t.shape[-2]
    if n_heads % g:
        raise ValueError(f"{n_heads} heads not grouped by {g} groups")
    return jnp.repeat(t, n_heads // g, axis=-2) if g != n_heads else t


def ssd_step(x, dt, A, B, C, D, S):
    """One token. x `[b, H, P]`, dt `[b, H]` (after softplus; 0 holds
    the row still), A, D `[H]`, B, C `[b, G, N]`, S `[b, H, P, N]`
    float32 -> (y `[b, H, P]` float32, the new S)."""
    f32 = jnp.float32
    x, dt, S = x.astype(f32), dt.astype(f32), S.astype(f32)
    h = x.shape[1]
    Bh, Ch = _heads(B.astype(f32), h), _heads(C.astype(f32), h)
    decay = jnp.exp(dt * A.astype(f32))                       # [b, H]
    S = (decay[:, :, None, None] * S
         + (dt[:, :, None] * x)[..., None] * Bh[:, :, None, :])
    y = jnp.sum(S * Ch[:, :, None, :], axis=-1)
    return y + D.astype(f32)[None, :, None] * x, S


def ssd_chunked(x, dt, A, B, C, D, S0, *, chunk: int = CHUNK):
    """A slice of s tokens from the state `S0`. x `[b, s, H, P]`, dt
    `[b, s, H]` (after softplus; 0 for padding), A, D `[H]`, B, C
    `[b, s, G, N]`, S0 `[b, H, P, N]` -> (y `[b, s, H, P]` float32, the
    state after the slice, float32). `s` is padded up to a multiple of
    `chunk` with `dt = 0` tokens, which move nothing."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        def padded(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, B, C = padded(x), padded(dt), padded(B), padded(C)
    nc = (s + pad) // chunk

    def chunks(t):
        """[b, nc * chunk, ...] -> [nc, b, chunk, ...] (scan's xs)."""
        return jnp.moveaxis(
            t.astype(f32).reshape((b, nc, chunk) + t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    A = A.astype(f32)

    def one(S, xs):
        xc, dtc, Bc, Cc = xs        # [b, c, H, P] [b, c, H] [b, c, G, N] x2
        G = jnp.cumsum(dtc * A, axis=1)                       # [b, c, H]
        diff = G[:, :, None, :] - G[:, None, :, :]            # [b, t, i, H]
        L = jnp.exp(jnp.where(causal[None, :, :, None], diff, -jnp.inf))
        Bh, Ch = _heads(Bc, h), _heads(Cc, h)                 # [b, c, H, N]
        cb = jnp.einsum("bthn,bihn->btih", Ch, Bh, precision=_HI)
        u = dtc[..., None] * xc                               # [b, c, H, P]
        y = jnp.einsum("btih,bihp->bthp", cb * L, u, precision=_HI)
        y += jnp.exp(G)[..., None] * jnp.einsum(
            "bthn,bhpn->bthp", Ch, S, precision=_HI)
        tail = jnp.exp(G[:, -1:, :] - G)                      # [b, c, H]
        S = (jnp.exp(G[:, -1, :])[:, :, None, None] * S
             + jnp.einsum("bihp,bihn->bhpn", tail[..., None] * u, Bh,
                          precision=_HI))
        return S, y

    S1, ys = jax.lax.scan(
        one, S0.astype(f32), (chunks(x), chunks(dt), chunks(B), chunks(C)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * chunk, h, p)[:, :s]
    return y + D.astype(f32)[None, None, :, None] * x[:, :s].astype(f32), S1


def causal_conv(x, tail, w, bias, n_valid):
    """Depthwise causal convolution of kernel K over a slice, with the
    K - 1 inputs before it carried in. x `[b, s, C]`, tail `[b, K - 1,
    C]` (zeros at a sequence's start), w `[K, C]` (w[K - 1] weighs the
    current token), bias `[C]`, n_valid `[b]` (tokens of the slice that
    count) -> (y `[b, s, C]` in x's dtype, the new tail: the last K - 1
    inputs of the row's valid tokens, so a row with none keeps its
    own). Sums in float32."""
    k = w.shape[0]
    s = x.shape[1]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = bias.astype(jnp.float32)
    for j in range(k):
        y = y + xp[:, j:j + s].astype(jnp.float32) * w[j].astype(jnp.float32)
    new_tail = jax.vmap(
        lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, k - 1, axis=0)
    )(xp, n_valid.astype(jnp.int32))
    return y.astype(x.dtype), new_tail.astype(tail.dtype)
