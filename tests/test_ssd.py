"""`ops/ssd.py`: the chunked state-space-dual scan, the one-token update
and the carried convolution against the recurrence written out a token
at a time."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops import ssd


def draw(seed, b, s, h=4, p=8, n=16, g=1):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return dict(
        x=normal(b, s, h, p),
        dt=jax.nn.softplus(normal(b, s, h)),
        A=-jnp.asarray(rng.uniform(1, 16, size=(h,)), jnp.float32),
        B=normal(b, s, g, n), C=normal(b, s, g, n),
        D=normal(h), S0=normal(b, h, p, n))


def token_by_token(x, dt, A, B, C, D, S0):
    """S_t = a_t S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D x_t, in
    numpy float64, one head's group looked up by hand."""
    x, dt, A, B, C, D = (np.asarray(t, np.float64)
                         for t in (x, dt, A, B, C, D))
    S = np.asarray(S0, np.float64).copy()
    _, s, h, _ = x.shape
    per_group = h // B.shape[2]
    ys = np.zeros(x.shape)
    for t in range(s):
        for i in range(h):
            g = i // per_group
            a = np.exp(dt[:, t, i] * A[i])[:, None, None]
            S[:, i] = a * S[:, i] + (
                dt[:, t, i, None, None] * x[:, t, i, :, None]
                * B[:, t, g, None, :])
            ys[:, t, i] = np.einsum("bpn,bn->bp", S[:, i], C[:, t, g])
            ys[:, t, i] += D[i] * x[:, t, i]
    return ys, S


def masked(dt, n_valid):
    s = dt.shape[1]
    return jnp.where(
        jnp.arange(s)[None, :, None] < jnp.asarray(n_valid)[:, None, None],
        dt, 0.0)


@pytest.mark.parametrize("s,n_valid,groups", [
    (64, (64, 64), 1),      # one whole chunk
    (150, (150, 77), 1),    # padded to three chunks, one row ragged
    (200, (0, 200), 2),     # a row with no valid token, two groups
    (5, (5, 3), 1),         # shorter than a chunk
])
def test_chunked_scan_is_the_recurrence(s, n_valid, groups):
    t = draw(0, 2, s, g=groups)
    t["dt"] = masked(t["dt"], n_valid)
    y, S1 = ssd.ssd_chunked(**t)
    want_y, want_S = token_by_token(**t)
    for row, n in enumerate(n_valid):
        np.testing.assert_allclose(y[row, :n], want_y[row, :n],
                                   rtol=2e-4, atol=2e-4)
        # the state that comes out is that of the valid tokens alone
        alone = {k: (v[row:row + 1, :n] if v.ndim > 1 and k != "S0" else v)
                 for k, v in t.items()}
        alone["S0"] = t["S0"][row:row + 1]
        if n:
            _, S_alone = token_by_token(**alone)
        else:
            S_alone = np.asarray(alone["S0"])
        np.testing.assert_allclose(S1[row], S_alone[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(S1, want_S, rtol=2e-4, atol=2e-4)


def test_a_row_with_no_valid_token_keeps_its_state_bit_for_bit():
    t = draw(1, 2, 32)
    t["dt"] = masked(t["dt"], (0, 32))
    _, S1 = ssd.ssd_chunked(**t)
    np.testing.assert_array_equal(S1[0], t["S0"][0])
    one = {k: (v[:, 0] if v.ndim > 1 and k != "S0" else v)
           for k, v in t.items()}
    one["S"] = one.pop("S0")
    _, S1 = ssd.ssd_step(**one)
    np.testing.assert_array_equal(S1[0], t["S0"][0])


def test_slices_carry_the_state():
    """A sequence fed in three ragged slices, then a token at a time,
    ends where the recurrence over the whole sequence ends."""
    t = draw(2, 1, 100)
    S = t["S0"]
    ys = []
    for lo, hi in ((0, 37), (37, 37 + 48), (85, 96)):
        part = {k: (v[:, lo:hi] if v.ndim > 1 and k != "S0" else v)
                for k, v in t.items()}
        part["S0"] = S
        y, S = ssd.ssd_chunked(**part, chunk=16)
        ys.append(y)
    for i in range(96, 100):
        y, S = ssd.ssd_step(t["x"][:, i], t["dt"][:, i], t["A"],
                            t["B"][:, i], t["C"][:, i], t["D"], S)
        ys.append(y[:, None])
    want_y, want_S = token_by_token(**t)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), want_y,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(S, want_S, rtol=2e-4, atol=2e-4)


def test_step_is_a_one_token_chunk():
    t = draw(3, 3, 1)
    y, S1 = ssd.ssd_chunked(**t)
    ys, Ss = ssd.ssd_step(t["x"][:, 0], t["dt"][:, 0], t["A"], t["B"][:, 0],
                          t["C"][:, 0], t["D"], t["S0"])
    np.testing.assert_allclose(y[:, 0], ys, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(S1, Ss, rtol=1e-5, atol=1e-5)


def test_no_positive_exponent():
    """A head that forgets everything inside a chunk (dt * A = -200 a
    token) neither overflows nor loses the tokens after it."""
    t = draw(4, 1, 64)
    t["dt"] = jnp.full_like(t["dt"], 50.0)
    t["A"] = jnp.full_like(t["A"], -4.0)
    y, S1 = ssd.ssd_chunked(**t)
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(S1)))
    want_y, want_S = token_by_token(**t)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(S1, want_S, rtol=2e-4, atol=2e-3)


def conv_by_hand(x, w, bias):
    """y_t = bias + sum_j w[j] x_{t - (K - 1) + j}, zeros before 0."""
    x, w, bias = (np.asarray(t, np.float64) for t in (x, w, bias))
    k = w.shape[0]
    padded = np.concatenate([np.zeros((k - 1, x.shape[1])), x])
    return bias + sum(padded[j:j + len(x)] * w[j] for j in range(k))


@pytest.mark.parametrize("cuts", [(20,), (7, 13, 20), (1, 2, 3, 20)])
def test_conv_carries_its_tail_across_ragged_slices(cuts):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(20, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    tail = jnp.zeros((1, 3, 6), jnp.float32)
    out, lo = [], 0
    for hi in cuts:
        # every slice is 8 wide (or as wide as it must be): what is
        # past its valid tokens is padding, and must not reach the tail
        width = max(8, hi - lo)
        piece = jnp.full((1, width, 6), 99.0).at[0, :hi - lo].set(x[lo:hi])
        y, tail = ssd.causal_conv(piece, tail, w, bias,
                                  jnp.asarray([hi - lo]))
        out.append(y[0, :hi - lo])
        lo = hi
    np.testing.assert_allclose(jnp.concatenate(out), conv_by_hand(x, w, bias),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(tail[0], x[-3:])


def test_conv_row_with_no_valid_token_keeps_its_tail():
    tail = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4)
    x = jnp.ones((2, 5, 4))
    _, new = ssd.causal_conv(x, tail, jnp.ones((4, 4)), jnp.zeros((4,)),
                             jnp.asarray([0, 2]))
    np.testing.assert_array_equal(new[0], tail[0])
    np.testing.assert_array_equal(new[1, 0], tail[1, 2])
    np.testing.assert_array_equal(new[1, 1:], x[1, :2])
