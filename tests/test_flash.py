"""Pallas flash attention vs dense XLA attention (interpreter mode on the
hermetic CPU backend; same kernel code compiles for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops.attention import dot_product_attention
from kubeflow_tpu.ops.pallas.flash_attention import flash_attention


def _make_qkv(b=2, s=128, n_q=4, n_kv=2, hd=64, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, s, n_q, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, n_kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, n_kv, hd)), jnp.float32)
    return q, k, v


def _reference(q, k, v, causal):
    b, s = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return dot_product_attention(q, k, v, pos, pos, causal=causal, impl="xla")


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [32, 64, 128])
def test_flash_forward_matches_dense(causal, block):
    q, k, v = _make_qkv()
    got = flash_attention(q, k, v, causal=causal, block_q=block, block_k=block)
    want = _reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_uneven_blocks():
    """block_q != block_k exercises the rectangular mask indexing."""
    q, k, v = _make_qkv(s=128)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
    want = _reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_mha():
    q, k, v = _make_qkv(n_q=4, n_kv=4)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = _reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_dense(causal):
    q, k, v = _make_qkv(b=1, s=64, n_q=4, n_kv=2, hd=32)

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        return jnp.sum(o * jnp.cos(o))

    def dense_loss(q, k, v):
        o = _reference(q, k, v, causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for name, gf, gd in zip("qkv", g_flash, g_dense):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=5e-4, atol=5e-4,
            err_msg=f"grad w.r.t. {name}",
        )


def _brute_window(q, k, v, window):
    """Oracle: dense attention with an explicit sliding-window mask."""
    b, s, n_q, hd = q.shape
    n_kv = k.shape[2]
    g = n_q // n_kv
    qg = q.reshape(b, s, n_kv, g, hd).astype(jnp.float32)
    logits = jnp.einsum("bsngh,btnh->bngst", qg,
                        k.astype(jnp.float32)) * hd**-0.5
    pos = jnp.arange(s)
    mask = (pos[:, None] >= pos[None, :]) & (
        pos[:, None] - pos[None, :] < window)
    logits = jnp.where(mask[None, None, None], logits, -2.0**30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngst,btnh->bsngh", probs, v.astype(jnp.float32))
    return out.reshape(b, s, n_q, hd)


@pytest.mark.parametrize("window", [1, 5, 32, 128])
@pytest.mark.parametrize("block", [32, 64])
def test_sliding_window_flash_matches_oracle(window, block):
    """Windowed flash (index masks + out-of-band block skip) must match
    a brute-force masked dense oracle — including window >= seq
    (degenerates to plain causal) and window smaller than a block."""
    q, k, v = _make_qkv(s=128)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=block, block_k=block)
    want = _brute_window(q, k, v, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sliding_window_xla_matches_oracle():
    q, k, v = _make_qkv(s=64)
    b, s = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    got = dot_product_attention(q, k, v, pos, pos, causal=True,
                                window=7, impl="xla")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_brute_window(q, k, v, 7)),
                               rtol=2e-5, atol=2e-5)


def test_sliding_window_gradients_match():
    """Windowed flash custom-VJP grads == autodiff through the masked
    dense oracle, for q, k, and v."""
    q, k, v = _make_qkv(s=64, hd=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=9, block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_brute_window(q, k, v, 9).astype(q.dtype) ** 2)

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_sliding_window_validation():
    q, k, v = _make_qkv(s=32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention(q, k, v, causal=True, window=0)


def test_sliding_window_model_locality():
    """A sliding_window model must ignore tokens beyond the window:
    perturbing a token at distance >= window leaves the last position's
    hidden state unchanged; perturbing inside the window changes it."""
    import dataclasses

    from kubeflow_tpu.models import llama

    cfg = dataclasses.replace(llama.LLAMA_TINY, num_layers=1,
                              sliding_window=4)
    params = llama.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (1, 16))

    def last_hidden(t):
        return np.asarray(llama.hidden(
            params, cfg, jnp.asarray(t, jnp.int32))[:, -1])

    base = last_hidden(toks)
    far = toks.copy(); far[0, 5] = (far[0, 5] + 1) % cfg.vocab_size
    np.testing.assert_array_equal(last_hidden(far), base)  # dist 10 >= 4
    near = toks.copy(); near[0, 13] = (near[0, 13] + 1) % cfg.vocab_size
    assert np.abs(last_hidden(near) - base).max() > 0      # dist 2 < 4


def test_flash_under_jit():
    q, k, v = _make_qkv(s=64)

    @jax.jit
    def run(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=32)

    got = run(q, k, v)
    want = _reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16_io():
    q, k, v = (x.astype(jnp.bfloat16) for x in _make_qkv(s=64))
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert got.dtype == jnp.bfloat16
    want = _reference(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_flash_rejects_cross_attention_shapes():
    q, k, v = _make_qkv(s=64)
    with pytest.raises(ValueError, match="equal q/kv"):
        flash_attention(q, k[:, :32], v[:, :32])


def test_dispatcher_routes_flash_on_request():
    """ops.attention impl='flash' path uses the kernel end-to-end."""
    q, k, v = _make_qkv(s=64)
    b, s = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    got = dot_product_attention(q, k, v, pos, pos, causal=True,
                                impl="flash", contiguous_positions=True)
    want = _reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_v_head_size_differs_from_qk():
    """Latent attention after up-projection: q/k heads of 192 beside v
    heads of 128 through the same kernels, forward and gradients."""
    rng = np.random.default_rng(5)
    b, s, n = 1, 128, 2
    q = jnp.asarray(rng.standard_normal((b, s, n, 192)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, n, 192)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, n, 128)), jnp.float32)

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
        return jnp.sum(o * jnp.cos(o)), o

    def dense_loss(q, k, v):
        o = _reference(q, k, v, True)
        return jnp.sum(o * jnp.cos(o)), o

    (_, got), g_flash = jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), g_dense = jax.value_and_grad(
        dense_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert got.shape == (b, s, n, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for name, gf, gd in zip("qkv", g_flash, g_dense):
        assert gf.shape == gd.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=5e-4, atol=5e-4,
            err_msg=f"grad w.r.t. {name}")
