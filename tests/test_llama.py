"""Llama model correctness: shapes, causality, sharded-vs-single parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.parallel import MeshSpec, create_mesh
from kubeflow_tpu.train.trainer import Trainer, TrainConfig, cross_entropy_loss

CFG = llama.LLAMA_TINY


@pytest.fixture(scope="module")
def params():
    return llama.init(jax.random.key(0), CFG)


def test_forward_shape(params):
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.apply(params, CFG, tokens)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality(params):
    """Changing a future token must not change past logits."""
    rng = np.random.default_rng(0)
    toks = rng.integers(0, CFG.vocab_size, (1, 12)).astype(np.int32)
    toks2 = toks.copy()
    toks2[0, -1] = (toks2[0, -1] + 1) % CFG.vocab_size
    l1 = llama.apply(params, CFG, jnp.asarray(toks))
    l2 = llama.apply(params, CFG, jnp.asarray(toks2))
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)
    assert not np.allclose(l1[0, -1], l2[0, -1])


def test_padding_mask(params):
    """Padded kv positions must not leak into valid positions."""
    rng = np.random.default_rng(1)
    toks = rng.integers(0, CFG.vocab_size, (1, 8)).astype(np.int32)
    padded = np.concatenate([toks, rng.integers(0, CFG.vocab_size, (1, 4)).astype(np.int32)], 1)
    mask = np.concatenate([np.ones((1, 8), bool), np.zeros((1, 4), bool)], 1)
    l_ref = llama.apply(params, CFG, jnp.asarray(toks))
    l_pad = llama.apply(params, CFG, jnp.asarray(padded), kv_mask=jnp.asarray(mask))
    np.testing.assert_allclose(l_ref[0], l_pad[0, :8], atol=1e-5)


def test_num_params():
    n = llama.num_params(CFG)
    assert n > 0
    # embed + lm_head + 2 layers of (2 norms + 4 attn + 3 mlp mats)
    D, L = CFG.hidden_size, CFG.num_layers
    expected = (
        CFG.vocab_size * D * 2
        + L * (2 * D + D * CFG.q_dim + 2 * D * CFG.kv_dim + CFG.q_dim * D
               + 3 * D * CFG.intermediate_size)
        + D
    )
    assert n == expected


def test_fsdp_tp_parity():
    """Sharded (fsdp=4, tensor=2) forward == single-device forward."""
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, CFG.vocab_size, (4, 16)), jnp.int32
    )
    params = llama.init(jax.random.key(0), CFG)
    ref = llama.apply(params, CFG, tokens)

    mesh = create_mesh(MeshSpec(data=1, fsdp=4, tensor=2))
    with jax.set_mesh(mesh):
        sharded = jax.jit(lambda p, t: llama.apply(p, CFG, t))(params, tokens)
    np.testing.assert_allclose(ref, sharded, atol=2e-4, rtol=1e-3)


def test_train_step_runs_and_learns():
    mesh = create_mesh(MeshSpec(data=2, fsdp=2, tensor=2))
    trainer = Trainer(
        mesh=mesh,
        apply_fn=lambda p, t: llama.apply(p, CFG, t),
        init_fn=lambda k: llama.init(k, CFG),
        logical_axes=llama.param_logical_axes(CFG),
        train_config=TrainConfig(learning_rate=1e-2, warmup_steps=2, total_steps=50),
    )
    state = trainer.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (8, 16)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    losses = []
    for _ in range(5):
        state, loss = trainer.step(state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert int(state.step) == 5


@pytest.mark.slow
def test_hybrid_dcn_trainer_matches_single_slice():
    """DP-over-DCN: the Trainer on a hybrid (dcn=2, fsdp=2, tensor=2)
    mesh — params replicated per slice, grads all-reduced across the dcn
    axis — yields the same losses and params as a single-slice mesh on
    identical data."""
    from kubeflow_tpu.parallel import create_hybrid_mesh

    tc = TrainConfig(learning_rate=1e-2, warmup_steps=2, total_steps=50)

    def mk(mesh):
        return Trainer(
            mesh=mesh,
            apply_fn=lambda p, t: llama.apply(p, CFG, t),
            init_fn=lambda k: llama.init(k, CFG),
            logical_axes=llama.param_logical_axes(CFG),
            train_config=tc,
        )

    hybrid = mk(create_hybrid_mesh(
        MeshSpec(data=1, fsdp=2, tensor=2), num_slices=2))
    assert hybrid.batch_sharding.spec[0] == ("dcn", "data", "fsdp")
    single = mk(create_mesh(
        MeshSpec(data=1, fsdp=2, tensor=2), devices=jax.devices()[:4]))

    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (8, 16)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    hstate, sstate = hybrid.init(jax.random.key(5)), single.init(jax.random.key(5))
    for _ in range(3):
        hstate, hloss = hybrid.step(hstate, tokens, targets)
        sstate, sloss = single.step(sstate, tokens, targets)
        np.testing.assert_allclose(float(hloss), float(sloss), rtol=2e-4)
    for (kh, vh), (ks, vs) in zip(
        jax.tree_util.tree_leaves_with_path(hstate.params),
        jax.tree_util.tree_leaves_with_path(sstate.params),
    ):
        # Loose-ish: Adam's mu/(sqrt(nu)+eps) amplifies float
        # reassociation noise for near-zero second moments early on.
        np.testing.assert_allclose(
            np.asarray(vh), np.asarray(vs), rtol=5e-3, atol=3e-4,
            err_msg=jax.tree_util.keystr(kh),
        )


@pytest.mark.slow
def test_remat_policies_match_full_remat(params):
    """Every remat_policy ("mlp" save-list, "dots") is a pure
    HBM-for-FLOPs schedule change: loss and grads must match the default
    full-remat path to fp32 rounding (llama.py _REMAT_POLICIES; exact
    bitwise equality is NOT guaranteed — the save-set moves XLA fusion
    boundaries, which may reassociate reductions)."""
    import dataclasses

    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 16)), jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)

    def loss_and_grads(cfg):
        f = lambda p: cross_entropy_loss(llama.apply(p, cfg, toks), tgts)
        return jax.value_and_grad(f)(params)

    base = dataclasses.replace(CFG, remat=True)
    ref_l, ref_g = loss_and_grads(base)
    assert list(llama._REMAT_POLICIES) == ["full", "mlp", "dots"]
    for policy in ("mlp", "dots"):
        l, g = loss_and_grads(
            dataclasses.replace(base, remat_policy=policy))
        np.testing.assert_allclose(float(l), float(ref_l), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(ref_g), jax.tree.leaves(g)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_cross_entropy_masked():
    logits = jnp.zeros((1, 4, 10))
    targets = jnp.zeros((1, 4), jnp.int32)
    full = cross_entropy_loss(logits, targets)
    np.testing.assert_allclose(full, np.log(10), rtol=1e-6)
    mask = jnp.asarray([[1, 1, 0, 0]], jnp.float32)
    masked = cross_entropy_loss(logits, targets, mask)
    np.testing.assert_allclose(masked, np.log(10), rtol=1e-6)


@pytest.mark.slow
def test_chunked_ce_matches_dense_value_and_grads():
    """chunked_cross_entropy_from_hidden == cross_entropy_loss(hidden @
    head) to fp32 rounding, for values AND parameter gradients, with and
    without a mask, tied and untied heads."""
    import dataclasses

    from kubeflow_tpu.train.trainer import (
        chunked_cross_entropy_from_hidden)

    rng = np.random.default_rng(11)
    for tie in (False, True):
        cfg = dataclasses.replace(CFG, tie_embeddings=tie)
        params = llama.init(jax.random.key(11), cfg)
        toks = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
        tgts = jnp.roll(toks, -1, axis=1)
        mask = jnp.asarray(rng.integers(0, 2, (2, 16)), jnp.float32)

        def dense(p, m):
            return cross_entropy_loss(llama.apply(p, cfg, toks), tgts, m)

        def chunked(p, m):
            h = llama.hidden(p, cfg, toks)
            return chunked_cross_entropy_from_hidden(
                h, llama.unembed_matrix(p, cfg), tgts, m, num_chunks=8)

        for m in (None, mask):
            np.testing.assert_allclose(
                float(chunked(params, m)), float(dense(params, m)),
                rtol=1e-5)
            g_d = jax.grad(lambda p: dense(p, m))(params)
            g_c = jax.grad(lambda p: chunked(p, m))(params)
            for (kd, vd), (kc, vc) in zip(
                jax.tree_util.tree_leaves_with_path(g_d),
                jax.tree_util.tree_leaves_with_path(g_c),
            ):
                np.testing.assert_allclose(
                    np.asarray(vc), np.asarray(vd), rtol=2e-4, atol=2e-6,
                    err_msg=f"tie={tie} {jax.tree_util.keystr(kd)}")


def test_chunked_ce_indivisible_vocab_falls_back():
    from kubeflow_tpu.train.trainer import chunked_cross_entropy_from_hidden

    h = jnp.asarray(np.random.default_rng(0).normal(size=(1, 4, 8)),
                    jnp.float32)
    head = jnp.asarray(np.random.default_rng(1).normal(size=(8, 13)),
                       jnp.float32)
    tgts = jnp.asarray([[0, 5, 12, 7]], jnp.int32)
    got = chunked_cross_entropy_from_hidden(h, head, tgts, num_chunks=8)
    want = cross_entropy_loss(h @ head, tgts)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


@pytest.mark.slow
def test_trainer_with_chunked_loss_matches_dense_trainer():
    """The Trainer driven by the chunked loss must train identically to
    the logits path (same losses, same updated params)."""
    from kubeflow_tpu.train.trainer import chunked_cross_entropy_from_hidden

    tc = TrainConfig(learning_rate=1e-2, warmup_steps=2, total_steps=50)
    mesh = create_mesh(MeshSpec(data=2, fsdp=2, tensor=2))

    def chunked_loss(params, tokens, targets, mask):
        h = llama.hidden(params, CFG, tokens)
        return chunked_cross_entropy_from_hidden(
            h, llama.unembed_matrix(params, CFG), targets, mask,
            num_chunks=8)

    common = dict(
        mesh=mesh,
        apply_fn=lambda p, t: llama.apply(p, CFG, t),
        init_fn=lambda k: llama.init(k, CFG),
        logical_axes=llama.param_logical_axes(CFG),
        train_config=tc,
    )
    dense_tr = Trainer(**common)
    chunk_tr = Trainer(**common, loss_fn=chunked_loss)
    rng = np.random.default_rng(12)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (8, 16)), jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)
    ds, cs = dense_tr.init(jax.random.key(3)), chunk_tr.init(jax.random.key(3))
    for _ in range(3):
        ds, dl = dense_tr.step(ds, toks, tgts)
        cs, cl = chunk_tr.step(cs, toks, tgts)
        np.testing.assert_allclose(float(cl), float(dl), rtol=2e-4)
    for (kd, vd), (kc, vc) in zip(
        jax.tree_util.tree_leaves_with_path(ds.params),
        jax.tree_util.tree_leaves_with_path(cs.params),
    ):
        np.testing.assert_allclose(
            np.asarray(vc), np.asarray(vd), rtol=5e-3, atol=3e-4,
            err_msg=jax.tree_util.keystr(kd))


@pytest.mark.slow
def test_grad_accum_matches_full_batch_step():
    """grad_accum=N must produce the same loss and (to summation-order
    tolerance) the same updated params as the full-batch step — the
    mask-weighted averaging is what makes ragged masks exact."""
    mesh = create_mesh(MeshSpec(data=2, fsdp=2, tensor=2))

    def build(acc):
        return Trainer(
            mesh=mesh,
            apply_fn=lambda p, t: llama.apply(p, CFG, t),
            init_fn=lambda k: llama.init(k, CFG),
            logical_axes=llama.param_logical_axes(CFG),
            train_config=TrainConfig(
                learning_rate=1e-2, warmup_steps=2, total_steps=50,
                grad_accum=acc),
        )

    rng = np.random.default_rng(9)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (8, 16)),
                         jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    # ragged mask: rows carry different token counts, so unweighted
    # micro averaging would be wrong and this test would catch it
    mask = jnp.asarray(
        (np.arange(16)[None, :] < rng.integers(4, 17, (8, 1)))
        .astype(np.float32))

    ref_t = build(1)
    state = ref_t.init(jax.random.key(0))
    ref_state, ref_loss = ref_t.step(state, tokens, targets, mask)

    for acc in (2, 4):
        t = build(acc)
        s = t.init(jax.random.key(0))
        s2, loss = t.step(s, tokens, targets, mask)
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
        ref_leaves = jax.tree.leaves(ref_state.params)
        got_leaves = jax.tree.leaves(s2.params)
        for a, b in zip(ref_leaves, got_leaves):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(a)),
                np.asarray(jax.device_get(b)), rtol=2e-4, atol=2e-6)

    with pytest.raises(ValueError, match="not divisible"):
        build(3).step(ref_state, tokens, targets, mask)


@pytest.mark.slow
def test_adafactor_trains_and_checkpoints():
    """TrainConfig.optimizer=adafactor: loss falls under the sharded
    step, the factored second-moment state shards/replicates cleanly
    (non-mirroring leaves replicate by design), and the state
    round-trips through the Checkpointer."""
    from kubeflow_tpu.train.checkpoint import (
        CheckpointConfig, Checkpointer,
    )

    mesh = create_mesh(MeshSpec(data=2, fsdp=2, tensor=2))
    trainer = Trainer(
        mesh=mesh,
        apply_fn=lambda p, t: llama.apply(p, CFG, t),
        init_fn=lambda k: llama.init(k, CFG),
        logical_axes=llama.param_logical_axes(CFG),
        train_config=TrainConfig(learning_rate=1e-2, warmup_steps=2,
                                 total_steps=50, optimizer="adafactor"),
    )
    state = trainer.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (8, 16)),
                         jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    losses = []
    for _ in range(5):
        state, loss = trainer.step(state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses

    import tempfile

    with tempfile.TemporaryDirectory() as d:
        ckpt = Checkpointer(
            CheckpointConfig(d, save_interval_steps=1,
                             enable_async=False), trainer)
        assert ckpt.save(state, force=True)
        restored = ckpt.restore()
        _, la = trainer.step(state, tokens, targets)
        _, lb = trainer.step(restored, tokens, targets)
        assert float(la) == float(lb)
        ckpt.close()

    with pytest.raises(ValueError, match="unknown optimizer"):
        Trainer(
            mesh=mesh,
            apply_fn=lambda p, t: llama.apply(p, CFG, t),
            init_fn=lambda k: llama.init(k, CFG),
            logical_axes=llama.param_logical_axes(CFG),
            train_config=TrainConfig(optimizer="sgd"),
        )
