"""Kimi-Linear on the CPU in float32, against the plain reference the
benchmark uses (`benchmarks/models/kimi_linear_reference.py`): the
chunked KDA scan against the token-by-token recurrence, latent
attention without positions through flash (interpret mode) and XLA, an
expert layer's shares adding up to the uncut layer, nothing dropped
under a skewed router, and the five-layer model in the published
pattern (logits, loss, every parameter's gradient, a `Trainer` step)."""

from __future__ import annotations

import dataclasses
import gc
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.models import kimi_linear_reference as ref
from kubeflow_tpu.models import kimi_linear as kl
from kubeflow_tpu.ops import kda as kda_ops
from kubeflow_tpu.parallel import MeshSpec, create_mesh
from kubeflow_tpu.parallel import moe as moe_lib
from kubeflow_tpu.train import TrainConfig, Trainer

TINY = kl.KIMI_LINEAR_TINY


def config_dict(cfg: kl.KimiLinearConfig) -> dict:
    """The keys of a configuration file the reference reads."""
    return {
        "num_hidden_layers": cfg.num_layers, "first_layer": cfg.first_layer,
        "first_k_dense_replace": cfg.first_k_dense_replace,
        "rms_norm_eps": cfg.norm_eps,
        "linear_attn_config": {
            "kda_layers": list(cfg.kda_layers),
            "full_attn_layers": list(cfg.full_attn_layers),
            "num_heads": cfg.kda_num_heads, "head_dim": cfg.kda_head_dim,
            "short_conv_kernel_size": cfg.short_conv_kernel_size},
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "experts_held": list(cfg.experts_held),
        "num_experts_per_token": cfg.num_experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling_factor}


def highest(fn):
    """The reference sets the precision itself only at its entry
    points; its layer functions are called here under it."""
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


# -- (a) the chunked scan against the recurrence ----------------------------

def kda_inputs(t, decay, b=2, h=3, dk=32, dv=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    log_a = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h, dk)) - 2.0)
    if decay == "e-20":
        # four channels lose e^-20 every 16 tokens (e^-80 a chunk), four
        # more lose e^-30 in one token: e^G_t e^-G_i would overflow
        log_a = log_a.at[..., :4].set(-20.0 / 16)
        log_a = log_a.at[:, 5, :, 4:8].set(-30.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, log_a, beta


@highest
def recurrence(*args):
    return jax.vmap(ref.kda_recurrence)(*args)


@pytest.mark.parametrize("decay", ["mild", "e-20"])
@pytest.mark.parametrize("t", [64, 128, 192])
def test_chunked_kda_matches_the_recurrence(t, decay):
    args = kda_inputs(t, decay)
    # segment 128: lengths 128 and 192 cross a segment's boundary
    o, s = kda_ops.kda(*args, segment=128)
    want_o, want_s = recurrence(*args)
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_allclose(s, want_s, atol=5e-6)


GRADIENTS = ("q", "k", "v", "log_a", "beta")


def assert_gradients_match(got, want):
    """To 1e-5 of the largest entry, each of the five."""
    for name, g, g_want in zip(GRADIENTS, got, want, strict=True):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(
            g, g_want, atol=1e-5 * float(jnp.abs(g_want).max()), err_msg=name)


@pytest.mark.parametrize("decay", ["mild", "e-20"])
@pytest.mark.parametrize("t", [64, 192])
def test_chunked_kda_gradients_match_the_recurrence(t, decay):
    args = kda_inputs(t, decay, seed=1)
    w = jax.random.normal(jax.random.key(9), (*args[2].shape,))
    got = jax.grad(lambda *a: jnp.sum(
        kda_ops.kda(*a, segment=128)[0] * w), argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a)[0] * w),
                    argnums=range(5))(*args)
    assert_gradients_match(got, want)


def autodiff_segments(xs):
    """`ops/kda.py`'s scan over segments as autodiff sees it, with no
    backward of its own: the oracle for the one it has."""
    q, _, v = xs[:3]
    state = jnp.zeros((*q.shape[1:3], q.shape[-1], v.shape[-1]), jnp.float32)

    def body(state, x):
        state, o = kda_ops._segment(state, x)
        return state, (o, state)

    return jax.lax.scan(body, state, xs)[1]


@pytest.mark.parametrize("decay", ["mild", "e-20"])
@pytest.mark.parametrize("t", [64, 128, 192, 512])
def test_the_scans_own_backward_matches_autodiff(t, decay, monkeypatch):
    # segment 128: one segment of one and of two chunks, three of one,
    # four of two
    args = kda_inputs(t, decay, seed=2)
    w_o = jax.random.normal(jax.random.key(7), args[2].shape)
    w_s = jax.random.normal(jax.random.key(8), (2, 3, 32, 16))

    @highest
    def grads():
        def loss(*a):
            o, s = kda_ops.kda(*a, segment=128)
            return jnp.sum(o * w_o) + jnp.sum(s * w_s)
        return jax.grad(loss, argnums=range(5))(*args)

    got = grads()
    monkeypatch.setattr(kda_ops, "_segments", autodiff_segments)
    want = grads()
    assert_gradients_match(got, want)


def test_the_final_states_cotangent_reaches_every_segment():
    """A loss on the final state alone: its cotangent enters at the last
    segment and is carried back through all four."""
    args = kda_inputs(512, "mild", seed=3)
    w = jax.random.normal(jax.random.key(5), (2, 3, 32, 16))
    got = jax.grad(lambda *a: jnp.sum(
        kda_ops.kda(*a, segment=128)[1] * w), argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a)[1] * w),
                    argnums=range(5))(*args)
    assert_gradients_match(got, want)
    # the state holds what was written, not what read it
    for name, g in zip(GRADIENTS, got):
        assert (float(jnp.abs(g[:, :128]).max()) > 0) == (name != "q"), name


def test_kda_refuses_a_length_that_is_no_multiple_of_the_chunk():
    with pytest.raises(ValueError, match="multiple"):
        kda_ops.kda(*kda_inputs(96, "mild"))


def test_short_conv_is_causal_and_matches_the_reference():
    x = jax.random.normal(jax.random.key(0), (2, 12, 8))
    taps = jax.random.normal(jax.random.key(1), (4, 8))
    got = kda_ops.short_conv(x, taps)
    np.testing.assert_allclose(got[1], ref.short_conv(x[1], taps), atol=1e-6)
    later = x.at[:, 7:].set(0.0)
    np.testing.assert_allclose(kda_ops.short_conv(later, taps)[:, :7],
                               got[:, :7], atol=1e-6)


# -- (b) latent attention without positions --------------------------------

@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_mla_layer_matches_the_reference(impl):
    # published head sizes: q/k 128 + 64 = 192 beside v 128; two heads
    cfg = dataclasses.replace(
        TINY, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        attention_impl=impl)
    p = kl._init_layer(jax.random.key(3), cfg, 4)["attn"]
    h = jax.random.normal(jax.random.key(4), (2, 128, cfg.hidden_size))
    got = kl.mla_attention(cfg, h, p)
    c = ref.static_config(config_dict(cfg))["mla"]
    for i in range(2):
        np.testing.assert_allclose(
            got[i], highest(ref.mla_attention)(h[i], p, **c), atol=2e-5)


def test_mla_keys_are_not_rotated():
    """Without positions the layer commutes with a permutation of the
    earlier tokens: the last token's output does not change."""
    cfg = TINY
    p = kl._init_layer(jax.random.key(3), cfg, 4)["attn"]
    h = jax.random.normal(jax.random.key(5), (1, 64, cfg.hidden_size))
    flipped = jnp.concatenate([h[:, :-1][:, ::-1], h[:, -1:]], axis=1)
    np.testing.assert_allclose(kl.mla_attention(cfg, h, p)[0, -1],
                               kl.mla_attention(cfg, flipped, p)[0, -1],
                               atol=2e-5)


# -- (c), (d) the expert layer's shares ---------------------------------------

def expert_layer(skew=None, n_tok=96, seed=0):
    """A whole expert layer of TINY's sizes (16 experts) and tokens."""
    cfg = dataclasses.replace(TINY, experts_held=(0, TINY.num_experts))
    p = kl._init_layer(jax.random.key(seed), cfg, 2)["ffn"]
    h = jax.random.normal(jax.random.key(seed + 1), (n_tok, cfg.hidden_size))
    if skew is not None:
        # every token's score for expert `skew` is the largest by far
        # for 60 % of the tokens
        push = jnp.where(jnp.arange(n_tok) < 0.6 * n_tok, 8.0, 0.0)
        router = p["experts"]["router"]
        direction = h / jnp.sum(jnp.square(h), -1, keepdims=True)
        p["experts"]["router"] = router.at[:, skew].set(
            jnp.einsum("t,td->d", push, direction))
    return cfg, p, h


def share_of(p, first, count):
    e = p["experts"]
    return {"shared": p["shared"], "experts": {
        "router": e["router"],
        **{n: e[n][first:first + count] for n in ("w_gate", "w_up", "w_down")}}}


def test_eight_shares_add_up_to_the_uncut_layer():
    cfg, p, h = expert_layer()
    want = highest(ref.moe_ffn)(
        h, p, held=(0, cfg.num_experts), top_k=cfg.num_experts_per_token,
        scale=cfg.routed_scaling_factor)
    count = cfg.num_experts // 8
    total, pairs = 0.0, 0
    for first in range(0, cfg.num_experts, count):
        share = dataclasses.replace(cfg, experts_held=(first, count))
        y, load = kl.moe_ffn(share, h[None], share_of(p, first, count))
        total = total + y[0]
        pairs += int(load.sum())
    # what every chip computes alike, the shared expert, counted once
    shared = kl._swiglu(h, p["shared"], cfg.dtype)
    np.testing.assert_allclose(total - 7 * shared, want, atol=2e-5)
    assert pairs == h.shape[0] * cfg.num_experts_per_token


@pytest.mark.parametrize("held", [(4, 4), (0, 16)])
def test_nothing_is_dropped_under_a_skewed_router(held):
    cfg, p, h = expert_layer(skew=5)
    first, count = held
    share = dataclasses.replace(cfg, experts_held=held)
    y, load = kl.moe_ffn(share, h[None], share_of(p, first, count))
    # expert 5 took 60 % of the tokens at least
    assert int(load[5 - first]) >= int(0.6 * h.shape[0])
    want = highest(ref.moe_ffn)(
        h, share_of(p, first, count), held=held,
        top_k=cfg.num_experts_per_token, scale=cfg.routed_scaling_factor)
    np.testing.assert_allclose(y[0], want, atol=2e-5)


def test_routed_experts_gradients_match_the_reference():
    cfg, p, h = expert_layer(seed=2)
    e = share_of(p, 4, 4)["experts"]

    def got(e, h):
        return jnp.sum(jnp.square(moe_lib.routed_experts(
            e, h, cfg.routing, (4, 4))[0]))

    @highest
    def want(e, h):
        return jnp.sum(jnp.square(ref.routed_experts(
            h, e, held=(4, 4), top_k=cfg.num_experts_per_token,
            scale=cfg.routed_scaling_factor)))

    g, g_want = jax.grad(got, (0, 1))(e, h), jax.grad(want, (0, 1))(e, h)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))


def test_load_stats():
    stats = moe_lib.load_stats(np.array([[4, 4, 4, 4], [10, 2, 2, 2]]))
    assert stats == {"moe_held_assignments": 32.0,
                     "moe_max_over_mean_load": 2.5}


# -- (e) the five-layer model in the published pattern -----------------------

@pytest.fixture(scope="module")
def model():
    params = kl.init(jax.random.key(1), TINY)
    tokens = jax.random.randint(jax.random.key(2), (2, 128), 0,
                                TINY.vocab_size)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def test_tiny_is_the_published_pattern():
    kinds = [("kda" if TINY.is_kda(n) else "mla",
              "dense" if TINY.is_dense(n) else "moe")
             for n in TINY.layer_numbers]
    assert kinds == [("kda", "dense"), ("kda", "moe"), ("kda", "moe"),
                     ("mla", "moe"), ("kda", "moe")]
    axes = kl.param_logical_axes(TINY)
    shapes = jax.eval_shape(lambda k: kl.init(k, TINY), jax.random.key(0))
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert (jax.tree.structure(axes, is_leaf=is_axes)
            == jax.tree.structure(shapes))
    for a, s in zip(jax.tree.leaves(axes, is_leaf=is_axes),
                    jax.tree.leaves(shapes)):
        assert len(a) == len(s.shape)


def test_a_layer_cannot_be_of_both_kinds():
    with pytest.raises(ValueError, match="exactly one"):
        dataclasses.replace(TINY, kda_layers=(1, 2, 3, 4, 5))
    with pytest.raises(ValueError, match="no range"):
        dataclasses.replace(TINY, experts_held=(12, 8))


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_model_logprobs_match_the_reference(model, impl):
    params, tokens, _ = model
    cfg = dataclasses.replace(TINY, attention_impl=impl)
    got = jax.nn.log_softmax(
        jax.jit(lambda p, t: kl.apply(p, cfg, t))(params, tokens), -1)
    for i in range(tokens.shape[0]):
        want = ref.logprobs(config_dict(cfg), params, tokens[i])
        np.testing.assert_allclose(got[i], want, atol=5e-5)


def test_model_loss_and_every_gradient_match_the_reference(model):
    params, tokens, targets = model
    c = config_dict(TINY)

    def got(p):
        lp = jax.nn.log_softmax(kl.apply(p, TINY, tokens), -1)
        return -jnp.mean(jnp.take_along_axis(lp, targets[..., None], -1))

    def want(p):
        return -jnp.mean(jnp.stack([
            ref.token_logprobs(c, p, tokens[i], targets[i])
            for i in range(tokens.shape[0])]))

    (loss, g), (loss_want, g_want) = (jax.value_and_grad(f)(params)
                                      for f in (got, want))
    assert abs(float(loss) - ref.loss(c, params, tokens, targets)) < 1e-5
    assert abs(float(loss) - float(loss_want)) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(g_want)):
        np.testing.assert_allclose(
            a, b, atol=2e-4 * float(jnp.abs(b).max()) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (list, tuple)) else [value]:
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def scans(jaxpr, recomputing=False):
    """-> (length, reverse, inside a rematerialised computation) of
    every `scan` in `jaxpr`, at any depth."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            yield eqn.params["length"], eqn.params["reverse"], recomputing
        for inner in sub_jaxprs(eqn):
            yield from scans(inner, recomputing or name.startswith("remat"))


@jax.grad
def three_segment_step(params):
    """A loss on the tiny five-layer model at three segments of two
    chunks: the loop over segments is the only one of length 3."""
    tokens = jnp.zeros((1, 3 * TINY.kda_segment), jnp.int32)
    return jnp.mean(jnp.square(kl.hidden(params, TINY, tokens)))


def test_the_scan_runs_twice_a_step_and_never_in_the_recomputed_layer():
    """A step holds the loop over segments once a KDA layer going forward
    and once (reversed: the scan's own backward, which runs each segment
    again) inside that layer's rematerialised backward, which recomputes
    everything of the layer but the scan."""
    step = jax.make_jaxpr(three_segment_step)(
        kl.init(jax.random.key(1), TINY))
    over_segments = sorted(
        (reverse, recomputing) for length, reverse, recomputing
        in scans(step.jaxpr) if length == 3)
    n_kda = sum(TINY.is_kda(n) for n in TINY.layer_numbers)
    assert n_kda == 4
    assert over_segments == [(False, False)] * n_kda + [(True, True)] * n_kda


def test_four_layers_lower_one_forward_and_one_backward_scan():
    """What holds a training cell's set-up (PERF.md section 6, PR 32):
    the four KDA layers call the scan at one shape, and the step's
    module holds its forward and its backward once, each calling
    `_segment` as a function of its own, not once a layer. That rests on
    what JAX caches by identity: the three `jax.jit` objects of
    `ops/kda.py` and the one checkpoint policy of the model. A policy
    made per layer lowers `_forward` four times (11 loops); a `_segment`
    that is not jitted is differentiated operation by operation; the
    scan as the parent had it lowered to 25 loops."""
    text = jax.jit(three_segment_step).lower(
        kl.init(jax.random.key(1), TINY)).as_text()

    def functions(name):
        return len(re.findall(
            rf"func\.func private @{name}(_\d+)?\(", text))

    # the loop over segments and, inside `_segment`, the one over its
    # chunks; the same two in reverse and the chunks' again before them
    assert text.count("stablehlo.while") == 5
    assert (functions("_forward"), functions("_backward")) == (1, 1)
    assert functions("_segment") >= 1
    assert len(re.findall(r"call @_(forward|backward)\(", text)) == 8


@pytest.mark.parametrize("number, kept", [
    # o [segments, b, h, chunks, CHUNK, dv] and the states [segments, b,
    # h, dk, dv] of `ops.kda.SAVED`
    (2, {"f32[3,1,2,2,64,32]", "f32[3,1,2,32,32]"}),
    (4, set()),
], ids=["kda", "mla"])
def test_a_layers_checkpoint_keeps_the_scan_and_nothing_else(
        number, kept, capsys):
    assert TINY.is_kda(number) == bool(kept)
    params = kl.init(jax.random.key(1), TINY)
    x = jnp.zeros((1, 3 * TINY.kda_segment, TINY.hidden_size), jnp.float32)
    jax.ad_checkpoint.print_saved_residuals(
        lambda x, p: kl._layer(TINY, number)(x, p)[0], x,
        params["layers"][number - 1])
    lines = capsys.readouterr().out.splitlines()
    assert any("from the argument x" in line for line in lines)
    beyond = [line for line in lines if "from the argument" not in line]
    assert {line.split()[0] for line in beyond} == kept
    assert len(beyond) == len(kept)
    if kept:
        assert any(f"named '{kda_ops.SAVED[1]}'" in line for line in beyond)


def test_trainer_steps_lower_the_loss_and_count_the_loads(model):
    from kubeflow_tpu.controlplane.metrics import Registry
    from kubeflow_tpu.train.trainer import chunked_cross_entropy_from_hidden

    _, tokens, targets = model

    def loss_fn(params, toks, tgts, mask):
        h, load = kl.hidden_and_load(params, TINY, toks)
        return chunked_cross_entropy_from_hidden(
            h, kl.unembed_matrix(params, TINY), tgts, mask,
            num_chunks=4), {"moe_load": load}

    registry = Registry()
    trainer = Trainer(
        mesh=create_mesh(MeshSpec(data=1, fsdp=1, tensor=1),
                         devices=jax.devices()[:1]),
        apply_fn=lambda p, t: kl.apply(p, TINY, t),
        init_fn=lambda k: kl.init(k, TINY),
        logical_axes=kl.param_logical_axes(TINY),
        train_config=TrainConfig(learning_rate=1e-2, warmup_steps=2,
                                 total_steps=50),
        loss_fn=loss_fn, registry=registry)
    state = trainer.init(jax.random.key(0))
    losses = []
    for _ in range(5):
        state, loss = trainer.step(state, tokens, targets)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    load = np.asarray(trainer.last_aux["moe_load"])
    assert load.shape == (4, TINY.experts_held[1])
    assert "moe_held_assignments" in registry.render()
    assert registry.get("moe_held_assignments").value() == float(load.sum())
    assert registry.get("moe_max_over_mean_load").value() == pytest.approx(
        (load.max(1) / load.mean(1)).max())
    # the registry outlives the trainer: its collector reads the last
    # step's loads once more and goes
    del trainer, state
    gc.collect()
    registry.get("moe_held_assignments").set(0.0)
    registry.render()
    assert registry.get("moe_held_assignments").value() == float(load.sum())
    registry.get("moe_held_assignments").set(0.0)
    registry.render()
    assert registry.get("moe_held_assignments").value() == 0.0
