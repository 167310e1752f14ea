"""The main path's kernels compiled at real widths for a described (not
attached) TPU v5e: what Mosaic refuses, it refuses here, at no chip
time. Nothing runs, so nothing is said about results or times.

The topology is described inside a fixture and only there (libtpu loads
in the worker that runs this file, once); where it cannot be described
the tests skip. Keep such tests in this one file.

The serving step programs compile in about twenty seconds each at any
depth (their layers are a scan). The last case compiles a whole
training step (over a minute, where a kernel takes two seconds): it is
marked `slow`.
"""

from __future__ import annotations

import functools
import math
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from kubeflow_tpu.ops.pallas import flash_attention, force_interpret


# what a v5e's runtime leaves a program of its 16 GB: `bytes_limit` of
# `jax.devices()[0].memory_stats()` on the chip (PERF.md section 4)
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.fixture(scope="module")
def one_chip(chip):
    return SingleDeviceSharding(chip)


def compile_flash(sharding, *, b, s, n_q, n_kv, hd, hd_v):
    shapes = [jax.ShapeDtypeStruct((b, s, n, d), jnp.bfloat16,
                                   sharding=sharding)
              for n, d in ((n_q, hd), (n_kv, hd), (n_kv, hd_v))]

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, interpret=False).astype(jnp.float32)), argnums=(0, 1, 2))(
                q, k, v)

    # the kernels refuse any backend but the TPU; the compile is for one
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = jax.jit(grads).lower(*shapes).compile()
    return compiled.as_text()


@pytest.mark.parametrize("shape", [
    # mistral-7b.train: GQA 32/8 heads of 128 at 2048
    dict(b=2, s=2048, n_q=32, n_kv=8, hd=128, hd_v=128),
    # kimi-linear-48b.train-8k: latent attention after up-projection,
    # q/k heads of 192 beside v heads of 128 at 8192
    dict(b=2, s=8192, n_q=32, n_kv=32, hd=192, hd_v=128),
], ids=["gqa-128-128-2048", "mla-192-128-8192"])
def test_flash_forward_and_backward_compile_for_v5e(one_chip, shape):
    text = compile_flash(one_chip, **shape)
    for kernel in ("flash_attention_fwd", "flash_attention_dq",
                   "flash_attention_dkv"):
        assert kernel in text


def mistral_model(config):
    """`mistral-7b.steady`'s model cut to depth 2, which changes neither
    step program's body. -> (config, parameters' init, family)."""
    from benchmarks.models import llama as model
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import engine as engine_lib

    cfg = model.program_config(dict(config, num_hidden_layers=2))
    return cfg, llama.init, engine_lib.LLAMA_FAMILY


def granite_model(config, *, periods):
    """`granite-4.0-h-micro.decode-heavy`'s model cut to `periods` of its
    layer pattern (nine Mamba layers and an attention layer each), which
    changes no layer's body. -> as `mistral_model`."""
    from benchmarks.models import granite_hybrid as model
    from kubeflow_tpu.models import granite_hybrid
    from kubeflow_tpu.serving import engine as engine_lib

    cfg = model.program_config(dict(
        config, layer_types=config["layer_types"][:10 * periods]))
    return cfg, granite_hybrid.init, engine_lib.granite_hybrid_family(cfg)


def compile_serving_step(sharding, cell, build, case, *, steps,
                         num_blocks=None):
    """One of a serving cell's two step programs (`case`: "decode-step",
    `steps` decode steps a dispatch, or "prefill-slice"), compiled from
    shapes alone: the cell's widths, slots, block size and prefill
    slice, the model as `build` cuts it, and a pool of `num_blocks` (two
    slots' blocks where not given, which changes neither program's
    body). -> (the compiled program, the model's config, the
    `ContinuousEngine`, the slots' state as shapes)."""
    from benchmarks import harness
    from kubeflow_tpu.serving import engine as engine_lib
    from kubeflow_tpu.serving.continuous import ContinuousEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = harness.load_cell(root, cell).config
    cfg, init, family = build(config)
    batcher = config["batcher"]
    slots, block = batcher["max_slots"], batcher["kv_block_size"]
    if num_blocks is None:
        num_blocks = 1 + 2 * config["engine"]["max_len"] // block

    def described(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    def of(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rng = described(jax.eval_shape(lambda: jax.random.key(0)))
    params = described(jax.eval_shape(lambda k: init(k, cfg), rng))
    sp = engine_lib.SamplingParams(
        of((slots,), jnp.float32), of((slots,)), of((slots,), jnp.float32))
    # "auto" picks the Pallas kernels where the backend is a TPU and the
    # heads are a size they copy, and the chip's programs hold them
    # compiled, where the suite runs them interpreted (conftest.py)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            force_interpret(False):
        ce = ContinuousEngine(
            engine_lib.InferenceEngine(
                params, cfg, family,
                engine_lib.EngineConfig(**config["engine"])),
            max_slots=slots, block_size=block, num_blocks=num_blocks)
        st = described(jax.eval_shape(ce.init_slots))
        if case == "decode-step":
            lowered = ce._step_jit.lower(
                params, None, st, sp, rng, steps=steps)
        else:
            lowered = ce._append_jit.lower(
                params, None, st, of((1,)),
                of((1, batcher["prefill_chunk_tokens"])), of((1,)),
                of((1,), jnp.bool_), sp, rng)
        return lowered.compile(), cfg, ce, st


@pytest.mark.parametrize("case", ["decode-step", "prefill-slice"])
def test_qkv_weights_are_read_where_they_lie(one_chip, case):
    """The q, k and v projections read the stacked weights in the
    parameters' own layout, inside the product, as `wo` and the MLP do.
    While the head split's layout reached them (`(h @ w).reshape(heads)`,
    before PR 35), the decode program transposed the three stacks whole
    once a dispatch and both programs copied a transposed slice a layer
    before the product could start (PERF.md section 6, PR 35)."""
    compiled, cfg, ce, st = compile_serving_step(
        one_chip, "mistral-7b.steady", mistral_model, case, steps=4)
    text = compiled.as_text()
    layers, d = cfg.num_layers, cfg.hidden_size
    kv = cfg.num_kv_heads * cfg.head_dim
    # heads of 128: a pool cell lies a head a row, as the kernels take it
    assert st.k.shape == st.v.shape == (layers, ce.num_blocks, 64, 8, 128)
    assert "tpu_custom_call" in text        # the Pallas kernels are in it
    # the parameters' names and layout, which the rest reads by
    assert re.search(rf"%params__blocks____wq__\S* = bf16\[{layers},{d},{d}\]"
                     r"\{2,1,0", text)
    whole = re.findall(
        r"^.* copy\(%params__blocks____w[qkv]__.*$", text, re.M)
    assert not whole, whole
    # wq, wk or wv (and wo: wq's shape), a layer's slice or the stack,
    # in another order of dimensions than the parameter's
    relaid = [line.strip()[:160] for line in text.splitlines() if re.search(
        rf"= bf16\[(1|{layers}),{d},({d}|{kv})\]\{{(?!2,1,0)"
        rf"|= bf16\[{d},({d}|{kv})\]\{{(?!1,0)", line)]
    assert not relaid, relaid


def test_a_recurrent_state_is_updated_where_it_lies(one_chip):
    """`granite-4.0-h-micro.decode-heavy`'s decode program at the cell's
    widths and slots (one period of the layer pattern, a pool of two
    slots' blocks: neither changes a layer's body): the slots' state, 67
    MB a Mamba layer and 2.47 GB in all, rides the layer loops as a
    donated carry and is read and written in place. A `copy` of the
    stack, or of one layer's `[64, 64, 64, 128]`, would be a pass over
    it that the update does not need."""
    compiled, cfg, ce, st = compile_serving_step(
        one_chip, "granite-4.0-h-micro.decode-heavy",
        functools.partial(granite_model, periods=1), "decode-step", steps=4)
    text = compiled.as_text()
    # heads of 64: the resolver answers from the shape
    assert (ce.attention_impl, ce.prefill_impl) == ("xla", "xla")
    slots = ce.S
    heads, hd, state = (cfg.mamba_n_heads, cfg.mamba_d_head,
                        cfg.mamba_d_state)
    assert st.rec.ssm.shape == (9, slots, heads, hd, state)
    # the stack is a parameter and a result of the program, aliased
    assert re.search(
        rf"bf16\[9,{slots},{heads},{hd},{state}\]\S* parameter\(", text)
    copies = [line.strip()[:160] for line in text.splitlines() if re.search(
        rf"= (bf16|f32)\[(9,)?{slots},{heads},{hd},{state}\]\S* copy\(",
        line)]
    assert not copies, copies


@pytest.mark.parametrize("case", ["decode-step", "prefill-slice"])
def test_the_kv_pool_is_written_and_read_where_it_lies(one_chip, case):
    """`granite-4.0-h-micro.decode-heavy`'s two step programs at the
    cell's widths, slots, `chunk` and pool of 2049 blocks, two periods
    of the layer pattern (two attention layers, so a layer's slice of
    the pool and the whole stack differ): the pool, 268 MB a layer for K
    and as much for V, is a parameter and an aliased result, rides the
    loops in the layout it rests in, and no `copy` or `transpose` yields
    an array of its size. While a cell was `[8, 64]` (heads of 64: half
    a lane tile) the pool rested with the block index minor, and both
    programs copied it whole on entry and on exit, the slice program
    once more after each layer's scatter: 27 % of the device's time in
    the cell (PERF.md section 6, PR 37)."""
    compiled, cfg, ce, st = compile_serving_step(
        one_chip, "granite-4.0-h-micro.decode-heavy",
        functools.partial(granite_model, periods=2), case, steps=2,
        num_blocks=2049)
    text = compiled.as_text()
    assert st.k.shape == st.v.shape == (2, 2049, 64, 1, 512)
    pool = "2,2049,64,1,512"
    # the two pools are parameters of the program and aliased results
    params = re.findall(
        rf"%(\S+) = bf16\[{pool}\]\S* parameter\((\d+)\)", text)
    assert len(params) == 2, params
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    for _, number in params:
        assert re.search(rf"\({number}, \{{\}}, may-alias\)",
                         aliased.group(1)), (number, aliased.group(1))
    # no copy or transpose of an array as large as a layer's pool or the
    # stack's, whatever its shape
    per_layer = math.prod(st.k.shape[1:])
    moved = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)
        if m and math.prod(map(int, m.group(1).split(","))) in (
                per_layer, 2 * per_layer):
            moved.append(line.strip()[:200])
    assert not moved, moved
    plan = compiled.memory_analysis()
    assert plan.temp_size_in_bytes < 1e9, plan.temp_size_in_bytes


@pytest.mark.slow
def test_the_kimi_step_fits_a_v5e(chip):
    """`kimi-linear-48b.train-8k`'s step as the benchmark builds it, at
    the cell's shapes: what the compiler plans for arguments, results
    and temporaries has to leave the chip's limit room. The KDA layers
    keep their scans' outputs and segment states across their
    rematerialisation (671 MB): a change to what is kept, or to what a
    segment's backward holds at once, shows here first."""
    from benchmarks import harness
    from benchmarks.models import kimi_linear as model
    from kubeflow_tpu import parallel

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = harness.load_cell(root, "kimi-linear-48b.train-8k")
    mesh_from_env = parallel.mesh_from_env
    with mock.patch.object(parallel, "mesh_from_env",
                           lambda: mesh_from_env(devices=[chip])):
        trainer = model.trainer(cell.config)
    shape = (cell.traffic["sequences_per_chip"], cell.traffic["seq_len"])

    def described(dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=trainer.batch_sharding)

    # the flash kernel refuses any backend but the TPU
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            jax.set_mesh(trainer.mesh):
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(trainer._init,
                           jax.eval_shape(lambda: jax.random.key(0))),
            trainer.state_shardings)
        plan = trainer._jit_step.__wrapped__.lower(
            state, described(jnp.int32), described(jnp.int32),
            described(jnp.float32)).compile().memory_analysis()
    planned = (plan.argument_size_in_bytes + plan.output_size_in_bytes
               - plan.alias_size_in_bytes + plan.temp_size_in_bytes)
    assert planned < V5E_BYTES_LIMIT, f"{planned / 1e9:.3f} GB"
