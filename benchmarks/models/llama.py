"""Model class `llama`: a configuration file's sizes -> the program's
`LlamaConfig`, engine and trainer, and the arithmetic of the
architecture (parameters, FLOPs, bytes) worked out from the file's own
numbers, never asked of the program.

A model class the benchmark does not know yet is one new file here,
named by a configuration's `model_class`, with the same functions.
"""

from __future__ import annotations

from benchmarks import reference

_DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


# -- arithmetic, from the configuration file alone ------------------------

def layer_params(c: dict) -> int:
    d, i = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * i + 2 * d


def embed_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def other_params(c: dict) -> int:
    """Outside the blocks: embedding, an untied head, the final norm."""
    tables = 1 if c["tie_word_embeddings"] else 2
    return tables * embed_params(c) + c["hidden_size"]


def num_params(c: dict) -> int:
    return c["num_hidden_layers"] * layer_params(c) + other_params(c)


def param_itemsize(c: dict) -> int:
    return _DTYPE_BYTES[c["torch_dtype"]]


def param_bytes(c: dict) -> int:
    return num_params(c) * param_itemsize(c)


def train_flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs a trained token needs, forward and backward:
    6 x the parameters that sit in a matrix multiplication (the
    embedding lookup is free; an untied head is a matmul) plus the
    attention scores and values at `seq` (12 x layers x heads x
    head_dim x seq, causal masking not discounted). Recomputation is
    not counted. As `bench.py`'s `model_flops_per_token`."""
    n_matmul = num_params(c)
    if not c["tie_word_embeddings"]:
        n_matmul -= embed_params(c)
    attn = (12 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * seq)
    return 6.0 * n_matmul + attn


def kv_token_bytes(c: dict) -> int:
    """K and V of one token over all layers, in the activation dtype."""
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * _DTYPE_BYTES[c["activation_dtype"]])


def kv_block_bytes(c: dict) -> int:
    return kv_token_bytes(c) * c["batcher"]["kv_block_size"]


def decode_bytes_per_step(c: dict, kv_tokens: float) -> float:
    """Bytes one decode step has to read: every weight once (the
    embedding table is a gather of a few rows and is left out) and the
    K and V of the `kv_tokens` cached tokens the batch attends to. As
    the byte model of `bench.py`'s `bench_decode_continuous`, less the
    embedding table."""
    weights = param_bytes(c) - embed_params(c) * param_itemsize(c)
    return weights + kv_tokens * kv_token_bytes(c)


# -- the program under test -----------------------------------------------

def program_config(c: dict, **overrides):
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import llama

    if c.get("hidden_act", "silu") != "silu":
        raise ValueError("models/llama.py computes SwiGLU (silu) only")
    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        sliding_window=c.get("sliding_window"),
        dtype=jnp.dtype(c["activation_dtype"]),
        param_dtype=jnp.dtype(c["torch_dtype"]), **overrides)


def rng_key(seed: int):
    """A key from a seed of any size (the driver's pass 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def serving_engine(c: dict, seed: int):
    """(engine, params): parameters made on the device from the seed in
    one jitted call, in the dtype they are served in."""
    import jax

    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import engine as engine_lib

    cfg = program_config(c)
    params = jax.jit(lambda k: llama.init(k, cfg))(rng_key(seed))
    eng = engine_lib.InferenceEngine(
        params, cfg, engine_lib.LLAMA_FAMILY,
        engine_lib.EngineConfig(**c["engine"]))
    return eng, params


def trainer(c: dict):
    """The `Trainer` as `tools/smoke_train.py` builds it: the mesh the
    environment names, the chunked cross-entropy that never holds the
    [b, s, vocab] logits, its `TrainConfig`."""
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.parallel import mesh_from_env
    from kubeflow_tpu.train import TrainConfig, Trainer
    from kubeflow_tpu.train.trainer import chunked_cross_entropy_from_hidden

    t = c["train"]
    cfg = program_config(c, remat_policy=t["remat_policy"])

    def chunked_loss(params, tokens, targets, mask):
        h = llama.hidden(params, cfg, tokens)
        return chunked_cross_entropy_from_hidden(
            h, llama.unembed_matrix(params, cfg), targets, mask,
            num_chunks=t["loss_chunks"])

    return Trainer(
        mesh=mesh_from_env(),
        apply_fn=lambda p, tok: llama.apply(p, cfg, tok),
        init_fn=lambda k: llama.init(k, cfg),
        logical_axes=llama.param_logical_axes(cfg),
        train_config=TrainConfig(**t["train_config"]),
        loss_fn=chunked_loss)


# -- the plain reference ----------------------------------------------------

reference_token_logprobs = reference.token_logprobs
reference_loss = reference.loss


def logprob_tolerance(c: dict) -> float:
    """The largest difference allowed between a log-probability the
    served path reports and the float32 reference's.

    bfloat16 activations: the served path rounds every activation to 8
    bits of mantissa (relative 2**-8) while the reference keeps 24, and
    the rounding accumulates over the depth. On the chip at Mistral-7B
    widths and depth 16 the largest difference read over 16 runs was
    0.022 (PERF.md section 6, PR 24); 0.1 is between four and five
    times that. Weights or activations held in 8 bits (int8, fp8:
    relative 2**-4 and worse) err sixteen times as much and fail it.
    float32 activations (the CPU rehearsal): only the order of
    summation differs."""
    return 0.1 if c["activation_dtype"] == "bfloat16" else 2e-3


def loss_tolerance(c: dict) -> float:
    """The largest difference allowed between the first step's loss and
    the reference's on the same batch and parameters. The loss is a
    mean over thousands of tokens, so per-token rounding averages out
    and the bound is far tighter than a single log-probability's: on
    the chip at depth 6 and 2 x 2048 tokens the largest difference read
    over 14 runs was 0.0003 on a loss of 10.8 (PERF.md section 6, PR
    24); 0.002 is six times that."""
    return 2e-3
