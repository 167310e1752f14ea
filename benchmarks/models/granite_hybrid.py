"""Model class `granite_hybrid` (granite-4.0-h-micro): a configuration
file's sizes -> the program's `GraniteHybridConfig` and engine, and the
arithmetic of the architecture (parameters, bytes, operations) worked
out from the file's own numbers, never asked of the program.

Serving only. The layers are `layer_types`, "mamba" or "attention";
there are no experts (`num_local_experts` 0): every layer's
feed-forward is the shared SwiGLU of `shared_intermediate_size`.
"""

from __future__ import annotations

from benchmarks.models import granite_hybrid_reference as reference
from benchmarks.models.llama import param_itemsize, rng_key  # noqa: F401

_DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


# -- arithmetic, from the configuration file alone ------------------------

def mamba_layers(c: dict) -> int:
    return c["layer_types"].count("mamba")


def attention_layers(c: dict) -> int:
    return c["layer_types"].count("attention")


def d_inner(c: dict) -> int:
    return c["mamba_n_heads"] * c["mamba_d_head"]


def conv_dim(c: dict) -> int:
    """Channels of the short convolution: x, B and C."""
    return d_inner(c) + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["shared_intermediate_size"]


def mamba_mixer_params(c: dict) -> int:
    """in_proj (z, xBC, dt), the convolution and its bias, dt_bias,
    A_log and D a head, the gated norm, out_proj."""
    d, h = c["hidden_size"], c["mamba_n_heads"]
    return (d * (d_inner(c) + conv_dim(c) + h)
            + conv_dim(c) * (c["mamba_d_conv"] + 1) + 3 * h
            + d_inner(c) + d_inner(c) * d)


def attention_mixer_params(c: dict) -> int:
    d = c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return 2 * d * q + 2 * d * kv


def embed_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def num_params(c: dict) -> int:
    """Each layer: its mixer, the MLP, two norms; the tied table; the
    final norm."""
    d = c["hidden_size"]
    per_layer = mlp_params(c) + 2 * d
    return (mamba_layers(c) * (mamba_mixer_params(c) + per_layer)
            + attention_layers(c) * (attention_mixer_params(c) + per_layer)
            + embed_params(c) + d)


def param_bytes(c: dict) -> int:
    return num_params(c) * param_itemsize(c)


def state_bytes_per_slot(c: dict) -> int:
    """One sequence's recurrent state over all Mamba layers, as it is
    stored: the SSM state and the convolution's last K - 1 inputs."""
    cells = (c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]
             + (c["mamba_d_conv"] - 1) * conv_dim(c))
    return mamba_layers(c) * cells * _DTYPE_BYTES[c["state_dtype"]]


def kv_token_bytes(c: dict) -> int:
    """K and V of one token over the attention layers."""
    return (2 * attention_layers(c) * c["num_key_value_heads"]
            * c["head_dim"] * _DTYPE_BYTES[c["activation_dtype"]])


def kv_block_bytes(c: dict) -> int:
    return kv_token_bytes(c) * c["batcher"]["kv_block_size"]


def _slots_decoding(counters: dict) -> float:
    return counters["decode_tokens"] / counters["decode_steps"]


def ssm_update_bytes_per_step(c: dict, counters: dict) -> float:
    """The least the recurrence moves a decode step, whatever
    implements it: each decoding slot's state read once and written
    once."""
    return 2.0 * _slots_decoding(counters) * state_bytes_per_slot(c)


def hybrid_decode_bytes_per_step(c: dict, counters: dict) -> float:
    """Bytes a whole decode step has to move: every weight once (the
    tied table too: it is the head's matrix), each decoding slot's
    recurrent state read and written once, and the K and V of the
    contexts of the slots that decode."""
    kv = (_slots_decoding(counters) * counters["mean_context_tokens"]
          * kv_token_bytes(c))
    return param_bytes(c) + ssm_update_bytes_per_step(c, counters) + kv


def ssd_flops_per_token(c: dict) -> float:
    """The recurrence's own operations a token, whatever implements
    it: a head a Mamba layer, the state's P x N cells are decayed (1),
    given the outer product dt x (x) B (2: multiply and add) and read
    out against C (2)."""
    return (5.0 * mamba_layers(c) * c["mamba_n_heads"] * c["mamba_d_head"]
            * c["mamba_d_state"])


# -- the program under test -----------------------------------------------

def program_config(c: dict):
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import granite_hybrid

    if c.get("hidden_act", "silu") != "silu":
        raise ValueError("serving/engine.py computes SwiGLU (silu) only")
    if c["num_local_experts"] or c["position_embedding_type"] != "nope":
        raise ValueError("granite_hybrid serves the expert-free, "
                         "position-free members of the family")
    if not (c["tie_word_embeddings"] and c["mamba_conv_bias"]
            and not c["mamba_proj_bias"] and not c["attention_bias"]):
        raise ValueError("a tied head, a biased convolution and "
                         "bias-free projections are what is computed")
    if d_inner(c) != c["mamba_expand"] * c["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not "
                         "mamba_expand x hidden_size")
    return granite_hybrid.GraniteHybridConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["shared_intermediate_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        layer_types=tuple(c["layer_types"]),
        mamba_n_heads=c["mamba_n_heads"], mamba_d_head=c["mamba_d_head"],
        mamba_d_state=c["mamba_d_state"], mamba_n_groups=c["mamba_n_groups"],
        mamba_d_conv=c["mamba_d_conv"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        norm_eps=float(c["rms_norm_eps"]),
        dtype=jnp.dtype(c["activation_dtype"]),
        param_dtype=jnp.dtype(c["torch_dtype"]),
        state_dtype=jnp.dtype(c["state_dtype"]))


def serving_engine(c: dict, seed: int):
    """(engine, params): parameters made on the device from the seed in
    one jitted call, in the dtype they are served in."""
    import jax

    from kubeflow_tpu.models import granite_hybrid
    from kubeflow_tpu.serving import engine as engine_lib

    cfg = program_config(c)
    params = jax.jit(lambda k: granite_hybrid.init(k, cfg))(rng_key(seed))
    eng = engine_lib.InferenceEngine(
        params, cfg, engine_lib.granite_hybrid_family(cfg),
        engine_lib.EngineConfig(**c["engine"]))
    return eng, params


# -- the plain reference ----------------------------------------------------

reference_token_logprobs = reference.token_logprobs


def logprob_tolerance(c: dict) -> float:
    """The largest difference allowed between a log-probability the
    served path reports and the float32 reference's; the reasons and
    the readings on both sides of it are the configuration file's
    `logprob_tolerance` entry's and PERF.md section 6's."""
    return float(c["logprob_tolerance"][c["activation_dtype"]])
