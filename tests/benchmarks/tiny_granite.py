"""A temporary checkout with one `granite_hybrid` serving cell at the
CPU tests' size: the repository's own manifest and per-layer metric
files, the model's two kinds of layer (Mamba, Mamba, attention, Mamba)
at toy widths. Nothing here touches JAX."""

from __future__ import annotations

import json
import os
import shutil

import tiny_cells

CELL = "tiny-granite.closed"
REAL = "granite-4.0-h-micro.decode-heavy"
TINY = {
    "model_class": "granite_hybrid",
    "source": "models/granite_hybrid.py GRANITE_HYBRID_TINY",
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "shared_intermediate_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "logits_scaling": 8, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 8, "mamba_proj_bias": False,
    "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True, "vocab_size": 256,
    "torch_dtype": "float32", "activation_dtype": "float32",
    "state_dtype": "float32", "reduced": {},
    "engine": {"max_len": 128},
    "batcher": {"max_slots": 4, "kv_block_size": 8,
                "prefill_chunk_tokens": 16, "kv_pool_blocks": 65},
    "reference_check": {"prompt_lengths": [10, 40], "max_new": 8},
    "logprob_tolerance": {"float32": 1e-5},
}
TRAFFIC = dict(tiny_cells.TRAFFIC["closed"])


def make_checkout(root: str) -> dict:
    """-> the manifest written to `root/BENCHMARK.json`: the one cell,
    reporting every metric the repository's manifest gives the real
    one."""
    with open(os.path.join(tiny_cells.REPO, "BENCHMARK.json"),
              encoding="utf-8") as f:
        manifest = json.load(f)
    shutil.copytree(os.path.join(tiny_cells.REPO, "benchmarks", "layers"),
                    os.path.join(root, "benchmarks", "layers"))
    tiny_cells.write_json(
        os.path.join(root, "benchmarks/configs/tiny-granite.json"), TINY)
    tiny_cells.write_json(
        os.path.join(root, "benchmarks/traffic/closed.json"), TRAFFIC)
    manifest["configs"] = [{
        "name": "tiny-granite", "source": "GRANITE_HYBRID_TINY",
        "reduced": [], "file": "benchmarks/configs/tiny-granite.json",
        "why": "CPU rehearsal"}]
    manifest["workloads"] = [{
        "name": CELL, "config": "tiny-granite", "traffic": "closed",
        "chips": 1, "why": "CPU rehearsal"}]
    for section in ("end_to_end", "per_layer"):
        for m in manifest[section]:
            if "workloads" in m:
                m["workloads"] = [CELL] if REAL in m["workloads"] else []
    tiny_cells.write_json(os.path.join(root, "BENCHMARK.json"), manifest)
    return manifest


def perturb_one_weight(params):
    """One leaf of one layer, the third Mamba layer's `A_log`, off by a
    half: that layer forgets faster, and nothing else differs."""
    mamba = params["mamba_blocks"]
    return {**params, "mamba_blocks": {
        **mamba, "A_log": mamba["A_log"].at[2].add(0.5)}}
