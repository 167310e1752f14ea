"""A temporary checkout with one `kimi_linear` cell at the CPU tests'
size: the repository's own manifest and per-layer metric files, the
published layer pattern (KDA + dense, KDA, KDA, MLA, KDA) at toy
widths, 4 of 16 experts held. Nothing here touches JAX."""

from __future__ import annotations

import json
import os
import shutil

import tiny_cells

CELL = "tiny-kimi.job"
TINY = {
    "model_class": "kimi_linear", "source": "models/kimi_linear.py TINY",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "first_layer": 1, "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "num_attention_heads": 2, "kv_lora_rank": 32,
    "q_lora_rank": None, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
    "v_head_dim": 32, "mla_use_nope": True,
    "linear_attn_config": {
        "full_attn_layers": [4, 8], "kda_layers": [1, 2, 3, 5, 6, 7],
        "head_dim": 32, "num_heads": 2, "short_conv_kernel_size": 4},
    "num_experts": 4, "experts_held": [4, 4], "num_experts_per_token": 4,
    "num_shared_experts": 1, "num_expert_group": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "routed_scaling_factor": 2.446,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "activation_dtype": "float32", "kda_gate_rank": 16,
    "kda_segment": 128,
    "reduced": {"num_hidden_layers": {"source": 8, "here": 5},
                "num_experts": {"source": 16, "here": 4}},
    "train": {"remat_policy": "full", "loss_chunks": 4,
              "train_config": {"warmup_steps": 2, "total_steps": 100}},
}
TRAFFIC = {"kind": "train", "seq_len": 128, "sequences_per_chip": 1}


def make_checkout(root: str) -> dict:
    """-> the manifest written to `root/BENCHMARK.json`: the one cell,
    reporting every training metric the repository's manifest has."""
    with open(os.path.join(tiny_cells.REPO, "BENCHMARK.json"),
              encoding="utf-8") as f:
        manifest = json.load(f)
    shutil.copytree(os.path.join(tiny_cells.REPO, "benchmarks", "layers"),
                    os.path.join(root, "benchmarks", "layers"))
    tiny_cells.write_json(
        os.path.join(root, "benchmarks/configs/tiny-kimi.json"), TINY)
    tiny_cells.write_json(
        os.path.join(root, "benchmarks/traffic/job.json"), TRAFFIC)
    manifest["configs"] = [{
        "name": "tiny-kimi", "source": "KIMI_LINEAR_TINY", "reduced": [],
        "file": "benchmarks/configs/tiny-kimi.json", "why": "CPU rehearsal"}]
    manifest["workloads"] = [{
        "name": CELL, "config": "tiny-kimi", "traffic": "job", "chips": 1,
        "why": "CPU rehearsal"}]
    real = "kimi-linear-48b.train-8k"
    for section in ("end_to_end", "per_layer"):
        for m in manifest[section]:
            if "workloads" in m:
                m["workloads"] = [CELL] if real in m["workloads"] else []
    tiny_cells.write_json(os.path.join(root, "BENCHMARK.json"), manifest)
    return manifest


def perturb_one_weight(params):
    """One leaf of one layer, KDA's decay scale of layer 2, off by a
    half: a tenth more decay, and nothing else differs."""
    layers = list(params["layers"])
    attn = {**layers[1]["attn"], "a_log": layers[1]["attn"]["a_log"] + 0.5}
    layers[1] = {**layers[1], "attn": attn}
    return {**params, "layers": layers}
