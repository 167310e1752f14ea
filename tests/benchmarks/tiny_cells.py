"""A temporary checkout for the rehearsal tests: the repository's own
manifest and per-layer metric files, with cells at `LLAMA_TINY` widths
in place of the real ones. Nothing here touches JAX."""

from __future__ import annotations

import json
import os
import shutil
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {
    "model_class": "llama", "source": "models/llama.py LLAMA_TINY",
    "hidden_size": 128, "intermediate_size": 384, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "rope_theta": 500000.0, "rms_norm_eps": 1e-5,
    "sliding_window": None, "tie_word_embeddings": False,
    "torch_dtype": "float32", "hidden_act": "silu",
    "activation_dtype": "float32",
    "reduced": {"num_hidden_layers": {"source": 2, "here": 2}},
    "engine": {"max_len": 128},
    "batcher": {"max_slots": 4, "kv_block_size": 8,
                "prefill_chunk_tokens": 16},
    "reference_check": {"prompt_lengths": [10, 40], "max_new": 8},
    "train": {"remat_policy": "full", "loss_chunks": 4,
              "train_config": {"warmup_steps": 2, "total_steps": 100}},
}
LENGTHS = {
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 4, "max": 64},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 16},
    "cycle": 16, "ramp_s": 0.3, "drain_s": 30,
}
TRAFFIC = {
    "closed": {"kind": "serve",
               "arrivals": {"process": "closed", "clients": 4}, **LENGTHS},
    "open": {"kind": "serve",
             "arrivals": {"process": "poisson", "rate_rps": 8.0}, **LENGTHS},
    "job": {"kind": "train", "seq_len": 64, "sequences_per_chip": 1},
}
SERVE_CELLS = ["tiny.closed", "tiny.open"]


def write_json(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


def make_checkout(root: str) -> dict:
    """-> the manifest written to `root/BENCHMARK.json`."""
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    shutil.copytree(os.path.join(REPO, "benchmarks", "layers"),
                    os.path.join(root, "benchmarks", "layers"))
    write_json(os.path.join(root, "benchmarks/configs/tiny.json"), TINY)
    for name, mix in TRAFFIC.items():
        write_json(os.path.join(root, f"benchmarks/traffic/{name}.json"), mix)
    manifest["configs"] = [{
        "name": "tiny", "source": "LLAMA_TINY", "reduced": [],
        "file": "benchmarks/configs/tiny.json", "why": "CPU rehearsal"}]
    manifest["workloads"] = [
        {"name": f"tiny.{t}", "config": "tiny", "traffic": t, "chips": 1,
         "why": "CPU rehearsal"} for t in TRAFFIC]
    for section in ("end_to_end", "per_layer"):
        for m in manifest[section]:
            if "workloads" not in m:
                continue
            if m["name"].startswith("train"):
                m["workloads"] = ["tiny.job"]
            elif m["name"] == "serve_tok_s":
                # at a fixed rate it is only the offered load: the
                # open-loop cell reports the two latencies alone
                m["workloads"] = ["tiny.closed"]
            else:
                m["workloads"] = list(SERVE_CELLS)
    write_json(os.path.join(root, "BENCHMARK.json"), manifest)
    return manifest


def run(root: str, name: str, *, trace: bool = False, seconds: float = 1.0,
        seed: int = 2**33 + 5, tamper=None) -> dict:
    """One run of a cell of the temporary checkout, on the CPU, through
    the function `run.py`'s `main()` calls once it has found a chip.
    The cells are one-chip cells, so the run is held to the first of
    the suite's eight virtual CPU devices: a rehearsal of the harness
    needs no mesh, and eight device threads under a loaded test run
    only add collectives that can time out."""
    from unittest import mock

    import jax

    from benchmarks import harness

    first = jax.devices()[:1]
    with mock.patch.object(jax, "devices", lambda *a, **kw: first):
        return harness.run_cell(
            harness.load_cell(root, name), seed=seed, seconds=seconds,
            trace=trace, peaks=None, t_start=time.perf_counter(),
            tamper=tamper)


def perturb_one_weight(params):
    """The final norm's scale, one leaf of the tree, off by a half."""
    return {**params, "final_norm": params["final_norm"] + 0.5}
