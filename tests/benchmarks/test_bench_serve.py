"""Rehearsal of the `serve` kind of cell on the CPU at a tiny size:
closed-loop and Poisson arrivals end to end, a traced run, the
reference check failing on one perturbed weight, and a new cell added
by files alone. Times read here say nothing about a chip and are only
checked for being there."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import tiny_cells

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_serve"))
    return root, tiny_cells.make_checkout(root)


def _names(manifest, section, cell):
    return {m["name"] for m in manifest[section]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", tiny_cells.SERVE_CELLS)
def test_serve_cell_runs_end_to_end(checkout, cell):
    root, manifest = checkout
    line = tiny_cells.run(root, cell)
    assert LINE_KEYS <= set(line)
    json.dumps(line)                       # the line is printable
    assert line["correct"] is True, line["problems"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == _names(manifest, "end_to_end", cell)
    assert ("serve_tok_s" in line["metrics"]) == (cell == "tiny.closed")
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["device"]["platform"] == "cpu"
    assert line["extra"]["reference_worst_logprob_diff"] < 1e-3
    # every request due in the window got its first token
    assert line["extra"]["n_ttft"] == line["attempted"]


def test_traced_serve_run_reports_the_counted_layer_metrics(checkout):
    root, manifest = checkout
    line = tiny_cells.run(root, "tiny.closed", trace=True)
    assert line["correct"] is True, line["problems"]
    got = set(line["metrics"])
    # the CPU has no device plane: the trace readers find nothing and
    # their metrics are left out, the counted ones are there
    assert got == {"sched.occupancy", "sched.recompiles",
                   "kv.peak_in_use_share"}
    assert got <= _names(manifest, "per_layer", "tiny.closed")
    assert 0 < line["metrics"]["sched.occupancy"]["value"] <= 100
    assert line["metrics"]["sched.recompiles"]["value"] == 0
    assert "busy_s" not in line["device"]


def test_reference_check_fails_on_one_perturbed_weight(checkout):
    root, _ = checkout
    line = tiny_cells.run(root, "tiny.closed", seconds=0.3,
                          tamper=tiny_cells.perturb_one_weight)
    assert line["correct"] is False
    assert any("log-probabilities differ" in p for p in line["problems"])


def test_a_cell_a_config_and_a_layer_metric_are_added_by_files_alone(
        checkout, tmp_path):
    """The data-drivenness the contract asks for: one new config file,
    one new traffic file, one new layer-metric file and new manifest
    entries; no file that was there is edited."""
    src, _ = checkout
    root = str(tmp_path / "copy")
    shutil.copytree(src, root)
    before = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()

    shallow = {**tiny_cells.TINY, "num_hidden_layers": 1}
    tiny_cells.write_json(f"{root}/benchmarks/configs/shallow.json", shallow)
    tiny_cells.write_json(
        f"{root}/benchmarks/traffic/short.json",
        {**tiny_cells.TRAFFIC["closed"],
         "arrivals": {"process": "closed", "clients": 2},
         "output_tokens": {"dist": "fixed", "value": 4}})
    tiny_cells.write_json(
        f"{root}/benchmarks/layers/sched.tokens_per_step.json",
        {"reader": "counters.ratio",
         "args": {"num": "decode_tokens", "den": "decode_steps"}})
    manifest = json.loads(before[f"{root}/BENCHMARK.json"])
    manifest["configs"].append({
        "name": "shallow", "source": "LLAMA_TINY", "reduced": [],
        "file": "benchmarks/configs/shallow.json", "why": "one layer"})
    manifest["workloads"].append({
        "name": "shallow.short", "config": "shallow", "traffic": "short",
        "chips": 1, "why": "short outputs"})
    manifest["per_layer"].append({
        "name": "sched.tokens_per_step", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tok_s", "workloads": ["shallow.short"]})
    for m in manifest["end_to_end"]:
        if m["name"] in ("serve_tok_s", "ttft_p90_ms"):
            m["workloads"].append("shallow.short")
    tiny_cells.write_json(f"{root}/BENCHMARK.json", manifest)

    line = tiny_cells.run(root, "shallow.short", trace=True, seconds=0.5)
    assert line["correct"] is True, line["problems"]
    assert set(line["metrics"]) == {"sched.tokens_per_step"}
    assert 0 < line["metrics"]["sched.tokens_per_step"]["value"] <= 2
    line = tiny_cells.run(root, "shallow.short", seconds=0.5)
    assert set(line["metrics"]) == {"serve_tok_s", "ttft_p90_ms", "setup_s"}
    for p, content in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == content, p


def test_off_the_chip_the_command_fails_and_prints_no_result():
    manifest = json.load(open(os.path.join(tiny_cells.REPO, "BENCHMARK.json")))
    proc = subprocess.run(
        [sys.executable, *manifest["command"][1:], "--workload",
         manifest["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tiny_cells.REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU only" in proc.stderr
