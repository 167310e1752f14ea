"""A new cell and a new per-layer metric are new files and manifest
entries alone: every manifest rule of `tests/benchmarks` holds on a
copy of the repository's manifest and data files that adds a cell (an
existing configuration under a new traffic file) and a per-layer metric
(a program counter that lists that cell alone, appended last), and no
file that was there changes but the manifest."""

from __future__ import annotations

import os
import shutil

import pytest

import manifest_rules
import tiny_cells
from benchmarks import harness

# names no later PR gives a cell, a traffic file or a metric of its own
NEW_CELL = "mistral-7b.new-cell-guard"
NEW_TRAFFIC = "new-cell-guard"
NEW_METRIC = "new-cell-guard.tokens_per_step"


def _files(root: str) -> dict[str, bytes]:
    out = {}
    for d, _, names in os.walk(root):
        for name in names:
            with open(os.path.join(d, name), "rb") as f:
                out[os.path.join(d, name)] = f.read()
    return out


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The copy, with the new cell and metric added as a later PR adds
    them."""
    root = str(tmp_path_factory.mktemp("bench_grown"))
    shutil.copy(os.path.join(tiny_cells.REPO, "BENCHMARK.json"), root)
    for d in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(tiny_cells.REPO, "benchmarks", d),
                        os.path.join(root, "benchmarks", d))
    before = _files(root)
    traffic_file = f"{root}/benchmarks/traffic/{NEW_TRAFFIC}.json"
    metric_file = f"{root}/benchmarks/layers/{NEW_METRIC}.json"
    assert traffic_file not in before and metric_file not in before

    traffic = harness.read_json(f"{root}/benchmarks/traffic/steady.json")
    traffic["arrivals"] = {**traffic["arrivals"], "clients": 8}
    tiny_cells.write_json(traffic_file, traffic)
    tiny_cells.write_json(
        metric_file,
        {"what": "decode tokens emitted per decode step",
         "reader": "counters.ratio",
         "args": {"num": "decode_tokens", "den": "decode_steps"}})
    manifest = manifest_rules.manifest(root)
    manifest["workloads"].append({
        "name": NEW_CELL, "config": "mistral-7b-serve",
        "traffic": NEW_TRAFFIC, "chips": 1,
        "why": "closed loop, 8 clients on 16 slots"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_tok_s" or m.get("moves") == "setup_s":
            m["workloads"].append(NEW_CELL)
    manifest["per_layer"].append({
        "name": NEW_METRIC, "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tok_s", "workloads": [NEW_CELL]})
    tiny_cells.write_json(f"{root}/BENCHMARK.json", manifest)

    for p, content in before.items():
        if not p.endswith("BENCHMARK.json"):
            with open(p, "rb") as f:
                assert f.read() == content, p
    return root


@pytest.mark.parametrize("rule", [
    "check_setup_metrics", "check_files_and_readers",
    "check_span_and_scope_metrics", "check_traced_serve_run"])
def test_every_manifest_rule_takes_a_new_cell_and_metric(
        grown, rule, tmp_path):
    root = grown
    if rule == "check_traced_serve_run":    # of the tiny checkout of it
        root = str(tmp_path / "tiny")
        tiny_cells.make_checkout(root, source=grown)
    getattr(manifest_rules, rule)(root)


def test_the_setup_rule_refuses_a_setup_metric_without_the_new_cell(
        grown, tmp_path):
    root = str(tmp_path / "short")
    shutil.copytree(grown, root)
    manifest = manifest_rules.manifest(root)
    for m in manifest["per_layer"]:
        if m["name"] == "setup.init_s":
            m["workloads"].remove(NEW_CELL)
    tiny_cells.write_json(f"{root}/BENCHMARK.json", manifest)
    with pytest.raises(AssertionError, match="setup.init_s"):
        manifest_rules.check_setup_metrics(root)
