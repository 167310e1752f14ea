"""The manifest's rules, each a function of a checkout's root, so that
the tests hold them on the repository and on a checkout that adds a
cell and a per-layer metric by new files and manifest entries alone
(`test_bench_new_cell.py`). Nothing here touches JAX until a tiny cell
runs."""

from __future__ import annotations

import importlib
import json
import os

import tiny_cells
from benchmarks import harness

SETUP_METRICS = {"setup.before_first_program_s": "program_span",
                 "setup.trace_lower_s": "program_span",
                 "setup.backend_s": "program_span",
                 "setup.cache_misses": "program_counter",
                 "setup.programs": "program_counter",
                 "setup.init_s": "program_span"}
# the per-layer metrics `benchmarks/readers/spans.py` and `scopes.py` serve
SPAN_METRICS = {"sched.iter_host_ms", "sched.queue_wait_ms",
                "sched.prefill_wait_share", "device.idle_host_share",
                "train.host_gap_ms"}
SCOPE_METRICS = {"step.kv_write_ms", "kernel.paged_attention_ms",
                 "kernel.paged_attention_bw_share",
                 "kernel.prefill_append_ms", "train.flash_attention_ms"}
# what a traced tiny serve cell reports off the chip: the program's
# counters and the tail the window took on the host's clock; the trace
# readers find no device plane and are left out
COUNTED = {"sched.occupancy", "sched.recompiles", "kv.peak_in_use_share",
           "sched.ttft_p90_ms"}


def manifest(root: str) -> dict:
    return harness.read_json(os.path.join(root, "BENCHMARK.json"))


def layer_spec(root: str, name: str) -> dict:
    return harness.read_json(os.path.join(
        root, "benchmarks", "layers", name + ".json"))


def reader(root: str, name: str):
    """-> (the reader function, its arguments) of a per-layer metric."""
    spec = layer_spec(root, name)
    module, _, fn = spec["reader"].rpartition(".")
    return getattr(importlib.import_module(
        f"benchmarks.readers.{module}"), fn), spec.get("args", {})


def _reports(root: str, cell: str) -> set[str]:
    return {e["name"] for e in
            harness.load_cell(root, cell).metrics("end_to_end")}


def check_setup_metrics(root: str) -> None:
    """The metrics that move `setup_s` are the six `setup.*` ones, and
    each lists every cell: every cell reports `setup_s`."""
    bench = manifest(root)
    moved = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
    assert {m["name"] for m in moved} == set(SETUP_METRICS)  # and no other
    cells = {w["name"] for w in bench["workloads"]}
    for m in moved:
        assert set(m["workloads"]) == cells, m["name"]
        assert m["layer"] == "start-up" and m["better"] == "lower"
        assert m["source"] == SETUP_METRICS[m["name"]]
        assert m["unit"] == ("s" if m["name"].endswith("_s") else "count")
        fn, _ = reader(root, m["name"])
        assert fn.__module__ == "benchmarks.readers.setup" and callable(fn)
        for w in m["workloads"]:
            assert m["moves"] in _reports(root, w), (m["name"], w)


def check_files_and_readers(root: str) -> None:
    """Every cell finds its configuration, traffic, kind and metrics;
    every per-layer metric its data file and reader."""
    bench = manifest(root)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = harness.load_cell(root, w["name"])
        assert importlib.import_module(
            f"benchmarks.kinds.{cell.traffic['kind']}").run
        names = _reports(root, w["name"])
        assert "setup_s" in names and len(names) >= 2
        assert cell.metrics("per_layer")
        for m in cell.metrics("per_layer"):
            assert m["moves"] in names, (m["name"], w["name"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert callable(reader(root, m["name"])[0])
    assert len(json.dumps(bench)) < 64 * 1024


def check_span_and_scope_metrics(root: str) -> None:
    """Each span and scope metric is there, from its source, moves a
    metric its cells report, and lists cells of its own kind: a
    `train.*` metric training cells, every other one serving cells."""
    added = {m["name"]: m for m in manifest(root)["per_layer"]
             if m["name"] in SPAN_METRICS | SCOPE_METRICS}
    assert set(added) == SPAN_METRICS | SCOPE_METRICS
    for name, m in added.items():
        assert m["source"] == ("program_span" if name in SPAN_METRICS
                               else "device_trace")
        assert m["workloads"], name
        kind = "train" if name.startswith("train") else "serve"
        for w in m["workloads"]:
            assert harness.load_cell(root, w).traffic["kind"] == kind, \
                (name, w)
            assert m["moves"] in _reports(root, w), (name, w)
        assert callable(reader(root, name)[0])


def check_traced_serve_run(root: str) -> None:
    """A traced run of the tiny closed cell of a checkout made by
    `tiny_cells.make_checkout` reports the counted metrics, and only
    those, off the chip."""
    line = tiny_cells.run(root, "tiny.closed", trace=True)
    assert line["correct"] is True, line["problems"]
    got = set(line["metrics"])
    assert got == COUNTED
    assert got <= {m["name"] for m in manifest(root)["per_layer"]
                   if "tiny.closed" in m.get("workloads", ["tiny.closed"])}
    assert 0 < line["metrics"]["sched.occupancy"]["value"] <= 100
    assert line["metrics"]["sched.recompiles"]["value"] == 0
    assert line["metrics"]["sched.ttft_p90_ms"]["value"] > 0
    assert "busy_s" not in line["device"]
