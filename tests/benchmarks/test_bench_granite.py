"""The `granite_hybrid` model class: a tiny serving cell through the
harness on the CPU, the new readers on a hand-made trace, and the
arithmetic ISSUE 36 states for the configuration."""

from __future__ import annotations

import importlib
import json
import os

import pytest

import tiny_cells
import tiny_granite
from benchmarks import harness, spans
from benchmarks.models import granite_hybrid as model

REAL = tiny_granite.REAL
CONFIG = harness.read_json(os.path.join(
    tiny_cells.REPO, "benchmarks", "configs",
    "granite-4.0-h-micro-serve.json"))
PEAKS = harness.read_json(os.path.join(
    tiny_cells.REPO, "benchmarks", "peaks.json"))["kinds"]["TPU v5 lite"]
NEW_METRICS = {"step.hybrid_decode_ms", "step.hybrid_decode_bw_share",
               "step.ssm_update_ms", "kernel.ssm_update_bw_share",
               "step.ssd_scan_ms", "kernel.ssd_scan_flops_share"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_granite"))
    return root, tiny_granite.make_checkout(root)


@pytest.mark.parametrize("trace", [False, True])
def test_granite_cell_runs_end_to_end(checkout, trace):
    root, _ = checkout
    line = tiny_cells.run(root, tiny_granite.CELL, trace=trace, seconds=1.0)
    assert line["correct"] is True, line["problems"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    if trace:
        # times and shares of a peak: nothing off the chip; the
        # counters are the program's own and read anywhere
        assert set(line["metrics"]) == {"sched.occupancy",
                                        "kv.peak_in_use_share"}
    else:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert line["extra"]["reference_worst_logprob_diff"] < 1e-5
    assert line["extra"]["decode_tokens"] > 0


def test_reference_check_fails_on_one_perturbed_weight(checkout):
    root, _ = checkout
    line = tiny_cells.run(root, tiny_granite.CELL, seconds=0.2,
                          tamper=tiny_granite.perturb_one_weight)
    assert line["correct"] is False
    assert any("reference" in p for p in line["problems"])


# -- the new readers --------------------------------------------------------

def _reader(name):
    spec = harness.read_json(os.path.join(
        tiny_cells.REPO, "benchmarks", "layers", name + ".json"))
    module, _, fn = spec["reader"].rpartition(".")
    return getattr(importlib.import_module(
        f"benchmarks.readers.{module}"), fn), spec.get("args", {})


def _context(model_class, peaks, counters=None):
    cell = harness.Cell(name="c", chips=1, config=CONFIG, traffic={},
                        manifest={}, root="")
    run = harness.Run(end_to_end={}, attempted=0, failed=0, problems=[],
                      counters=counters or {})
    return harness.Context(run=run, cell=cell, model=model_class,
                           peaks=peaks)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_every_new_reader_is_silent_where_there_is_nothing_to_read(
        name, monkeypatch):
    """Nothing off the chip; and nothing on it from a profile that
    holds neither the scopes nor the spans, as the parent's program
    writes none, nor for a model class without the counting functions
    (`tiny_cells` hands every metric it does not know to its `llama`
    cells, and the driver lays these files over the parent)."""
    from benchmarks.models import llama

    counters = {"traced_decode_steps": 8, "decode_steps": 8,
                "decode_tokens": 24, "mean_context_tokens": 30.0}
    reader, args = _reader(name)
    assert reader(_context(model, None, counters), **args) is None
    bare = spans.Profile(programs=[("jit__step(1)", 0.0, 1.0)],
                         ops=[("%a = f32[] add(", 0.0, 1.0)], lines=[])
    monkeypatch.setattr(spans, "load", lambda _dir: bare)
    for model_class in (model, llama):
        assert reader(_context(model_class, PEAKS, counters),
                      **args) is None


STEP, APPEND = 11, 22
BODY = "jit(_step)/while/body/closed_call/while/body/closed_call/"


def hlo(name, opcode):
    return f"%{name} = f32[8]{{0}} {opcode}(%p0)"


def test_the_new_readers_read_a_hand_made_trace(monkeypatch):
    """What the chip run does, on hand-made lists: two decode
    dispatches (8 steps counted) and one prefill slice of 200 valid
    tokens, with the profile's loading stubbed out."""
    from benchmarks import devtrace

    ops = [(hlo("f.1", "fusion"), 0.0, 0.1),       # the state's update
           (hlo("f.2", "fusion"), 0.1, 0.06),      # ... and its write
           (hlo("f.3", "fusion"), 0.16, 0.04),     # the MLP
           (hlo("f.1", "fusion"), 0.3, 0.1),
           (hlo("f.4", "fusion"), 0.5, 0.02)]      # the slice's scan
    prof = spans.Profile(
        programs=[(f"jit__step({STEP})", 0.0, 0.2),
                  (f"jit__step({STEP})", 0.3, 0.1),
                  (f"jit__append_rows({APPEND})", 0.5, 0.05)],
        ops=ops,
        op_names={
            (STEP, ops[0][0]): BODY + "ssm_update/mul:",
            (STEP, ops[1][0]): BODY + "ssm_update/scatter:",
            (STEP, ops[2][0]): BODY + "mlp/dot_general:",
            (APPEND, ops[4][0]): "jit(_append_rows)/while/body/closed_call/"
                                 "ssd_scan/while/body/dot_general:"},
        lines=[[("dispatch.prefill_chunk", 0.5, 0.05,
                 {"tokens": 200, "finish": 1})]])
    monkeypatch.setattr(spans, "load", lambda _dir: prof)
    counters = {"traced_decode_steps": 8, "decode_steps": 100,
                "decode_tokens": 6000, "mean_context_tokens": 500.0}
    ctx = _context(model, PEAKS, counters)
    ctx.run.trace = devtrace.DeviceTrace(
        busy_s=0.32, window_s=0.55, device_ops=[], idle_gaps=[],
        programs={"jit__step": [0.2, 0.1], "jit__append_rows": [0.05]})

    def read(name):
        reader, args = _reader(name)
        return reader(ctx, **args)

    state = 60 * model.state_bytes_per_slot(CONFIG) * 2
    whole = model.param_bytes(CONFIG) + state + 60 * 500 * 8192
    assert read("step.hybrid_decode_ms") == pytest.approx(1e3 * 0.3 / 8)
    assert read("step.hybrid_decode_bw_share") == pytest.approx(
        100 * whole / 819e9 / (0.3 / 8))
    assert read("step.ssm_update_ms") == pytest.approx(1e3 * 0.26 / 8)
    assert read("kernel.ssm_update_bw_share") == pytest.approx(
        100 * state / 819e9 / (0.26 / 8))
    assert read("step.ssd_scan_ms") == pytest.approx(20.0)
    assert read("kernel.ssd_scan_flops_share") == pytest.approx(
        100 * 200 * model.ssd_flops_per_token(CONFIG) / 197e12 / 0.02)


def test_each_new_metric_moves_what_the_cell_reports():
    manifest = harness.read_json(
        os.path.join(tiny_cells.REPO, "BENCHMARK.json"))
    added = {m["name"]: m for m in manifest["per_layer"]
             if m["name"] in NEW_METRICS}
    assert set(added) == NEW_METRICS
    reports = {m["name"] for m in manifest["end_to_end"]
               if REAL in m.get("workloads", [REAL])}
    assert reports == {"serve_tok_s", "setup_s"}
    for m in manifest["per_layer"]:
        if REAL in m.get("workloads", []):
            assert m["moves"] in reports, m["name"]
    for m in added.values():
        assert m["workloads"] == [REAL] and m["source"] == "device_trace"


# -- arithmetic ---------------------------------------------------------------

def test_parameters_and_bytes_as_the_issue_states_them():
    assert model.mamba_layers(CONFIG) == 36
    assert model.attention_layers(CONFIG) == 4
    assert model.mamba_mixer_params(CONFIG) == 25_847_232
    assert model.attention_mixer_params(CONFIG) == 10_485_760
    assert model.mlp_params(CONFIG) == 50_331_648
    assert model.num_params(CONFIG) == 3_191_396_096
    assert round(model.param_bytes(CONFIG) / 1e9, 2) == 6.38
    assert model.state_bytes_per_slot(CONFIG) == 38_688_768
    assert round(64 * model.state_bytes_per_slot(CONFIG) / 1e9, 2) == 2.48
    assert model.kv_token_bytes(CONFIG) == 8192
    # the pool: 2049 blocks of 64 cells
    assert round(2049 * model.kv_block_bytes(CONFIG) / 1e9, 2) == 1.07
    assert model.ssd_flops_per_token(CONFIG) == 5 * 36 * 64 * 64 * 128


def test_the_program_counts_the_same_parameters():
    from kubeflow_tpu.models import granite_hybrid

    assert (granite_hybrid.num_params(model.program_config(CONFIG))
            == model.num_params(CONFIG))


def test_the_file_holds_the_catalog_entry_whole():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog, encoding="utf-8") as f:
        row = next(json.loads(line) for line in f
                   if '"granite-4.0-h-micro"' in line)
    assert {k for k, v in row["config"].items() if CONFIG.get(k) != v} \
        == set()
    assert CONFIG["reduced"] == {}
    assert CONFIG["source"] == row["source_url"]


def test_shares_read_100_percent_at_the_peaks_own_bound():
    """A step that takes exactly its bytes over the bandwidth reads
    100 %: no slower chip time can pass it."""
    from benchmarks import devtrace
    from benchmarks.readers import state

    counters = {"traced_decode_steps": 10, "decode_steps": 50,
                "decode_tokens": 50 * 64, "mean_context_tokens": 400.0}
    need = model.hybrid_decode_bytes_per_step(CONFIG, counters)
    assert 11.5e9 < need < 11.7e9       # 6.38 + 2 x 2.48 + 0.21 GB
    ctx = _context(model, PEAKS, counters)
    step_s = need / PEAKS["hbm_bytes_per_s"]
    ctx.run.trace = devtrace.DeviceTrace(
        busy_s=1.0, window_s=1.0, device_ops=[], idle_gaps=[],
        programs={"jit__step": [10 * step_s]})
    assert state.program_bw_share(
        ctx, program="jit__step", count="traced_decode_steps",
        bytes="hybrid_decode_bytes_per_step") == pytest.approx(100.0)
