"""The `kimi_linear` model class: a tiny cell through the harness on
the CPU, and the arithmetic ISSUE 28 states for the configuration."""

from __future__ import annotations

import json
import os

import pytest

import tiny_cells
import tiny_kimi
from benchmarks import harness
from benchmarks.models import kimi_linear as model

CONFIG = harness.read_json(os.path.join(
    tiny_cells.REPO, "benchmarks", "configs", "kimi-linear-48b-train.json"))
PEAK = harness.read_json(os.path.join(
    tiny_cells.REPO, "benchmarks", "peaks.json"))["kinds"]["TPU v5 lite"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_kimi"))
    return root, tiny_kimi.make_checkout(root)


@pytest.mark.parametrize("trace", [False, True])
def test_kimi_cell_runs_end_to_end(checkout, trace):
    root, _ = checkout
    line = tiny_cells.run(root, tiny_kimi.CELL, trace=trace, seconds=0.5)
    assert line["correct"] is True, line["problems"]
    assert line["attempted"] >= 3 and line["failed"] == 0
    if trace:
        # times and shares of a peak: nothing off the chip; the
        # counters are the program's own and read anywhere
        assert set(line["metrics"]) == {"train.step_ms",
                                        "moe.max_over_mean_load"}
        assert line["metrics"]["moe.max_over_mean_load"]["value"] >= 1.0
    else:
        assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    extra = line["extra"]
    assert abs(extra["first_loss"] - extra["reference_loss"]) < 1e-4
    assert model.trainer_gauge("moe_held_assignments") > 0


def test_reference_check_fails_on_one_perturbed_weight(checkout):
    root, _ = checkout
    line = tiny_cells.run(root, tiny_kimi.CELL, seconds=0.2,
                          tamper=tiny_kimi.perturb_one_weight)
    assert line["correct"] is False
    assert any("reference" in p for p in line["problems"])


def test_the_new_readers_say_nothing_for_a_model_class_without_them(
        tmp_path):
    """A cell of another model class may list these metrics: the
    readers ask a `llama` class, find no such function and leave the
    metric out."""
    from benchmarks.models import llama
    from benchmarks.readers import flops

    ctx = harness.Context(run=None, cell=None, model=llama, peaks=None)
    assert flops.trainer_gauge(ctx, name="moe_max_over_mean_load") is None
    assert flops.scope_flops_share(
        ctx, program="jit__step", scope="kda",
        flops="kda_flops_per_step") is None


# -- arithmetic ---------------------------------------------------------------

def test_parameters_at_the_source_and_at_the_cut():
    assert model.kda_attention_params(CONFIG) == 39_514_272
    assert model.mla_attention_params(CONFIG) == 29_114_880
    assert model.expert_params(CONFIG) == 7_077_888
    full = model.at_source(CONFIG)
    assert round(model.num_params(full) / 1e9, 1) == 49.1
    assert round(model.num_params(CONFIG) / 1e9, 3) == 1.282
    assert round(model.state_bytes(CONFIG) / 1e9, 2) == 10.26
    by_layer = [round(model.layer_params_of(CONFIG, n) / 1e6, 1)
                for n in model.layer_numbers(CONFIG)]
    assert by_layer == [103.2, 273.7, 273.7, 263.3, 273.7]
    assert model.num_params(CONFIG) == (
        model.other_params(CONFIG)
        + sum(model.layer_params_of(CONFIG, n) for n in (2, 3, 4, 5)))


def test_the_program_counts_the_same_parameters():
    from kubeflow_tpu.models import kimi_linear

    for c in (CONFIG, model.at_source(CONFIG)):
        assert (kimi_linear.num_params(model.program_config(c))
                == model.num_params(c))


def test_the_file_holds_the_catalog_entry_but_for_what_reduced_lists():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog, encoding="utf-8") as f:
        row = next(json.loads(line) for line in f
                   if '"Kimi-Linear-48B-A3B-Instruct"' in line)
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"])
    for key, cut in CONFIG["reduced"].items():
        assert cut["source"] == row["config"][key]
        assert cut["here"] == CONFIG[key]


def test_flops_per_token():
    flops = model.train_flops_per_token(CONFIG, 8192)
    assert flops == pytest.approx(2.69e9, rel=2e-3)
    assert model.kda_flops_per_token(CONFIG) == 3 * 114_688 * 32 * 4
    # a token meets one held expert on average: 8 x 32 / 256
    base = model.train_flops_per_token(CONFIG, 0) \
        - model.kda_flops_per_token(CONFIG)
    assert base / 6 == pytest.approx(357.0e6, rel=1e-3)


def test_shares_read_100_percent_at_the_peaks_own_bound(monkeypatch):
    """A step, or a scope, that takes exactly its operations over the
    peak reads 100 %: no slower chip time can pass it."""
    from benchmarks.readers import flops, roofline

    tokens, seq = 2 * 8192, 8192
    peak = PEAK["bf16_flops_per_s"]
    step_s = model.train_flops_per_token(CONFIG, seq) * tokens / peak
    assert 0.2 < step_s < 0.25          # 44 TFLOP a step at 197 TFLOP/s
    counters = {"tokens_per_step_per_chip": tokens, "seq_len": seq,
                "step_median_ms": 1e3 * step_s}
    cell = harness.Cell(name="c", chips=1, config=CONFIG, traffic={},
                        manifest={}, root="")
    run = harness.Run(end_to_end={}, counters=counters, attempted=1,
                      failed=0, problems=[])
    ctx = harness.Context(run=run, cell=cell, model=model, peaks=PEAK)
    assert roofline.train_mfu(ctx) == pytest.approx(100.0)

    pairs = 4 * tokens                  # a held expert a token a layer
    monkeypatch.setattr(model, "trainer_gauge", lambda name: pairs)
    need_s = {"kda_flops_per_step": model.kda_flops_per_step(
                  CONFIG, counters) / peak,
              "moe_experts_flops_per_step":
                  2 * 3 * model.expert_params(CONFIG) * pairs / peak}
    for fn, seconds in need_s.items():
        assert seconds < step_s
        monkeypatch.setattr(flops, "scope_ms",
                            lambda ctx, **kw: 1e3 * seconds)
        assert flops.scope_flops_share(
            ctx, program="jit__step", scope="s", flops=fn
        ) == pytest.approx(100.0)
