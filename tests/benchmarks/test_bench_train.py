"""Rehearsal of the `train` kind of cell on the CPU at a tiny size."""

from __future__ import annotations

import pytest

import tiny_cells


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_train"))
    return root, tiny_cells.make_checkout(root)


@pytest.mark.parametrize("trace", [False, True])
def test_train_cell_runs_end_to_end(checkout, trace):
    root, _ = checkout
    line = tiny_cells.run(root, "tiny.job", trace=trace, seconds=0.5)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line["problems"]
    assert line["attempted"] >= 3 and line["failed"] == 0
    if trace:
        # train.mfu is a share of a chip's peak: nothing off the chip
        assert set(line["metrics"]) == {"train.step_ms"}
    else:
        assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    extra = line["extra"]
    assert abs(extra["first_loss"] - extra["reference_loss"]) < 2e-3


def test_reference_check_fails_on_one_perturbed_weight(checkout):
    root, _ = checkout
    line = tiny_cells.run(root, "tiny.job", seconds=0.2,
                          tamper=tiny_cells.perturb_one_weight)
    assert line["correct"] is False
    assert any("reference" in p for p in line["problems"])
