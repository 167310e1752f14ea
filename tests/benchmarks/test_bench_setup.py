"""The six `setup.*` per-layer metrics: the manifest's entries against
their data files and readers, and each reader against a hand-made
ledger, on the training kind directly (the tiny checkout sends a
metric whose name does not start with `train` to its serve cells).
Seconds are the hand-made events' own: no clock is read."""

from __future__ import annotations

import importlib
import os

import pytest

import tiny_cells
from benchmarks import harness
from benchmarks.readers import setup as reader
from kubeflow_tpu import obs
from kubeflow_tpu.obs.compiles import (CACHE_READ_EVENT, STAGE_EVENTS,
                                       CompileLedger)

REPO = tiny_cells.REPO
SETUP_METRICS = {"setup.before_first_program_s": "program_span",
                 "setup.trace_lower_s": "program_span",
                 "setup.backend_s": "program_span",
                 "setup.cache_misses": "program_counter",
                 "setup.programs": "program_counter",
                 "setup.init_s": "program_span"}
CELLS = ["mistral-7b.steady", "mistral-7b.train", "kimi-linear-48b.train-8k",
         "granite-4.0-h-micro.decode-heavy"]
TRACE, LOWER, BACKEND = STAGE_EVENTS
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- the manifest's rule, for the entries this PR adds --------------------

def _spec(name: str) -> dict:
    return harness.read_json(os.path.join(
        REPO, "benchmarks", "layers", name + ".json"))


def test_each_new_metric_moves_setup_s_in_the_four_cells_that_report_it():
    manifest = harness.read_json(os.path.join(REPO, "BENCHMARK.json"))
    moved = [m for m in manifest["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in moved] == list(SETUP_METRICS)   # and no other
    assert moved == manifest["per_layer"][-len(moved):]        # appended
    assert CELLS == [w["name"] for w in manifest["workloads"]]
    for m in moved:
        assert m["workloads"] == CELLS, m["name"]
        assert m["layer"] == "start-up" and m["better"] == "lower"
        assert m["source"] == SETUP_METRICS[m["name"]]
        assert m["unit"] == ("s" if m["name"].endswith("_s") else "count")
        module, _, fn = _spec(m["name"])["reader"].rpartition(".")
        assert module == "setup"
        assert callable(getattr(importlib.import_module(
            f"benchmarks.readers.{module}"), fn))
        for w in m["workloads"]:
            reported = {e["name"] for e in
                        harness.load_cell(REPO, w).metrics("end_to_end")}
            assert m["moves"] in reported, (m["name"], w)


# -- each reader against a hand-made ledger -------------------------------

def _ctx(setup_s: float | None = 50.0, peaks=PEAKS):
    """A train run's context, as `kinds/train.py` hands it back."""
    end_to_end = {"train_tok_s": 1.0}
    if setup_s is not None:
        end_to_end["setup_s"] = setup_s
    run = harness.Run(end_to_end=end_to_end, counters={}, attempted=1,
                      failed=0, problems=[])
    return harness.Context(run=run, cell=None, model=None, peaks=peaks)


def _read(ctx, name: str):
    spec = _spec(name)
    fn = getattr(reader, spec["reader"].rpartition(".")[2])
    return fn(ctx, **spec.get("args", {}))


class _Heard:
    def __init__(self):
        self.cbs = {}
        for kind in ("scalar", "event_time_span", "event",
                     "event_duration_secs"):
            setattr(self, f"register_{kind}_listener",
                    lambda cb, kind=kind: self.cbs.__setitem__(kind, cb))

    def stage(self, event, start, end, fn, inside=()):
        self.cbs["scalar"](event, start, fun_name=fn)
        for name, *value in inside:
            if value:
                self.cbs["event_duration_secs"](name, *value)
            else:
                self.cbs["event"](name)
        self.cbs["event_time_span"](event, start, end, fun_name=fn)


class _Clock:
    t = 1000.0

    def __call__(self):
        return self.t


@pytest.fixture
def hand_made(monkeypatch):
    """A ledger fed by hand in the place of the process's, installed at
    1000: the Trainer built and its state made (one program read from
    the cache under it), the step compiled under `startup.first_step`,
    and one program built after the window opened at +50."""
    heard, clock = _Heard(), _Clock()
    ledger = CompileLedger(clock=clock)
    ledger.install(heard)
    clock.t = 1006.0
    with ledger.span("startup.trainer"):
        clock.t = 1006.5                       # built: half a second
    with ledger.span("startup.trainer"):       # init: the state's program
        heard.stage(TRACE, 1007.0, 1008.0, "_init")
        heard.stage(LOWER, 1008.0, 1008.5, "jit(_init)")
        heard.stage(BACKEND, 1008.5, 1008.75, "jit(_init)",
                    inside=[(REQUEST,), (HIT,), (CACHE_READ_EVENT, 0.2)])
        clock.t = 1009.0
    with ledger.span("startup.first_step"):
        heard.stage(TRACE, 1010.0, 1012.0, "_step")
        heard.stage(LOWER, 1012.0, 1013.0, "jit(_step)")
        heard.stage(BACKEND, 1013.0, 1043.0, "jit(_step)",
                    inside=[(REQUEST,)])
        clock.t = 1044.0
    heard.stage(BACKEND, 1060.0, 1064.0, "jit(save)")    # after +50
    monkeypatch.setattr(obs, "compile_ledger", lambda: ledger)
    return ledger


WANT = {"setup.before_first_program_s": 7.0,
        "setup.trace_lower_s": 1.0 + 0.5 + 2.0 + 1.0,
        "setup.backend_s": 0.25 + 30.0,
        "setup.cache_misses": 1,
        "setup.programs": 2,
        # built 0.5; init 2.5 less the 1.75 of stages under it
        "setup.init_s": 0.5 + 0.75}


@pytest.mark.parametrize("name", list(SETUP_METRICS))
def test_a_reader_reads_the_hand_made_ledger(hand_made, name, capsys):
    ctx = _ctx()
    assert _read(ctx, name) == pytest.approx(WANT[name])
    # the first reader called leaves the table in the line and on stderr
    shown = ctx.run.extra["compile_ledger"]
    assert [r["program"] for r in shown["compiles"]["programs"]] \
        == ["_step", "save", "_init"]
    assert shown["before_window"]["backends"] == 2
    err = capsys.readouterr()
    assert err.out == "" and err.err.count("before the window") == 1
    _read(ctx, name)
    assert capsys.readouterr().err == ""        # once a run


def test_without_a_setup_s_every_program_counts(hand_made):
    assert _read(_ctx(setup_s=None), "setup.programs") == 3


@pytest.mark.parametrize("name", list(SETUP_METRICS))
def test_a_reader_answers_nothing_off_the_chip_or_without_a_ledger(
        hand_made, monkeypatch, name):
    assert _read(_ctx(peaks=None), name) is None            # off the chip
    empty = CompileLedger()
    monkeypatch.setattr(obs, "compile_ledger", lambda: empty)
    assert _read(_ctx(), name) is None                      # never installed
    empty.install(_Heard())
    assert _read(_ctx(), name) is None                      # holds nothing
    monkeypatch.delattr(obs, "compile_ledger")              # an older program
    assert _read(_ctx(), name) is None
    monkeypatch.setattr(obs, "compile_ledger",
                        lambda: (_ for _ in ()).throw(RuntimeError("x")),
                        raising=False)
    assert _read(_ctx(), name) is None                      # and no raise


def test_init_s_is_silent_where_no_span_was_opened(monkeypatch):
    heard = _Heard()
    ledger = CompileLedger()
    ledger.install(heard)
    heard.stage(BACKEND, 1.0, 2.0, "jit(f)")
    monkeypatch.setattr(obs, "compile_ledger", lambda: ledger)
    assert _read(_ctx(), "setup.init_s") is None
    assert _read(_ctx(setup_s=None), "setup.programs") == 1
