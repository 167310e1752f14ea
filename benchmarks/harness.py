"""Runs one cell of `BENCHMARK.json` and builds the line it prints.

Everything that belongs to one cell is data, found by name:

- `BENCHMARK.json` `workloads[name]` -> its configuration and traffic;
- the configuration's `file` -> sizes, `model_class` (a module of
  `benchmarks/models/`), engine and batcher arguments;
- `benchmarks/traffic/<traffic>.json` -> `kind` (a module of
  `benchmarks/kinds/`) and the mix's parameters;
- each per-layer metric of the manifest that lists the cell (or lists
  none) -> `benchmarks/layers/<metric>.json` -> `reader` (a function of
  a module of `benchmarks/readers/`) and its arguments.

Which metrics a cell reports is the manifest's `workloads` key on the
metric, read here and nowhere else. A new cell, configuration or
per-layer metric is new files and manifest entries; no file that is
there changes.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Callable

from benchmarks import devtrace

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the data files' directory, relative to the checkout's root
DATA_DIR = "benchmarks"


def log(t_start: float, msg: str) -> None:
    """Progress, to stderr: stdout carries the result line alone."""
    print(f"[bench +{time.perf_counter() - t_start:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    manifest: dict
    root: str

    def metrics(self, section: str) -> list[dict]:
        """The manifest's metrics of `section` this cell reports."""
        return [m for m in self.manifest[section]
                if self.name in m.get("workloads", [self.name])]


def load_cell(root: str, name: str) -> Cell:
    manifest = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = read_json(
        os.path.join(root, DATA_DIR, "traffic", w["traffic"] + ".json"))
    return Cell(name=name, chips=w["chips"], config=config,
                traffic=traffic, manifest=manifest, root=root)


def peaks_for(kind: str) -> dict:
    """The device's published peaks. A kind that is not in the table is
    an error: a guessed peak makes every share of it a guess."""
    table = read_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "peaks.json"))["kinds"]
    if kind not in table:
        raise SystemExit(
            f"device_kind {kind!r} is not in benchmarks/peaks.json "
            f"(have {sorted(table)}): add it with its published peaks")
    return table[kind]


class CompileCount:
    """Counts the programs JAX compiles, or reads from its cache, while
    it is open. `n` inside the measured window has to stay 0."""

    def __init__(self):
        self.n = 0

    def _on_event(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_event)


@dataclasses.dataclass
class Run:
    """What a kind of cell hands back for one run."""
    end_to_end: dict[str, float]           # every metric the kind takes
    counters: dict[str, float]             # for the per-layer readers
    attempted: int
    failed: int
    problems: list[str]                    # empty <=> correct
    trace: devtrace.DeviceTrace | None = None
    extra: dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    run: Run
    cell: Cell
    model: Any
    peaks: dict | None                     # None off the chip


def trace_dir(cell: Cell) -> str:
    """Where a traced run writes its profile: inside the checkout, under
    a directory `.gitignore` lists."""
    return os.path.join(cell.root, ".bench_trace", cell.name)


def read_layer_metrics(ctx: Context) -> dict[str, dict]:
    out = {}
    for m in ctx.cell.metrics("per_layer"):
        spec = read_json(os.path.join(
            ctx.cell.root, DATA_DIR, "layers", m["name"] + ".json"))
        module, _, fn = spec["reader"].rpartition(".")
        reader: Callable = getattr(
            importlib.import_module(f"benchmarks.readers.{module}"), fn)
        value = reader(ctx, **spec.get("args", {}))
        if value is not None:      # nothing to read: left out of the line
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             peaks: dict | None, t_start: float,
             tamper: Callable | None = None) -> dict:
    """One run of one cell -> the object `run.py` prints. `tamper` is
    the tests' hook: it is applied to the parameters the reference is
    given (never to the ones under test), to show that the reference
    check fails on a one-weight difference."""
    import jax

    kind = importlib.import_module(
        f"benchmarks.kinds.{cell.traffic['kind']}")
    model = importlib.import_module(
        f"benchmarks.models.{cell.config['model_class']}")
    run: Run = kind.run(cell, model, seed=seed, seconds=seconds,
                        trace_dir=trace_dir(cell) if trace else None,
                        t_start=t_start, tamper=tamper)
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    device: dict[str, Any] = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(
            s.get("peak_bytes_in_use", 0) for s in stats),
    }
    line: dict[str, Any] = {
        "correct": not run.problems, "attempted": run.attempted,
        "failed": run.failed,
    }
    if trace:
        line["metrics"] = read_layer_metrics(
            Context(run=run, cell=cell, model=model, peaks=peaks))
        if run.trace is not None:
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            line["breakdown"] = {"device_ops": run.trace.device_ops,
                                 "idle_gaps": run.trace.idle_gaps}
            run.extra["device_program_s"] = sorted(
                ([name, sum(runs), len(runs)]
                 for name, runs in run.trace.programs.items()),
                key=lambda row: -row[1])[:6]
    else:
        metrics = {}
        for m in cell.metrics("end_to_end"):
            if m["name"] not in run.end_to_end:
                raise RuntimeError(
                    f"kind {cell.traffic['kind']!r} does not take "
                    f"{m['name']!r}, which {cell.name!r} is to report")
            metrics[m["name"]] = {"value": run.end_to_end[m["name"]],
                                  "unit": m["unit"]}
        line["metrics"] = metrics
    line["device"] = device
    line["problems"] = run.problems
    line["extra"] = run.extra
    return line


class Profile:
    """`jax.profiler` around part of a window, reduced when it stops.
    Without a directory (an untraced run) it does nothing."""

    def __init__(self, directory: str | None):
        self.directory = directory
        self._t0 = 0.0

    def start(self) -> None:
        if self.directory is None:
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)  # an earlier run's
        os.makedirs(self.directory)
        jax.profiler.start_trace(self.directory)
        self._t0 = time.perf_counter()

    def stop(self) -> devtrace.DeviceTrace | None:
        if self.directory is None:
            return None
        import jax

        window = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        return devtrace.reduce(devtrace.read_xplane(self.directory), window)
