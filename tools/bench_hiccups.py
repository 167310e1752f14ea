#!/usr/bin/env python3
"""Where a serving window of the benchmark stood still, and on whom.

    chiprun -- python3 tools/bench_hiccups.py chiprun_out/h.json \
        --workload mistral-7b.steady --seed 7 --seconds 51 --trace 0

Runs `benchmarks/run.py` of the checkout it is started in, unchanged
(the same last line on stdout), and writes beside it a timeline, in
seconds from the window's first instant:

- `gc`: every garbage collection (start, seconds, generation);
- `hiccups`: each time a thread that sleeps 5 ms woke more than 30 ms
  late, with what rose meanwhile: steal ticks (`/proc/stat`), the
  cgroup's throttling (`cpu.stat`), the main thread's wait for a CPU
  (`/proc/self/schedstat`), CPU pressure (`/proc/pressure/cpu`);
- `hiccups_other_process`: the same sleeper in a process of its own,
  which shares the machine and the cgroup but not the interpreter: a
  freeze only the first list has is this process's own;
- `calls`: the wall time of each `ContinuousEngine.step`,
  `append_rows`, `reset_slots`, `adopt_slot` (the host's dispatch);
- `chunks`: when the worker processed each decode chunk;
- `requests`: due, sent, first and last token of every request.

`setup_s` reads ~14 s less than a plain run's: the imports happen
before `run.py` starts its clock. Nothing else of the line moves.

PERF.md section 7 (PR 37) says what ten `mistral-7b.steady` windows
showed: every run replays one schedule until the process stands still
for 0.1 s, and `ttft_p90_ms` lands on another mode from there.
"""

from __future__ import annotations

import gc
import json
import os
import runpy
import subprocess
import sys
import threading
import time

SLEEP_S = 0.005
LATE_S = 0.03
LEAD_S = 8.0        # kept from before the window: the ramp

_OTHER = """
import sys, time
last = time.perf_counter()
with open(sys.argv[1], "w", buffering=1) as f:
    while True:
        time.sleep(%r)
        now = time.perf_counter()
        if now - last > %r:
            f.write(f"{last} {now - last}\\n")
        last = now
""" % (SLEEP_S, LATE_S)


def _read(path: str) -> str:
    try:
        with open(path, encoding="utf-8") as f:
            return f.read()
    except OSError:
        return ""


def host_counters() -> dict:
    """Cumulative counters of what can hold a process off its CPUs;
    a key is left out where the machine does not have its file."""
    out = {}
    cpu = _read("/proc/stat").split("\n", 1)[0].split()
    if len(cpu) > 8:
        out["steal_ticks"] = int(cpu[8])
    for path in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"):
        for line in _read(path).splitlines():
            key, _, value = line.partition(" ")
            if key in ("nr_throttled", "throttled_usec", "throttled_time"):
                out[key] = int(value)
    sched = _read("/proc/self/schedstat").split()
    if len(sched) == 3:
        out["main_wait_ms"] = int(sched[1]) / 1e6
    for line in _read("/proc/pressure/cpu").splitlines():
        if line.startswith("some") and "total=" in line:
            out["psi_some_ms"] = int(line.rsplit("total=", 1)[1]) / 1e3
    return out


class Recorder:
    def __init__(self, out: str):
        self.out = out
        self.gc_events: list[tuple] = []
        self.hiccups: list[tuple] = []
        self.calls: list[tuple] = []
        self.chunks: list[tuple] = []
        self.window: dict = {}
        self.first = host_counters()
        self._gc_start = 0.0
        self._other = None

    def start(self) -> None:
        gc.callbacks.append(self._on_gc)
        threading.Thread(target=self._sleeper, daemon=True).start()
        self._other = subprocess.Popen(
            [sys.executable, "-c", _OTHER, self.out + ".other"])
        self._patch()

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        else:
            self.gc_events.append(
                (self._gc_start, time.perf_counter() - self._gc_start,
                 info["generation"]))

    def _sleeper(self) -> None:
        last, before, n = time.perf_counter(), host_counters(), 0
        while True:
            time.sleep(SLEEP_S)
            now = time.perf_counter()
            if now - last > LATE_S:
                after = host_counters()
                self.hiccups.append(
                    (last, now - last,
                     {k: round(after[k] - before[k], 3)
                      for k in after if k in before}))
                before = after
            n += 1
            if n % 4 == 0:
                before = host_counters()
            last = time.perf_counter()

    def _patch(self) -> None:
        from benchmarks.kinds import serve
        from kubeflow_tpu.serving import continuous

        def timed(name):
            inner = getattr(continuous.ContinuousEngine, name)

            def call(engine, *args, **kwargs):
                t = time.perf_counter()
                try:
                    return inner(engine, *args, **kwargs)
                finally:
                    self.calls.append((name, t, time.perf_counter() - t))
            setattr(continuous.ContinuousEngine, name, call)

        for name in ("step", "append_rows", "reset_slots", "adopt_slot"):
            timed(name)

        process = continuous.ContinuousBatcher._process_chunk

        def process_chunk(batcher, rec):
            t = time.perf_counter()
            process(batcher, rec)
            self.chunks.append((t, rec["steps"], time.perf_counter() - t))
        continuous.ContinuousBatcher._process_chunk = process_chunk

        reduce_requests = serve.reduce_requests

        def reduce(requests, t0, t1, vocab):
            self.window.update(t0=t0, t1=t1, requests=[
                (r.due, r.sent, r.times[0] if r.times else None,
                 r.times[-1] if r.times else None, len(r.times), r.max_new)
                for r in requests])
            return reduce_requests(requests, t0, t1, vocab)
        serve.reduce_requests = reduce

    def dump(self) -> None:
        if self._other is not None:
            self._other.terminate()
            self._other.wait()
        other = [tuple(map(float, line.split())) for line in
                 _read(self.out + ".other").splitlines()
                 if len(line.split()) == 2]
        if os.path.exists(self.out + ".other"):
            os.remove(self.out + ".other")
        # no window (the run stopped before one): everything is kept
        t0, t1 = self.window.get("t0", 0.0), self.window.get("t1")

        def kept(t):
            return t1 is None or -LEAD_S <= t - t0 <= t1 - t0 + 1.0

        def at(t):
            return None if t is None else round(t - t0, 5)

        last = host_counters()
        line = {
            "window_s": None if t1 is None else t1 - t0,
            "gc": [[at(t), round(s, 5), gen]
                   for t, s, gen in self.gc_events if kept(t)],
            "hiccups": [[at(t), round(s, 5), rose]
                        for t, s, rose in self.hiccups if kept(t)],
            "hiccups_other_process": [[at(t), round(s, 5)]
                                      for t, s in other if kept(t)],
            "host_counters_whole_run": {
                k: round(v - self.first[k], 3)
                for k, v in last.items() if k in self.first},
            "calls": [[name, at(t), round(s, 5)]
                      for name, t, s in self.calls if kept(t)],
            "chunks": [[at(t), steps, round(s, 5)]
                       for t, steps, s in self.chunks if kept(t)],
            "requests": [[at(due), at(sent), at(first), at(end), n, asked]
                         for due, sent, first, end, n, asked
                         in self.window.get("requests", [])],
        }
        os.makedirs(os.path.dirname(os.path.abspath(self.out)),
                    exist_ok=True)
        with open(self.out, "w", encoding="utf-8") as f:
            json.dump(line, f)


def main(argv: list[str]) -> int:
    if len(argv) < 2 or argv[1].startswith("-"):
        print(__doc__, file=sys.stderr)
        return 2
    root = os.getcwd()
    sys.path.insert(0, root)
    recorder = Recorder(os.path.abspath(argv[1]))
    recorder.start()
    sys.argv = [os.path.join(root, "benchmarks", "run.py")] + argv[2:]
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
        code = 0
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else (0 if e.code is None
                                                       else 1)
    finally:
        recorder.dump()
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv))
