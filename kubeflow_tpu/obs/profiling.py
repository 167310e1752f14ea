"""Step-anatomy profiling: phase attribution, goodput, compile-watch.

Metrics say HOW LONG a step took; traces say WHICH step was slow. This
module answers WHERE the time went: every `ContinuousBatcher` worker
iteration (and every `Trainer.step`) decomposes into named phases with
per-phase wall time, token counts, and occupancy, aggregated three
ways —

  1. `serving_step_phase_seconds{phase}` / `serving_step_tokens{phase}`
     histograms (the server binds them through `on_phase`, zero-seeded
     so dashboards see every phase from the first scrape),
  2. a goodput ledger: host wall time in the dispatching phases over
     total non-idle step time, bubble fraction (host-gap share), and
     occupancy / KV-pool high-water marks. Every time here is the
     HOST's: a dispatch is asynchronous, so the wall time around one
     is the enqueue plus whatever the host then waited for, not the
     device's time. Device time comes from the profiler's trace alone
     (below),
  3. Chrome-trace COUNTER tracks (`"ph": "C"`) merged into the same
     `/debug/traces` payload as the span events, so one trace shows
     phase budgets and pool fill over time next to the spans.

Phase mapping for the continuous batcher (the honest one for this
architecture — sampling is fused into the device step, so the host-side
phases measure what the HOST does around it):

  admit       queue pop, block planning, grouping, insert dispatch
  prefill     the grouped prefill/gather device call
  decode      decode-chunk dispatch + waiting on device results
  sample      host materialization of sampled tokens (device->numpy)
  detokenize  per-token emit bookkeeping (stop-seq scan, timelines,
              stream queues)
  preempt     evicting a batch decode (cache blocks, release slot)
  resume      zero-duration marker per preemption replay admission
  host_gap    the iteration residual no explicit phase claims — the
              bubble dispatch-ahead exists to hide
  idle        waiting for work (empty batcher); excluded from goodput

Phase and fn label values are CLOSED SETS behind `LabelGuard`s: an
unknown name collapses to `other` instead of minting a series.

The same phases go into the JAX profiler's trace where the owner hands
the profiler an `annotate` factory (`jax.profiler.TraceAnnotation`; obs
itself imports no jax): `phase(name)` also opens the span
`sched.<name>` (`train.<name>` under `TRAIN_PHASES`) with the stat
`tokens`, and `begin_iteration`/`end_iteration` open and close
`sched.iteration` with the counts they are given. The spans land in
the same `.xplane.pb`, on the same clock, as the device's operations;
`host_gap` needs none, being the iteration's self time there. Without a
profiler session an annotation records nothing.

The compile-watch (`CompileWatch`) is a view of what JAX itself holds
about a few jitted callables of the hot path: each function's own
dispatch cache says how many signatures it was built for, and the
compile ledger's events (`obs/compiles.py`) say when to look and how
long the build took. It wraps nothing: the watched function is called
as it is, and nothing of the watch runs on a dispatch. An entry past a
function's first is a retrace: the counter hook fires
(`serving_recompiles_total{fn}` / `train_recompiles_total{fn}`) and a
`recompile` span names the program with the seconds of its trace,
lowering and backend stage. Steady-state decode repeats one signature,
so a nonzero rate is always news.

No jax import here: obs stays importable in jax-free processes.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
import weakref
from typing import Any, Callable

from kubeflow_tpu.obs.cardinality import LabelGuard
from kubeflow_tpu.obs.compiles import LEDGER, CompileLedger
from kubeflow_tpu.obs.metrics import sample_quantile

# The serving step anatomy (ContinuousBatcher worker loop).
# prefill_chunk = chunked-prefill slices interleaved with decode
# (ISSUE 9); draft/verify = the speculative round's two device legs.
SERVING_PHASES = ("admit", "prefill_chunk", "decode",
                  "draft", "verify", "sample", "detokenize",
                  "preempt", "resume", "host_gap", "idle")
# The training step anatomy (Trainer.step): one device phase plus the
# host gap between consecutive steps (input pipeline, checkpointing).
TRAIN_PHASES = ("step", "host_gap")
# Goodput numerator per anatomy: the phases that dispatch useful device
# work and wait for it (draft/verify are the speculative round's
# token-producing legs). Their time is host wall time.
GOODPUT_PHASES = ("decode", "draft", "verify", "step")
# Phases excluded from the goodput denominator: an empty batcher
# parked on its wake event is not a bubble, it has no work.
IDLE_PHASES = ("idle",)

# Jitted callables the serving compile-watch wraps (closed fn set).
WATCHED_SERVING_FNS = ("decode_step", "reset_slots", "prefill_append",
                       "spec_draft", "spec_verify")
WATCHED_TRAIN_FNS = ("train_step",)

_MAX_COUNTER_EVENTS = 2048


class _PhaseStats:
    __slots__ = ("count", "total_s", "tokens", "window")

    def __init__(self, window: int | None):
        self.count = 0
        self.total_s = 0.0
        self.tokens = 0
        self.window: Any = (collections.deque(maxlen=window)
                            if window else [])


class PhaseProfiler:
    """Aggregates named-phase timings into totals, rolling-window
    percentiles, a goodput ledger, and Chrome counter tracks.

    Usage (the batcher/trainer side):

        with profiler.phase("decode", tokens=steps * occupancy):
            ... device call ...

    Phases nest: a parent's recorded duration EXCLUDES time spent in
    nested phases (admit excludes the prefill dispatch it contains), so
    phase sums reconcile against wall time without double counting.
    `begin_iteration`/`end_iteration` bracket one worker-loop pass and
    book the unclaimed residual as `host_gap` — by construction the
    phase sums then equal the measured loop wall time.

    Everything here is defensive pure python: a profiler bug must never
    kill the instrumented worker, so the `on_phase` hook is swallowed
    like every other batcher hook and internal state is lock-guarded.
    """

    def __init__(self, *, phases: tuple[str, ...] = SERVING_PHASES,
                 clock: Callable[[], float] | None = None,
                 wall_clock: Callable[[], float] | None = None,
                 window: int | None = 512,
                 annotate: Callable[..., Any] | None = None):
        self.phases = tuple(phases)
        # annotate(name, **stats) -> context manager: the profiler's
        # own trace (module docstring). None: the phases stay here.
        self._annotate = annotate
        self._span_prefix = ("train." if self.phases == TRAIN_PHASES
                             else "sched.")
        self._iter_span: Any = None
        self.guard = LabelGuard(seed=self.phases, closed=True)
        self._clock = clock or time.perf_counter
        self._wall = wall_clock or time.time
        self._window = window
        self._lock = threading.Lock()
        self._stats: dict[str, _PhaseStats] = {
            p: _PhaseStats(window) for p in self.phases}
        # nesting stack (single worker task/thread by construction):
        # [name, start, child_seconds]
        self._stack: list[list] = []
        self._iter_t0: float | None = None
        self._iter_claimed = 0.0
        self._t_first: float | None = None
        self._t_last: float | None = None
        # optional hook(phase, seconds, tokens) — the server wires the
        # labeled histograms through it; exceptions are swallowed.
        # seconds is None for token-only attributions (add_tokens).
        self.on_phase: Callable[[str, float | None, int], None] | None \
            = None
        # goodput ledger extras
        self.pool_high_water = 0
        self.pool_capacity = 0
        self.occupancy_high_water = 0
        self.slots = 0
        self._pool_last = -1
        self._occ_last = -1
        self._events: collections.deque = collections.deque(
            maxlen=_MAX_COUNTER_EVENTS)

    # -- recording ---------------------------------------------------------

    def _span(self, name: str, **stats):
        if self._annotate is None:
            return contextlib.nullcontext()
        return self._annotate(self._span_prefix + name, **stats)

    @contextlib.contextmanager
    def phase(self, name: str, tokens: int = 0, **stats: int):
        """`stats` go to the trace's span beside `tokens`, and nowhere
        else."""
        with self._span(name, tokens=int(tokens), **stats):
            start = self._clock()
            if self._t_first is None:
                # the observed-wall window opens at the first phase
                # START (record() only back-dates by the EXCLUSIVE
                # duration, which undercounts when the first record is
                # a nested child)
                self._t_first = start
            frame = [name, start, 0.0]
            self._stack.append(frame)
            try:
                yield
            finally:
                dur = self._clock() - start
                if self._stack and self._stack[-1] is frame:
                    self._stack.pop()
                if self._stack:
                    self._stack[-1][2] += dur
                self.record(name, max(0.0, dur - frame[2]),
                            tokens=tokens)

    def record(self, name: str, seconds: float, tokens: int = 0) -> None:
        name = self.guard.admit(name)
        seconds = max(0.0, float(seconds))
        now = self._clock()
        with self._lock:
            st = self._stats.get(name)
            if st is None:
                st = self._stats[name] = _PhaseStats(self._window)
            st.count += 1
            st.total_s += seconds
            st.tokens += int(tokens)
            st.window.append(seconds)
            self._t_last = now
            if self._t_first is None:
                self._t_first = now - seconds
            if self._iter_t0 is not None:
                # phases record EXCLUSIVE durations (nesting subtracts
                # child time), so summing every record — nested or
                # not — claims exactly the inclusive wall of the
                # iteration's top-level phases
                self._iter_claimed += seconds
        if self.on_phase is not None:
            try:
                self.on_phase(name, seconds, int(tokens))
            except Exception:  # noqa: BLE001 — metrics hook
                pass           # must never kill the instrumented loop

    def add_tokens(self, name: str, tokens: int) -> None:
        """Attribute tokens to a phase without a timing sample (decode
        tokens are counted where they are OBSERVED — at host
        processing — while decode time is measured at dispatch)."""
        if tokens <= 0:
            return
        name = self.guard.admit(name)
        with self._lock:
            st = self._stats.get(name)
            if st is None:
                st = self._stats[name] = _PhaseStats(self._window)
            st.tokens += int(tokens)
        if self.on_phase is not None:
            try:
                self.on_phase(name, None, int(tokens))
            except Exception:  # noqa: BLE001 — metrics hook
                pass

    def begin_iteration(self, **counts: int) -> None:
        """`counts` (the batcher's occupancy at the boundary) become
        the stats of the trace's `sched.iteration` span."""
        self._close_iteration_span()   # a pass that never reached its end
        self._iter_t0 = self._clock()
        self._iter_claimed = 0.0
        self._iter_span = self._span("iteration", **counts)
        self._iter_span.__enter__()

    def _close_iteration_span(self) -> None:
        span, self._iter_span = self._iter_span, None
        if span is not None:
            span.__exit__(None, None, None)

    def end_iteration(self) -> None:
        """Book the loop-pass residual (wall minus every top-level
        phase recorded since begin_iteration) as `host_gap` — the
        attribution invariant `sum(phases) == loop wall` holds by
        construction."""
        self._close_iteration_span()
        if self._iter_t0 is None:
            return
        residual = (self._clock() - self._iter_t0) - self._iter_claimed
        self._iter_t0 = None
        if residual > 0.0:
            self.record("host_gap", residual)
        self._emit_phase_track()

    # -- pool / occupancy high-water marks ---------------------------------

    def note_pool(self, in_use: int, capacity: int) -> None:
        self.pool_capacity = int(capacity)
        in_use = int(in_use)
        if in_use > self.pool_high_water:
            self.pool_high_water = in_use
        if in_use != self._pool_last:
            self._pool_last = in_use
            self._events.append({
                "name": "kv_blocks", "ph": "C",
                "ts": round(self._wall() * 1e6, 1), "pid": 1, "tid": 0,
                "args": {"in_use": in_use}})

    def note_occupancy(self, occupied: int, slots: int) -> None:
        self.slots = int(slots)
        occupied = int(occupied)
        if occupied > self.occupancy_high_water:
            self.occupancy_high_water = occupied
        if occupied != self._occ_last:
            self._occ_last = occupied
            self._events.append({
                "name": "batch_occupancy", "ph": "C",
                "ts": round(self._wall() * 1e6, 1), "pid": 1, "tid": 0,
                "args": {"slots_active": occupied}})

    def _emit_phase_track(self) -> None:
        with self._lock:
            args = {p: round(st.total_s, 6)
                    for p, st in self._stats.items() if st.count}
        if args:
            self._events.append({
                "name": "phase_seconds", "ph": "C",
                "ts": round(self._wall() * 1e6, 1), "pid": 1, "tid": 0,
                "args": args})

    # -- read side ---------------------------------------------------------

    def counter_events(self, *, prefix: str = "") -> list[dict]:
        """Chrome counter-track events (`"ph": "C"`), timestamped on
        the same wall clock as the tracer's span events so they merge
        into one `/debug/traces` payload. `prefix` namespaces the track
        names per model."""
        out = []
        for e in list(self._events):
            e = dict(e)
            if prefix:
                e["name"] = f"{prefix}.{e['name']}"
            out.append(e)
        return out

    def totals(self) -> dict[str, float]:
        with self._lock:
            return {p: st.total_s for p, st in self._stats.items()}

    def phase_tokens(self) -> dict[str, int]:
        with self._lock:
            return {p: st.tokens for p, st in self._stats.items()}

    def samples(self, name: str) -> list[float]:
        with self._lock:
            st = self._stats.get(name)
            return list(st.window) if st else []

    def wall_s(self) -> float:
        """Wall window the profiler has observed (first record to
        last) — what the attribution 5%-reconciliation compares phase
        sums against."""
        with self._lock:
            if self._t_first is None or self._t_last is None:
                return 0.0
            return self._t_last - self._t_first

    def goodput(self) -> dict[str, float]:
        """The ledger: the share of non-idle wall time the host spent
        in the dispatching phases (enqueue plus waiting on results —
        host time, not the device's), the bubble (host_gap) share, and
        the high-water marks."""
        with self._lock:
            totals = {p: st.total_s for p, st in self._stats.items()}
        busy = sum(s for p, s in totals.items() if p not in IDLE_PHASES)
        good = sum(totals.get(p, 0.0) for p in GOODPUT_PHASES)
        bubble = totals.get("host_gap", 0.0)
        return {
            "goodput_ratio": good / busy if busy > 0 else 0.0,
            "bubble_fraction": bubble / busy if busy > 0 else 0.0,
            "busy_s": busy,
            "idle_s": sum(totals.get(p, 0.0) for p in IDLE_PHASES),
            "kv_blocks_high_water": self.pool_high_water,
            "kv_blocks_capacity": self.pool_capacity,
            "occupancy_high_water": self.occupancy_high_water,
            "slots": self.slots,
        }

    def snapshot(self) -> dict:
        """The `/debug/profile` building block: per-phase counts,
        totals, tokens, and rolling p50/p95 (same interpolation as
        `Histogram.quantile` — see `sample_quantile`), plus the goodput
        ledger."""
        phases = {}
        with self._lock:
            items = [(p, st.count, st.total_s, st.tokens,
                      list(st.window)) for p, st in self._stats.items()]
        for p, count, total_s, tokens, win in items:
            phases[p] = {
                "count": count,
                "total_s": round(total_s, 6),
                "tokens": tokens,
                "p50_s": sample_quantile(win, 0.50),
                "p95_s": sample_quantile(win, 0.95),
            }
        return {"phases": phases, "goodput": self.goodput(),
                "wall_s": round(self.wall_s(), 6)}


class _Watched:
    """One jitted function under a label: a weak reference, the
    program's name in JAX's events, and how many entries of its
    dispatch cache have been accounted for."""

    __slots__ = ("ref", "program", "seen", "events", "by_events")

    def __init__(self, fn):
        self.ref = weakref.ref(fn)
        self.program = getattr(fn, "__name__", "")
        self.events = 0             # backend stages under the name
        # another JAX, without `_cache_size`: count those, by name
        self.by_events = not callable(getattr(fn, "_cache_size", None))
        # what the function was built for before the watch: not news
        self.seen = self.size(fn)

    def size(self, fn) -> int:
        """Entries of the function's own dispatch cache, one a
        signature it was traced for."""
        return self.events if self.by_events else int(fn._cache_size())


class CompileWatch:
    """Retrace detector over jitted callables, without a wrapper.

    `watch(fn, name)` keeps a weak reference to the jitted `fn` under
    the label `name` and returns `fn` itself. `counts()` reads, when
    asked, how far each function's own dispatch cache
    (`fn._cache_size()`, JAX 0.9.0) has grown past its first entry: a
    new shape, dtype, static value, weak type, sharding or commitment
    of an argument, of the pool and the cursors as of the batch, is an
    entry, and the count is THIS function's, whatever else in the
    process compiles a program of the same name (a reload, a draft
    engine, a second model). An entry is a trace and nearly always a
    compile; two that differ only in whether an argument is committed
    to a device can share one program. The ledger's events are the
    trigger and the timing: the end of a backend stage under a watched
    function's name marks the watch, and the next event, or the next
    `counts()`, re-reads the caches (the entry is there once the call
    that compiled has returned), bumps the label, calls
    `on_recompile(label, program)` and opens the `recompile` span with
    the program's name and the seconds of its three stages. Without a
    listening ledger the counts are as exact and only the hooks wait
    for the next `counts()`.

    Label names are a closed set behind a LabelGuard (seeded by
    `watch`), so the label space cannot grow past the watched
    callables. The watch holds its functions weakly and the ledger
    holds the watch weakly: both go with the batcher or trainer.
    """

    def __init__(self, *, tracer=None,
                 on_recompile: Callable[[str, str], None] | None = None,
                 ledger: CompileLedger | None = None):
        self.tracer = tracer
        self.on_recompile = on_recompile
        self.guard = LabelGuard()
        self._watched: dict[str, list[_Watched]] = {}
        self._recompiles: dict[str, int] = {}
        self._stages: dict[str, dict[str, float]] = {}  # by program
        self._marked = False
        self._lock = threading.Lock()
        (LEDGER if ledger is None else ledger).attach(self)

    def watch(self, fn: Callable, name: str) -> Callable:
        if not callable(getattr(fn, "lower", None)):
            raise TypeError(
                f"CompileWatch.watch takes a jitted function (jax.jit's "
                f"result), got {type(fn).__name__}: there is no compile "
                f"to watch on a plain callable")
        name = self.guard.admit(name)
        with self._lock:
            self._watched.setdefault(name, []).append(_Watched(fn))
            self._recompiles.setdefault(name, 0)
        return fn

    def on_stage(self, stage: str, program: str, seconds: float) -> None:
        """The ledger's call at the end of a program's stage (compile
        time only). Nothing here may raise into JAX's compile path."""
        try:
            if self._marked:
                self._refresh()
            with self._lock:
                mine = [w for ws in self._watched.values() for w in ws
                        if w.program == program]
                if not mine:
                    return
                self._stages.setdefault(program, {})[stage] = seconds
                if stage == "backend":
                    self._marked = True
                    for w in mine:
                        w.events += 1
        except Exception:  # noqa: BLE001
            pass

    def _refresh(self) -> None:
        """Re-read every watched function's cache; book what grew."""
        grown: list[tuple[str, _Watched]] = []
        with self._lock:
            self._marked = False
            for label, watched in self._watched.items():
                for w in list(watched):
                    fn = w.ref()
                    if fn is None:
                        watched.remove(w)
                        continue
                    size = w.size(fn)
                    new = size - max(w.seen, 1)   # the first is expected
                    w.seen = size                 # (also after a clear)
                    if new > 0:
                        self._recompiles[label] += new
                        grown += [(label, w)] * new
            stages = {p: dict(s) for p, s in self._stages.items()}
        for label, w in grown:
            self._note_recompile(label, w, stages.get(w.program, {}))

    def _note_recompile(self, label: str, w: _Watched,
                        stages: dict[str, float]) -> None:
        if self.tracer is not None:
            try:
                with self.tracer.span(
                        "recompile", fn=label, program=w.program,
                        counted_by=("events_by_name" if w.by_events
                                    else "dispatch_cache"),
                        **{f"{k}_s": round(v, 6)
                           for k, v in stages.items()}):
                    pass
            except Exception:  # noqa: BLE001
                pass
        if self.on_recompile is not None:
            try:
                self.on_recompile(label, w.program)
            except Exception:  # noqa: BLE001 — metrics hook
                pass

    def counts(self) -> dict[str, int]:
        """Per-label retrace counts (the `/debug/profile` `recompiles`
        block; mirrors the `*_recompiles_total{fn}` counters)."""
        self._refresh()
        with self._lock:
            return dict(self._recompiles)

    def watched(self) -> tuple[str, ...]:
        with self._lock:
            return tuple(self._watched)


def merge_counter_tracks(payload: dict, events: list[dict]) -> dict:
    """Append counter-track events to a Chrome-trace payload in place
    (no-op for summary payloads without `traceEvents`)."""
    if isinstance(payload, dict) and isinstance(
            payload.get("traceEvents"), list):
        payload["traceEvents"].extend(events)
    return payload
