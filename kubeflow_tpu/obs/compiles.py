"""The compile ledger and the start-up spans: where a start goes.

JAX reports every stage of every program it builds as a monitoring
event: a trace, a lowering to MLIR and a backend stage (a compile, or a
read of the persistent cache), each with the program's name, its start
and its end; and, inside a backend stage, whether the cache was asked,
whether it held the program, and how long the read took. `CompileLedger`
listens to those events and to nothing else. Nothing of it runs when
JAX builds nothing: a warmed server or trainer dispatches without one
call into this module (tests/test_compile_ledger.py counts them).

One row a program name, bounded by a `LabelGuard` (past the cap a new
name is booked to `other`): traces and their seconds, lowering seconds,
backend seconds and the part of those that was a cache read, how often
the cache was asked, held the program or was written, and the first and
last moment the program was seen, on the clock the events carry
(`time.time()`), beside `installed_at`. Seconds are SELF time: a stage
that opens under another on the same thread (an eager operation
compiled while a function is traced) is taken out of the outer one, and
a function traced inside another's stage (an inner `jit`, `jnp.where`)
is no program of its own: its time stays in that stage.

The start-up spans (`STARTUP_SPANS`, a closed set) are kept by the same
ledger, because set-up runs before any profiler session can be open:
each sums its wall time by name, whole and as the time of what opened
under it on its thread (stages and spans). With `annotate` (handed over
by `install`: `jax.profiler.TraceAnnotation`) a span is also one of the
profiler's trace, where a session is open (a reload on a live server).

No jax import here: the caller that has JAX hands `install` the
`jax.monitoring` module (`compile_cache.enable()` does).
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
import weakref
from typing import Any, Callable

from kubeflow_tpu.obs.cardinality import LabelGuard

# JAX 0.9.0: jax/_src/dispatch.py (the stages: a scalar with the start
# time when one opens, a time span when it ends, both with `fun_name`),
# jax/_src/compiler.py and compilation_cache.py (the cache's events,
# which carry no name and fire inside the backend stage of the thread
# that compiles).
STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
CACHE_COUNT_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    # fired where an entry is written, so under the cache's thresholds
    # a program is compiled on every start and never counted here:
    # `compiled` (below) is backend stages less hits
    "/jax/compilation_cache/cache_misses": "cache_writes",
}
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

STARTUP_SPANS = ("startup.import_jax", "startup.engine", "startup.batcher",
                 "startup.warmup", "startup.trainer", "startup.first_step")
# the label values of `serving_startup_seconds` / `train_startup_seconds`
STARTUP_PHASES = tuple(s.removeprefix("startup.") for s in STARTUP_SPANS) \
    + ("before_first_program",)
MAX_PROGRAMS = 256


def program_name(fun_name: str) -> str:
    """One row for an event's `fun_name`: tracing names the function
    (`_step`), lowering and the backend the module (`jit(_step)`)."""
    for api in ("jit(", "pmap("):
        if fun_name.startswith(api) and fun_name.endswith(")"):
            return fun_name[len(api):-1]
    return fun_name


class _Frame:
    """One open stage or start-up span of one thread."""

    __slots__ = ("kind", "name", "start", "child_s", "folded", "counts",
                 "cache_read_s")

    def __init__(self, kind: str, name: str, start: float,
                 folded: bool = False):
        self.kind = kind            # a stage, or "span"
        self.name = name
        self.start = start
        self.child_s = 0.0          # what opened under it on this thread
        self.folded = folded        # a trace inside another stage
        self.counts: dict[str, int] = {}
        self.cache_read_s = 0.0


def _new_row(now: float) -> dict:
    return {"traces": 0, "trace_s": 0.0, "lower_s": 0.0, "backends": 0,
            "backend_s": 0.0, "cache_read_s": 0.0, "cache_requests": 0,
            "cache_hits": 0, "cache_writes": 0, "first_seen": now,
            "last_seen": now}


class CompileLedger:
    """What JAX traced, lowered and compiled or read from its cache in
    this process, by program, and the start-up spans (module docstring).
    Thread-safe; holds numbers and strings only. The listeners run
    inside JAX's compile path: nothing here may raise into it."""

    def __init__(self, *, max_programs: int = MAX_PROGRAMS,
                 clock: Callable[[], float] = time.time):
        self._guard = LabelGuard(max_values=max_programs)
        self._span_guard = LabelGuard(seed=STARTUP_SPANS, closed=True)
        self._clock = clock
        self._lock = threading.Lock()
        self._local = threading.local()
        self._rows: dict[str, dict] = {}
        self._spans: dict[str, dict] = {}
        self._watches: weakref.WeakSet = weakref.WeakSet()
        self._annotate: Callable[..., Any] | None = None
        self._registered: Any = None       # the module that holds them
        self.installed_at: float | None = None
        self.first_program_at: float | None = None
        # every call a listener or `span()` received: what the
        # window-inertness tests count
        self.calls = 0

    # -- wiring ------------------------------------------------------------

    def begin(self) -> None:
        """Stamp `installed_at`, once: what precedes the first program
        (the import of JAX, the device attach, the entry point's own
        work) is counted from here."""
        if self.installed_at is None:
            self.installed_at = self._clock()

    def install(self, monitoring, *,
                annotate: Callable[..., Any] | None = None) -> None:
        """Start listening to `monitoring` (the `jax.monitoring`
        module). A second call is harmless: the listeners are handed
        over once."""
        self.begin()
        with self._lock:
            self._annotate = annotate
            if self._registered is not None:
                return
            self._registered = monitoring
        monitoring.register_scalar_listener(self._on_scalar)
        monitoring.register_event_time_span_listener(self._on_time_span)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def uninstall(self) -> None:
        """Stop listening (a ledger a test made)."""
        with self._lock:
            monitoring, self._registered = self._registered, None
        if monitoring is not None:
            monitoring.unregister_scalar_listener(self._on_scalar)
            monitoring.unregister_event_time_span_listener(
                self._on_time_span)
            monitoring.unregister_event_listener(self._on_event)
            monitoring.unregister_event_duration_listener(self._on_duration)

    @property
    def installed(self) -> bool:
        return self._registered is not None

    def attach(self, watch) -> None:
        """`watch.on_stage(stage, name, seconds)` from now on at the
        end of every stage that is a program's own, for as long as the
        watch lives (held weakly)."""
        self._watches.add(watch)

    # -- frames ------------------------------------------------------------

    def _stack(self) -> list[_Frame]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _backend_frame(self) -> _Frame | None:
        return next((f for f in reversed(self._stack())
                     if f.kind == "backend"), None)

    # -- JAX's listeners ---------------------------------------------------

    def _on_scalar(self, event: str, value=None, **kw) -> None:
        self.calls += 1
        stage = STAGE_EVENTS.get(event)
        if stage is None:
            return
        try:
            stack = self._stack()
            under_stage = bool(stack) and stack[-1].kind != "span"
            start = float(value)
            if not under_stage and self.first_program_at is None:
                self.first_program_at = start
            stack.append(_Frame(
                stage, program_name(str(kw.get("fun_name", ""))), start,
                folded=under_stage and stage == "trace"))
        except Exception:  # noqa: BLE001 — never into JAX's compile path
            pass

    def _on_time_span(self, event: str, start: float, end: float,
                      **kw) -> None:
        self.calls += 1
        stage = STAGE_EVENTS.get(event)
        if stage is None:
            return
        try:
            name = program_name(str(kw.get("fun_name", "")))
            seconds = max(0.0, float(end) - float(start))
            stack = self._stack()
            at = next((i for i in range(len(stack) - 1, -1, -1)
                       if stack[i].kind == stage and stack[i].name == name),
                      None)
            if at is None:      # opened before the ledger listened
                frame = _Frame(stage, name, float(start))
            else:
                frame = stack[at]
                del stack[at:]
            if frame.folded:
                return
            if stack:
                stack[-1].child_s += seconds
            self_s = max(0.0, seconds - frame.child_s)
            with self._lock:
                label = self._guard.admit(name)
                row = self._rows.get(label)
                if row is None:
                    row = self._rows[label] = _new_row(float(start))
                row["last_seen"] = float(end)
                row[stage + "_s"] += self_s
                if stage == "trace":
                    row["traces"] += 1
                elif stage == "backend":
                    row["backends"] += 1
                    row["cache_read_s"] += frame.cache_read_s
                    for key, n in frame.counts.items():
                        row[key] += n
            for watch in tuple(self._watches):
                watch.on_stage(stage, name, self_s)
        except Exception:  # noqa: BLE001
            pass

    def _on_event(self, event: str, **_kw) -> None:
        self.calls += 1
        key = CACHE_COUNT_EVENTS.get(event)
        if key is None:
            return
        try:
            frame = self._backend_frame()
            if frame is not None:
                frame.counts[key] = frame.counts.get(key, 0) + 1
        except Exception:  # noqa: BLE001
            pass

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        self.calls += 1
        if event != CACHE_READ_EVENT:
            return
        try:
            frame = self._backend_frame()
            if frame is not None:
                frame.cache_read_s += float(seconds)
        except Exception:  # noqa: BLE001
            pass

    # -- start-up spans ----------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        """A start-up span, `name` one of `STARTUP_SPANS`."""
        self.calls += 1
        name = self._span_guard.admit(name)
        annotation = None
        if self._annotate is not None:
            annotation = self._annotate(name)
            annotation.__enter__()
        stack = self._stack()
        frame = _Frame("span", name, self._clock())
        stack.append(frame)
        try:
            yield
        finally:
            seconds = max(0.0, self._clock() - frame.start)
            if frame in stack:
                del stack[stack.index(frame):]
            if stack:
                stack[-1].child_s += seconds
            if annotation is not None:
                annotation.__exit__(None, None, None)
            with self._lock:
                rec = self._spans.setdefault(
                    name, {"count": 0, "start": frame.start,
                           "seconds": 0.0, "children_s": 0.0})
                rec["count"] += 1
                rec["seconds"] += seconds
                rec["children_s"] += min(seconds, frame.child_s)

    # -- read side ---------------------------------------------------------

    def rows(self) -> list[dict]:
        """The table, costliest first."""
        with self._lock:
            rows = [{"program": name, **row}
                    for name, row in self._rows.items()]
        rows.sort(key=lambda r: -(r["trace_s"] + r["lower_s"]
                                  + r["backend_s"]))
        return rows

    def totals(self, first_seen_before: float | None = None) -> dict:
        """Sums over the programs (those first seen before that moment,
        where one is given). `compiled`: backend stages the cache did
        not answer, whether it was asked or not."""
        keys = ("traces", "trace_s", "lower_s", "backends", "backend_s",
                "cache_read_s", "cache_requests", "cache_hits",
                "cache_writes")
        out: dict[str, float] = dict.fromkeys(keys, 0)
        for row in self.rows():
            if first_seen_before is None \
                    or row["first_seen"] < first_seen_before:
                for k in keys:
                    out[k] += row[k]
        out["compiled"] = out["backends"] - out["cache_hits"]
        return out

    def spans(self) -> dict[str, dict]:
        with self._lock:
            return {name: dict(rec) for name, rec in self._spans.items()}

    def before_first_program_s(self) -> float | None:
        """From `installed_at` to the start of the first stage of the
        first program: the import, the device attach and whatever an
        entry point does before it builds anything."""
        if self.installed_at is None or self.first_program_at is None:
            return None
        return max(0.0, self.first_program_at - self.installed_at)

    def startup_seconds(self) -> dict[str, float]:
        """Seconds by `STARTUP_PHASES`, zero where none was spent: the
        `*_startup_seconds{phase}` gauges."""
        out = dict.fromkeys(STARTUP_PHASES, 0.0)
        for name, rec in self.spans().items():
            if name in STARTUP_SPANS:       # not the overflow row
                out[name.removeprefix("startup.")] = rec["seconds"]
        out["before_first_program"] = self.before_first_program_s() or 0.0
        return out

    def snapshot(self, top: int | None = None) -> dict:
        """The `/debug/profile` blocks `compiles` and `startup`."""
        def rounded(d: dict) -> dict:
            return {k: round(v, 6) if isinstance(v, float) else v
                    for k, v in d.items()}

        return {
            "compiles": {
                "installed": self.installed,
                "installed_at": self.installed_at,
                "first_program_at": self.first_program_at,
                "totals": rounded(self.totals()),
                "programs": [rounded(r) for r in self.rows()[:top]]},
            "startup": {
                "before_first_program_s": self.before_first_program_s(),
                "spans": {n: rounded(r) for n, r in self.spans().items()}},
        }


# The process's one ledger: JAX's listeners are the process's too.
# `compile_cache.enable()` installs it; until then it holds nothing.
LEDGER = CompileLedger()


def startup_span(name: str):
    """Decorator: the call is the process ledger's start-up span
    `name` (a constructor, a warm-up: never a dispatch)."""
    def decorate(fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with LEDGER.span(name):
                return fn(*args, **kwargs)
        return spanned
    return decorate


def bind_startup_gauge(registry, name: str) -> None:
    """The gauge `name{phase}` on `registry`, once a registry:
    zero-seeded over `STARTUP_PHASES` and set from the process's ledger
    whenever the registry is rendered."""
    if registry.get(name) is not None:
        return
    from kubeflow_tpu.controlplane.metrics import Gauge

    gauge = Gauge(
        name, "Wall seconds of this process's start-up, by phase: the "
        "compile ledger's start-up spans and what preceded the first "
        "program (docs/observability.md)", registry)
    for phase in STARTUP_PHASES:
        gauge.set(0.0, phase=phase)

    def collect():
        for phase, seconds in LEDGER.startup_seconds().items():
            gauge.set(seconds, phase=phase)

    registry.register_collector(collect)
