"""The compile ledger (`obs/compiles.py`), the start-up spans and the
compile-watch as a view of JAX's own caches: counts, never times.

The window-inertness proofs are here: a warmed tiny batcher serving
requests and a warmed tiny trainer stepping reach the ledger with not
one call, and the watched functions are the jitted functions
themselves.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest_plugins = ("aiohttp.pytest_plugin",)

from kubeflow_tpu import compile_cache, obs
from kubeflow_tpu.obs import compiles
from kubeflow_tpu.obs.compiles import (
    CACHE_READ_EVENT,
    MAX_PROGRAMS,
    STAGE_EVENTS,
    CompileLedger,
    program_name,
)
from kubeflow_tpu.obs.profiling import CompileWatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE, LOWER, BACKEND = STAGE_EVENTS
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
WRITE = "/jax/compilation_cache/cache_misses"


@pytest.fixture
def ledger():
    """A ledger of the test's own on JAX's events, beside the
    process's: rows start empty whatever ran before."""
    led = CompileLedger()
    led.install(jax.monitoring)
    yield led
    led.uninstall()


@pytest.fixture(scope="module")
def process_ledger():
    """The process's ledger, listening, as after `enable()`."""
    compile_cache._install_ledger()
    return obs.compile_ledger()


class Heard:
    """Stands in for `jax.monitoring`: keeps the listeners it is
    handed, so that a test can send a ledger events by hand."""

    def __init__(self):
        self.scalar, self.span, self.event, self.duration = [], [], [], []
        self.register_scalar_listener = self.scalar.append
        self.register_event_time_span_listener = self.span.append
        self.register_event_listener = self.event.append
        self.register_event_duration_secs_listener = self.duration.append

    def open(self, event, at, fn):
        for cb in self.scalar:
            cb(event, at, fun_name=fn)

    def close(self, event, start, end, fn):
        for cb in self.duration:
            cb(event, end - start, fun_name=fn)
        for cb in self.span:
            cb(event, start, end, fun_name=fn)

    def stage(self, event, start, end, fn, inside=()):
        """One whole stage; `inside` are the cache's events, sent
        between its two ends as JAX sends them."""
        self.open(event, start, fn)
        for name, *value in inside:
            if value:
                for cb in self.duration:
                    cb(name, *value)
            else:
                for cb in self.event:
                    cb(name)
        self.close(event, start, end, fn)


class Clock:
    t = 100.0

    def __call__(self):
        return self.t


@pytest.fixture
def by_hand():
    heard, clock = Heard(), Clock()
    led = CompileLedger(clock=clock)
    led.install(heard)
    return led, heard, clock


# -- the ledger on JAX's own events ----------------------------------------

def test_two_shapes_are_two_events_of_each_stage_and_one_recompile(ledger):
    fired, tracer = [], obs.Tracer()
    watch = CompileWatch(tracer=tracer, ledger=ledger,
                         on_recompile=lambda *a: fired.append(a))

    def _double(x):
        return x * 2

    f = jax.jit(_double)
    assert watch.watch(f, "fn") is f
    f(jnp.ones((2,)))            # the first: expected, free
    f(jnp.ones((2,)))            # steady
    assert watch.counts() == {"fn": 0} and fired == []
    f(jnp.ones((3,)))            # a new shape: one retrace
    assert watch.counts() == {"fn": 1}
    assert fired == [("fn", "_double")]
    f(jnp.ones((3,)))
    f(jnp.ones((2,)))            # both seen before
    assert watch.counts() == {"fn": 1} and len(fired) == 1
    (row,) = [r for r in ledger.rows() if r["program"] == "_double"]
    assert (row["traces"], row["backends"]) == (2, 2)
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    assert row["first_seen"] < row["last_seen"]
    # the span names the program and carries its three stages' seconds
    (trace,) = tracer.traces(name="recompile")
    attrs = trace["spans"][0]["attrs"]
    assert attrs["fn"] == "fn" and attrs["program"] == "_double"
    assert attrs["counted_by"] == "dispatch_cache"
    assert {"trace_s", "lower_s", "backend_s"} <= set(attrs)


def test_a_plain_callable_is_refused():
    with pytest.raises(TypeError, match="jitted function"):
        CompileWatch().watch(lambda x: x, "fn")


def test_the_event_marks_the_watch_and_the_next_event_books_it(ledger):
    """No `counts()` between: the retrace is booked when JAX next
    reports a stage, of whatever program."""
    fired = []
    watch = CompileWatch(ledger=ledger,
                         on_recompile=lambda *a: fired.append(a))

    def _inc(x):
        return x + 1

    f = jax.jit(_inc)
    watch.watch(f, "fn")
    f(jnp.ones((2,)))
    f(jnp.ones((5,)))            # the entry lands once the call returns
    assert fired == []
    jax.jit(lambda x: x - 1)(jnp.ones((7,)))    # any later event
    assert fired == [("fn", "_inc")]


def test_a_function_traced_inside_another_is_no_program(ledger):
    @jax.jit
    def _inner(x):
        return x + 1

    @jax.jit
    def _outer(x):
        return jnp.where(x > 0, _inner(x), 0)

    _outer(jnp.ones((4,)))
    rows = {r["program"]: r for r in ledger.rows()}
    assert rows["_outer"]["traces"] == 1 and rows["_outer"]["backends"] == 1
    assert "_inner" not in rows and "_where" not in rows
    totals = ledger.totals()
    assert totals["traces"] == totals["backends"]


def test_registering_twice_is_harmless(ledger):
    ledger.install(jax.monitoring)
    ledger.install(jax.monitoring)

    @jax.jit
    def _once(x):
        return x * 3

    _once(jnp.ones((2,)))
    (row,) = [r for r in ledger.rows() if r["program"] == "_once"]
    assert (row["traces"], row["backends"]) == (1, 1)


def test_rows_are_bounded(by_hand):
    led, heard, _ = by_hand
    for i in range(500):
        heard.stage(TRACE, 1.0, 2.0, f"fn{i}")
        heard.stage(BACKEND, 2.0, 3.0, f"jit(fn{i})")
    rows = led.rows()
    assert len(rows) == MAX_PROGRAMS + 1 < 500
    other = next(r for r in rows if r["program"] == obs.OVERFLOW_LABEL)
    assert other["traces"] == 500 - MAX_PROGRAMS
    assert led.totals()["backends"] == 500


# -- the ledger on events sent by hand -------------------------------------

def test_program_name_joins_the_stages_of_one_program():
    assert program_name("jit(_step)") == program_name("_step") == "_step"
    assert program_name("pmap(f)") == "f"
    assert program_name("jit(") == "jit("


def test_cache_events_go_to_the_backend_stage_they_fire_in(by_hand):
    led, heard, _ = by_hand
    heard.stage(TRACE, 1.0, 3.0, "_step")
    heard.stage(LOWER, 3.0, 4.0, "jit(_step)")
    heard.stage(BACKEND, 4.0, 4.5, "jit(_step)",
                inside=[(REQUEST,), (HIT,), (CACHE_READ_EVENT, 0.4)])
    heard.stage(TRACE, 5.0, 6.0, "_adopt")
    heard.stage(BACKEND, 6.0, 36.0, "jit(_adopt)",
                inside=[(REQUEST,), (WRITE,)])
    heard.stage(BACKEND, 40.0, 41.0, "jit(callback)")   # cache not asked
    rows = {r["program"]: r for r in led.rows()}
    assert [r["program"] for r in led.rows()] == ["_adopt", "_step",
                                                  "callback"]
    step = rows["_step"]
    assert (step["trace_s"], step["lower_s"], step["backend_s"]) \
        == (2.0, 1.0, 0.5)
    assert step["cache_read_s"] == 0.4
    assert (step["cache_requests"], step["cache_hits"],
            step["cache_writes"]) == (1, 1, 0)
    assert (step["first_seen"], step["last_seen"]) == (1.0, 4.5)
    assert rows["_adopt"]["cache_writes"] == 1
    totals = led.totals()
    assert totals["backends"] == 3 and totals["cache_hits"] == 1
    assert totals["compiled"] == 2      # the miss and the one not asked
    # only what was first seen before a moment
    assert led.totals(first_seen_before=5.0)["backends"] == 1
    assert led.totals(first_seen_before=40.0)["compiled"] == 1


def test_seconds_are_self_time(by_hand):
    """An eager operation compiled while a function is traced is its
    own program, and its time leaves the trace's."""
    led, heard, _ = by_hand
    heard.open(TRACE, 10.0, "_step")
    heard.stage(TRACE, 10.5, 10.6, "where")             # folded
    heard.stage(BACKEND, 11.0, 12.5, "jit(iota)")       # its own row
    heard.close(TRACE, 10.0, 14.0, "_step")
    rows = {r["program"]: r for r in led.rows()}
    assert set(rows) == {"_step", "iota"}
    assert rows["_step"]["trace_s"] == 4.0 - 1.5
    assert rows["iota"]["backend_s"] == 1.5
    assert led.first_program_at == 10.0


def test_start_up_spans_sum_by_name_with_what_opened_under_them(by_hand):
    led, heard, clock = by_hand
    assert led.before_first_program_s() is None
    with led.span("startup.batcher"):
        clock.t = 101.0
        with led.span("startup.engine"):
            heard.stage(BACKEND, 101.0, 103.0, "jit(zeros)")
            clock.t = 104.0
        clock.t = 110.0
    with led.span("startup.engine"):
        clock.t = 110.5
    with led.span("not.a.start-up.span"):       # a closed set
        clock.t = 111.0
    spans = led.spans()
    assert set(spans) == {"startup.batcher", "startup.engine",
                          obs.OVERFLOW_LABEL}
    assert spans["startup.batcher"] == {
        "count": 1, "start": 100.0, "seconds": 10.0, "children_s": 3.0}
    assert spans["startup.engine"] == {
        "count": 2, "start": 101.0, "seconds": 3.5, "children_s": 2.0}
    # installed at 100, the first program's first stage at 101
    assert led.before_first_program_s() == 1.0
    seconds = led.startup_seconds()
    assert tuple(seconds) == obs.STARTUP_PHASES
    assert seconds["engine"] == 3.5 and seconds["warmup"] == 0.0
    assert seconds["before_first_program"] == 1.0
    snap = led.snapshot(top=1)
    json.dumps(snap)                            # numbers and strings
    assert len(snap["compiles"]["programs"]) == 1
    assert snap["startup"]["spans"]["startup.engine"]["count"] == 2


def test_a_span_is_an_annotation_where_a_factory_is_given():
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    led = CompileLedger()
    led.install(Heard(), annotate=Annotation)
    with led.span("startup.warmup"):
        pass
    assert opened == [("enter", "startup.warmup"),
                      ("exit", "startup.warmup")]


class _NamedStub:
    """What another JAX's jitted function might be: it lowers, it has
    a name, it has no `_cache_size`."""

    __name__ = "_step"

    def lower(self, *a):
        raise NotImplementedError


def test_without_cache_size_the_watch_counts_events_by_name(by_hand):
    led, heard, _ = by_hand
    tracer = obs.Tracer()
    watch = CompileWatch(tracer=tracer, ledger=led)
    stub = _NamedStub()
    watch.watch(stub, "decode_step")
    for start in (1.0, 5.0, 9.0):
        heard.stage(TRACE, start, start + 1, "_step")
        heard.stage(BACKEND, start + 1, start + 2, "jit(_step)")
    heard.stage(BACKEND, 20.0, 21.0, "jit(_adopt)")     # not watched
    assert watch.counts() == {"decode_step": 2}
    attrs = tracer.traces(name="recompile")[0]["spans"][0]["attrs"]
    assert attrs["counted_by"] == "events_by_name"


def test_a_watch_dies_with_its_owner_and_forgets_a_dead_function(ledger):
    watch = CompileWatch(ledger=ledger)
    f = jax.jit(lambda x: x * 5)
    watch.watch(f, "fn")
    f(jnp.ones((2,)))
    f(jnp.ones((3,)))
    assert watch.counts() == {"fn": 1}
    del f
    gc.collect()
    assert watch.counts() == {"fn": 1}          # kept; nothing to read
    assert watch._watched["fn"] == []
    assert len(ledger._watches) == 1
    del watch
    gc.collect()
    assert len(ledger._watches) == 0


# -- the batcher and the trainer -------------------------------------------

def _engine(max_len=64):
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        LLAMA_FAMILY,
    )

    cfg = llama.LLAMA_TINY
    params = jax.device_put(dict(llama.init(jax.random.key(0), cfg)),
                            jax.devices()[0])
    return InferenceEngine(params, cfg, LLAMA_FAMILY,
                           EngineConfig(max_len=max_len)), cfg


def _batcher(engine, **kw):
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    kw = {"max_slots": 2, "chunk": 2, "kv_block_size": 8,
          "prefill_chunk_tokens": 16, **kw}
    return ContinuousBatcher(engine, asyncio.Lock(), **kw)


async def test_a_warmed_batcher_serves_20_requests_without_a_ledger_call(
        process_ledger):
    engine, cfg = _engine()
    b = _batcher(engine)
    ce = b.cengine
    # no wrapper: what the engine dispatches is JAX's jitted function
    for fn in (ce._step_jit, ce._append_jit, ce._reset_jit):
        assert type(fn).__name__ == "PjitFunction"
        assert callable(fn._cache_size)
    assert ce._step_jit.__wrapped__ == ce._step
    gen = np.random.default_rng(7)

    def prompt(n):
        return gen.integers(0, cfg.vocab_size, n).tolist()

    try:
        b.warmup()
        for _ in range(2):      # the worker's own first passes
            await asyncio.gather(*(b.submit(prompt(9), 5, ())
                                   for _ in range(2)))
        spans = process_ledger.spans()
        assert {"startup.engine", "startup.batcher",
                "startup.warmup"} <= set(spans)
        counts = b.compile_watch.counts()
        calls = process_ledger.calls
        for _ in range(10):
            await asyncio.gather(*(b.submit(prompt(9), 5, ())
                                   for _ in range(2)))
        assert process_ledger.calls == calls     # 20 requests: 0 calls
        assert b.compile_watch.counts() == counts
    finally:
        await b.close()


def _trainer(registry=None):
    from kubeflow_tpu.controlplane.metrics import Registry
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.parallel import MeshSpec, create_mesh
    from kubeflow_tpu.train import TrainConfig, Trainer

    cfg = llama.LLAMA_TINY
    return Trainer(
        mesh=create_mesh(MeshSpec(data=1), devices=jax.devices()[:1]),
        apply_fn=lambda p, t: llama.apply(p, cfg, t),
        init_fn=lambda k: llama.init(k, cfg),
        logical_axes=llama.param_logical_axes(cfg),
        train_config=TrainConfig(warmup_steps=1, total_steps=20),
        registry=registry if registry is not None else Registry(),
        tracer=obs.Tracer()), cfg


def _by_phase(families: dict, name: str) -> dict[str, float]:
    return {dict(labels)["phase"]: value for (_, labels), value
            in families[name]["samples"].items()}


def test_a_warmed_trainer_runs_5_steps_without_a_ledger_call(process_ledger):
    from kubeflow_tpu.controlplane.metrics import Registry
    from kubeflow_tpu.obs import parse_exposition

    reg = Registry()
    tr, cfg = _trainer(reg)
    assert type(tr._jit_step).__name__ == "PjitFunction"   # no wrapper
    state = tr.init(jax.random.key(0))
    tok = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, 16)), jnp.int32)
    tgt = jnp.roll(tok, -1, axis=1)
    for _ in range(2):
        state, loss = tr.step(state, tok, tgt)
    float(loss)
    spans = process_ledger.spans()
    assert spans["startup.first_step"]["count"] >= 1
    assert spans["startup.trainer"]["count"] >= 2          # built, state
    assert tr._compile_watch.counts() == {"train_step": 0}
    calls = process_ledger.calls
    for _ in range(5):
        state, loss = tr.step(state, tok, tgt)
    float(loss)
    assert process_ledger.calls == calls                   # 5 steps: 0
    # a new batch shape is one retrace, in the scrape that follows it
    tok2 = jnp.asarray(np.zeros((2, 24)), jnp.int32)
    tr.step(state, tok2, tok2)
    fams = parse_exposition(reg.render())
    assert fams["train_recompiles_total"]["samples"][
        ("train_recompiles_total", (("fn", "train_step"),))] == 1
    started = _by_phase(fams, "train_startup_seconds")
    assert set(started) == set(obs.STARTUP_PHASES)
    assert started["first_step"] > 0 and started["trainer"] > 0


async def test_a_second_batcher_leaves_the_first_ones_counts(process_ledger):
    """The reload case: another engine's `_step` compiles under the
    same program name and the first batcher's counts stay; a new shape
    through the first one's own `_step` moves them by one."""
    engine, cfg = _engine()
    fired = []
    first = _batcher(engine)
    first.compile_watch.on_recompile = lambda *a: fired.append(a)
    try:
        first.warmup()
        warmed = first.compile_watch.counts()
        assert warmed["decode_step"] == 1          # steps 1 and 2
        assert fired and all(p in ("_step", "_reset_slots", "_append_rows")
                             for _, p in fired)
        del fired[:]
        engine2, _ = _engine()
        second = _batcher(engine2)
        try:
            second.warmup()                        # `_step` twice more
            assert second.compile_watch.counts() == warmed
        finally:
            await second.close()
        assert first.compile_watch.counts() == warmed and fired == []
        ce = first.cengine
        st = ce.init_slots()
        ce.step(st, first._sp(), first._rng, 3)    # steps=3: new to it
        moved = first.compile_watch.counts()
        assert moved == {**warmed, "decode_step": 2}
        assert fired == [("decode_step", "_step")]
    finally:
        await first.close()


def test_a_change_of_the_pools_shape_alone_is_counted():
    """`SlotState` is no tuple, list or dict: the signature the watch
    once built held its type's name and none of its shapes."""
    from kubeflow_tpu.serving.continuous import ContinuousEngine

    engine, _ = _engine()
    ce = ContinuousEngine(engine, max_slots=2, block_size=8)
    watch = CompileWatch()
    watch.watch(ce._reset_jit, "reset_slots")
    st = ce.init_slots()
    ce.reset_slots(st, [0])
    assert watch.counts() == {"reset_slots": 0}
    st = ce.init_slots()
    grown = st.replace(k=jnp.concatenate([st.k, st.k], axis=1),
                       v=jnp.concatenate([st.v, st.v], axis=1))
    ce.reset_slots(grown, [0])                  # twice the blocks
    assert watch.counts() == {"reset_slots": 1}


# -- the operator's view ---------------------------------------------------

def test_serving_metrics_seed_the_start_up_phases(process_ledger):
    from kubeflow_tpu.obs import parse_exposition
    from kubeflow_tpu.serving.server import ServingObs

    sobs = ServingObs()
    started = _by_phase(parse_exposition(sobs.registry.render()),
                        "serving_startup_seconds")
    assert set(started) == set(obs.STARTUP_PHASES)
    assert started["before_first_program"] == pytest.approx(
        process_ledger.before_first_program_s() or 0.0)


def test_compile_cache_enable_installs_the_process_ledger_once():
    compile_cache._install_ledger()
    led = obs.compile_ledger()
    assert led is compiles.LEDGER and led.installed
    from jax._src import monitoring

    stamped, n = led.installed_at, len(monitoring.get_scalar_listeners())
    compile_cache.enable()
    assert led.installed_at == stamped
    assert len(monitoring.get_scalar_listeners()) == n


# -- a warm second process -------------------------------------------------

_WARM_PROCESS = textwrap.dedent("""
    import asyncio, json, sys
    sys.path.insert(0, {repo!r})
    from kubeflow_tpu import compile_cache, obs
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import (EngineConfig, InferenceEngine,
                                      LLAMA_FAMILY)
    from kubeflow_tpu.serving.continuous import ContinuousBatcher
    cfg = llama.LLAMA_TINY
    engine = InferenceEngine(llama.init(jax.random.key(0), cfg), cfg,
                             LLAMA_FAMILY, EngineConfig(max_len=32))
    b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2, chunk=1,
                          kv_block_size=8, prefill_chunk_tokens=8)
    b.warmup()
    led = obs.compile_ledger()
    print(json.dumps({{"totals": led.totals(),
                      "before": led.before_first_program_s(),
                      "spans": sorted(led.spans())}}))
""")


def test_a_warm_second_process_compiles_nothing(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _WARM_PROCESS.format(repo=REPO)],
            env=env, capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = (r["totals"] for r in runs)
    assert cold["compiled"] > 0 and cold["cache_hits"] == 0
    assert warm["compiled"] == 0
    assert warm["backends"] == cold["backends"] == warm["cache_hits"]
    assert warm["cache_read_s"] > 0
    assert runs[1]["before"] > 0
    assert runs[1]["spans"] == ["startup.batcher", "startup.engine",
                                "startup.import_jax", "startup.warmup"]
