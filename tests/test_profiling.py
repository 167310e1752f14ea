"""Step-anatomy profiling plane (ISSUE 8): PhaseProfiler attribution
invariants, quantile-interpolation pins, compile-watch
semantics, and the batcher/server integration.

The attribution contract under test everywhere: phase durations are
EXCLUSIVE (nesting subtracts child time) and `begin_iteration` /
`end_iteration` book the residual as `host_gap`, so phase sums equal
the measured wall by construction — no double counting, even across a
preempt/resume replay.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

pytest_plugins = ("aiohttp.pytest_plugin",)

from kubeflow_tpu import obs
from kubeflow_tpu.obs import OVERFLOW_LABEL
from kubeflow_tpu.obs.metrics import Histogram, sample_quantile
from kubeflow_tpu.obs.profiling import (
    SERVING_PHASES,
    WATCHED_SERVING_FNS,
    PhaseProfiler,
    merge_counter_tracks,
)
from kubeflow_tpu.utils.profiling import StepTimer


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


# -- PhaseProfiler ---------------------------------------------------------


def test_exclusive_nesting_and_host_gap_residual():
    """admit contains a prefill slice; the parent records only its EXCLUSIVE
    time, and end_iteration books the unclaimed residual as host_gap —
    so the totals sum exactly to the iteration wall."""
    clk = FakeClock()
    p = PhaseProfiler(clock=clk, wall_clock=clk)
    p.begin_iteration()
    with p.phase("admit"):
        clk.t = 1.0
        with p.phase("prefill_chunk", tokens=16):
            clk.t = 3.0
        clk.t = 3.5
    with p.phase("decode", tokens=8):
        clk.t = 5.5
    clk.t = 6.0
    p.end_iteration()

    t = p.totals()
    assert t["admit"] == pytest.approx(1.5)    # 3.5 wall - 2.0 child
    assert t["prefill_chunk"] == pytest.approx(2.0)
    assert t["decode"] == pytest.approx(2.0)
    assert t["host_gap"] == pytest.approx(0.5)  # 6.0 - 5.5 claimed
    assert sum(t.values()) == pytest.approx(6.0)
    assert p.wall_s() == pytest.approx(6.0)
    toks = p.phase_tokens()
    assert toks["prefill_chunk"] == 16 and toks["decode"] == 8


class FakeAnnotations:
    """Stands in for `jax.profiler.TraceAnnotation`: records what was
    opened and closed, in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **stats):
        import contextlib

        @contextlib.contextmanager
        def span():
            self.log.append(("open", name, stats))
            try:
                yield
            finally:
                self.log.append(("close", name))
        return span()


def test_phases_are_also_annotated_with_their_name_and_tokens():
    """With an `annotate` factory every phase is also a span
    `sched.<phase>` with its tokens, nested under `sched.iteration`,
    which carries the counts `begin_iteration` was given."""
    clk, ann = FakeClock(), FakeAnnotations()
    p = PhaseProfiler(clock=clk, wall_clock=clk, annotate=ann)
    p.begin_iteration(active=3, pending=1)
    with p.phase("admit"):
        with p.phase("prefill_chunk", tokens=16):
            clk.t = 2.0
    with p.phase("decode", kv_blocks_live=9):   # a span's own stats
        clk.t = 3.0
    p.end_iteration()
    assert ann.log == [
        ("open", "sched.iteration", {"active": 3, "pending": 1}),
        ("open", "sched.admit", {"tokens": 0}),
        ("open", "sched.prefill_chunk", {"tokens": 16}),
        ("close", "sched.prefill_chunk"), ("close", "sched.admit"),
        ("open", "sched.decode", {"tokens": 0, "kv_blocks_live": 9}),
        ("close", "sched.decode"),
        ("close", "sched.iteration")]
    # a pass that never reached end_iteration (the worker's `continue`
    # on a failure) is closed by the next begin: spans never interleave
    del ann.log[:]
    p.begin_iteration()
    p.begin_iteration()
    p.end_iteration()
    p.end_iteration()                       # a second end closes nothing
    assert [e[:2] for e in ann.log] == [
        ("open", "sched.iteration"), ("close", "sched.iteration"),
        ("open", "sched.iteration"), ("close", "sched.iteration")]
    # record() and add_tokens() are retrospective: no span
    del ann.log[:]
    p.record("host_gap", 0.5)
    p.add_tokens("decode", 4)
    assert ann.log == []


def test_train_phases_are_annotated_under_their_own_prefix():
    ann = FakeAnnotations()
    p = PhaseProfiler(phases=obs.TRAIN_PHASES, annotate=ann)
    with p.phase("step", tokens=4096):
        pass
    assert ann.log == [("open", "train.step", {"tokens": 4096}),
                       ("close", "train.step")]


def test_a_profiler_without_annotate_accounts_exactly_as_one_with():
    def drive(p, clk):
        p.begin_iteration(active=1)
        with p.phase("admit"):
            clk.t += 1.0
            with p.phase("prefill_chunk", tokens=16):
                clk.t += 2.0
        with p.phase("decode", tokens=8):
            clk.t += 2.0
        clk.t += 0.5
        p.end_iteration()
        return p.totals(), p.phase_tokens(), p.goodput()

    ca, cb = FakeClock(), FakeClock()
    plain = drive(PhaseProfiler(clock=ca, wall_clock=ca), ca)
    annotated = drive(PhaseProfiler(clock=cb, wall_clock=cb,
                                    annotate=FakeAnnotations()), cb)
    assert plain == annotated
    assert plain[0]["admit"] == 1.0 and plain[0]["host_gap"] == 0.5


def test_obs_imports_no_jax():
    """`annotate` is handed in by the owner: obs itself stays
    importable in a process without JAX."""
    import subprocess
    import sys

    code = ("import sys; import kubeflow_tpu.obs, "
            "kubeflow_tpu.obs.profiling, kubeflow_tpu.obs.timeline; "
            "bad = [m for m in sys.modules if m == 'jax' "
            "or m.startswith('jax.') or m.startswith('jaxlib')]; "
            "print(bad); sys.exit(1 if bad else 0)")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


async def test_the_batcher_and_the_trainer_hand_over_the_profilers_own_span():
    """The owners pass `jax.profiler.TraceAnnotation` itself: one
    tracing path, the profiler's."""
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving.continuous import ContinuousBatcher
    from kubeflow_tpu.serving.engine import (LLAMA_FAMILY, EngineConfig,
                                             InferenceEngine)

    cfg = llama.LLAMA_TINY
    engine = InferenceEngine(llama.init(jax.random.key(0), cfg), cfg,
                             LLAMA_FAMILY, EngineConfig(max_len=64))
    batcher = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2)
    try:
        assert batcher.profiler._annotate is jax.profiler.TraceAnnotation
    finally:
        await batcher.close()

    from kubeflow_tpu.parallel import MeshSpec, create_mesh
    from kubeflow_tpu.train.trainer import TrainConfig, Trainer

    tr = Trainer(
        mesh=create_mesh(MeshSpec(data=2, fsdp=2, tensor=2)),
        apply_fn=lambda p, t: llama.apply(p, cfg, t),
        init_fn=lambda k: llama.init(k, cfg),
        logical_axes=llama.param_logical_axes(cfg),
        train_config=TrainConfig(), tracer=obs.Tracer())
    assert tr.profiler._annotate is jax.profiler.TraceAnnotation
    assert tr.profiler._span_prefix == "train."


def test_unknown_phase_collapses_to_overflow_label():
    p = PhaseProfiler(phases=("decode",))
    p.record("decode", 1.0)
    p.record("surprise_phase", 2.0)
    t = p.totals()
    assert "surprise_phase" not in t
    assert t[OVERFLOW_LABEL] == pytest.approx(2.0)


def test_goodput_excludes_idle_and_tracks_high_water():
    clk = FakeClock()
    p = PhaseProfiler(clock=clk, wall_clock=clk)
    with p.phase("idle"):
        clk.t = 10.0           # parked: must not count as a bubble
    with p.phase("decode", tokens=4):
        clk.t = 13.0
    p.record("host_gap", 1.0)
    p.note_pool(3, 8)
    p.note_pool(5, 8)
    p.note_pool(2, 8)
    p.note_occupancy(2, 4)
    g = p.goodput()
    assert g["busy_s"] == pytest.approx(4.0)   # decode 3 + host_gap 1
    assert g["idle_s"] == pytest.approx(10.0)
    assert g["goodput_ratio"] == pytest.approx(3.0 / 4.0)
    assert g["bubble_fraction"] == pytest.approx(1.0 / 4.0)
    assert g["kv_blocks_high_water"] == 5
    assert g["kv_blocks_capacity"] == 8
    assert g["occupancy_high_water"] == 2 and g["slots"] == 4


def test_counter_events_are_chrome_counter_tracks():
    p = PhaseProfiler()
    p.note_pool(3, 8)
    p.note_occupancy(1, 4)
    evs = p.counter_events(prefix="m")
    assert {e["name"] for e in evs} == {"m.kv_blocks",
                                        "m.batch_occupancy"}
    for e in evs:
        assert e["ph"] == "C" and "ts" in e
        assert isinstance(e["args"], dict)
    # merge into a traces payload in place; summary payloads untouched
    payload = {"traceEvents": [{"name": "x", "ph": "X"}]}
    merge_counter_tracks(payload, evs)
    assert len(payload["traceEvents"]) == 3
    assert merge_counter_tracks({"summary": 1}, evs) == {"summary": 1}


def test_add_tokens_books_tokens_without_a_timing_sample():
    p = PhaseProfiler()
    seen = []
    p.on_phase = lambda name, secs, toks: seen.append((name, secs, toks))
    p.add_tokens("decode", 7)
    snap = p.snapshot()
    assert snap["phases"]["decode"]["tokens"] == 7
    assert snap["phases"]["decode"]["count"] == 0
    assert seen == [("decode", None, 7)]


def test_on_phase_hook_exceptions_are_swallowed():
    p = PhaseProfiler()
    p.on_phase = lambda *a: (_ for _ in ()).throw(RuntimeError("boom"))
    p.record("decode", 0.5)   # must not raise
    assert p.totals()["decode"] == pytest.approx(0.5)


def test_snapshot_percentiles_use_sample_quantile():
    p = PhaseProfiler()
    xs = [0.01 * i for i in range(1, 11)]
    for x in xs:
        p.record("decode", x)
    snap = p.snapshot()["phases"]["decode"]
    assert snap["p50_s"] == pytest.approx(sample_quantile(xs, 0.50))
    assert snap["p95_s"] == pytest.approx(sample_quantile(xs, 0.95))


# -- quantile interpolation pins ------------------------------------------


def test_sample_quantile_interpolates_order_statistics():
    xs = [float(i) for i in range(1, 11)]   # 1..10
    # q*(n-1) order-statistic interpolation — the naive index pick the
    # old StepTimer.summary used returned xs[5] == 6.0 here
    assert sample_quantile(xs, 0.50) == pytest.approx(5.5)
    assert sample_quantile(xs, 0.90) == pytest.approx(9.1)
    assert sample_quantile(xs, 0.0) == pytest.approx(1.0)
    assert sample_quantile(xs, 1.0) == pytest.approx(10.0)
    assert sample_quantile([2.5], 0.99) == pytest.approx(2.5)


def test_step_timer_summary_matches_histogram_interpolation():
    t = StepTimer()
    for d in range(1, 11):
        t.record(float(d))
    s = t.summary()
    assert s["count"] == 10
    assert s["p50_s"] == pytest.approx(5.5)    # NOT the naive 6.0
    assert s["p90_s"] == pytest.approx(9.1)
    assert s["p99_s"] == pytest.approx(9.91)
    assert s["max_s"] == pytest.approx(10.0)
    # and the StepTimer aggregates into its PhaseProfiler
    assert t.profiler.totals()["train.step"] == pytest.approx(55.0)


def test_histogram_quantile_within_bucket_interpolation():
    h = Histogram("q_seconds", "test", buckets=(1.0, 2.0, 4.0))
    assert h.quantile(0.5) is None              # no observations
    for v in (1.5, 3.0, 3.5):
        h.observe(v)
    # rank 1.5 of 3 lands in the (1, 2] bucket: 1 + (2-1) * 1.5/1... no:
    # acc=0 at (<=1, c=0); (<=2, c=1): 0+1 < 1.5; (<=4, c=2):
    # 2 + (4-2) * (1.5-1)/2 = 2.5
    assert h.quantile(0.5) == pytest.approx(2.5)
    # q=1.0 clamps into the last finite bound, never +Inf
    assert h.quantile(1.0) == pytest.approx(4.0)


def test_histogram_seed_renders_zero_row():
    from kubeflow_tpu.controlplane.metrics import Registry
    from kubeflow_tpu.obs.exposition import parse_exposition

    reg = Registry()
    h = Histogram("seeded_seconds", "test", registry=reg)
    h.seed(phase="decode")
    fams = parse_exposition(reg.render())
    key = ("seeded_seconds_count", (("phase", "decode"),))
    assert fams["seeded_seconds"]["samples"][key] == 0


# -- CompileWatch: tests/test_compile_ledger.py ----------------------------

# -- batcher / trainer / server integration --------------------------------


def _engine(max_len=64):
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        LLAMA_FAMILY,
    )

    cfg = llama.LLAMA_TINY
    params = dict(llama.init(jax.random.key(0), cfg))
    params["lm_head"] = params["lm_head"] * 50.0   # argmax can't flip
    return InferenceEngine(params, cfg, LLAMA_FAMILY,
                           EngineConfig(max_len=max_len)), cfg


@pytest.mark.slow
async def test_batcher_anatomy_reconciles_and_steady_state_recompiles():
    """Phase sums == wall (the attribution invariant) on a real
    workload; an identical second pass adds ZERO retraces — the
    acceptance pin for 'steady-state decode shows no recompiles'."""
    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    engine, cfg = _engine()
    gen = np.random.default_rng(4)
    prompts = [gen.integers(0, cfg.vocab_size, n).tolist()
               for n in (4, 7)]
    b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2)
    try:
        for _ in range(2):  # pass 2 also flushes the deferred
            # slot-recycle program's first compile
            await asyncio.gather(*(b.submit(p, 6, ()) for p in prompts))
        counts_warm = dict(b.compile_watch.counts())
        before = b.profiler.totals()
        await asyncio.gather(*(b.submit(p, 6, ()) for p in prompts))
        assert b.compile_watch.counts() == counts_warm, \
            "identical steady-state pass must not retrace"
        after = b.profiler.totals()
        # every phase of the serving anatomy exists in the totals
        assert set(SERVING_PHASES) <= set(after)
        delta = {p: after[p] - before.get(p, 0.0) for p in after}
        snap = b.profiler.snapshot()
        assert snap["goodput"]["goodput_ratio"] > 0
        assert snap["goodput"]["kv_blocks_high_water"] > 0
        # decode tokens are booked once per emitted token
        assert snap["phases"]["decode"]["tokens"] == b.tokens_emitted
        assert delta["decode"] > 0
    finally:
        await b.close()


@pytest.mark.slow
async def test_preempt_resume_phases_no_double_counted_decode():
    """A preempted-and-resumed request marks preempt/resume phases and
    its replayed tokens are NOT re-counted: profiler decode tokens ==
    batcher tokens_emitted == the sum of timeline token stamps, and the
    profiler's observed wall covers every timeline stamp."""
    from kubeflow_tpu.serving.continuous import ContinuousBatcher
    from kubeflow_tpu.tenancy import config_from_dict

    engine, cfg = _engine()
    qos = {"tenants": {"live": {"priority": "interactive"},
                       "bulk": {"priority": "batch"}}}
    p1, p2, p3 = [3, 5, 7, 11], [4, 6, 8, 10], [9, 2, 4, 8]
    b = ContinuousBatcher(engine, asyncio.Lock(), max_slots=2,
                          tenancy=config_from_dict(qos))
    try:
        f1 = asyncio.ensure_future(
            b.submit(p1, 24, (("tenant", "bulk"),)))
        f2 = asyncio.ensure_future(
            b.submit(p2, 24, (("tenant", "bulk"),)))
        for _ in range(400):
            if len(b._active) == 2:
                break
            await asyncio.sleep(0.02)
        assert len(b._active) == 2
        got3 = await b.submit(p3, 8, (("tenant", "live"),))
        await f1
        await f2
        assert b.preemptions >= 1
        assert len(got3) == 8

        snap = b.profiler.snapshot()
        tls = list(b.timelines._items.values())
        # phase markers reconcile against the timeline event stream
        tl_events = [kind for tl in tls for (_t, kind, _d) in tl.events]
        assert snap["phases"]["preempt"]["count"] == b.preemptions
        assert snap["phases"]["preempt"]["count"] == \
            tl_events.count("preempt")
        assert snap["phases"]["resume"]["count"] == \
            tl_events.count("resume") >= 1
        # every emitted token was stamped exactly once — a replayed
        # request resumes from its kept output, never re-emits
        stamps = [t for tl in tls for t in tl.tokens]
        assert len(stamps) == 24 + 24 + 8
        # decode-token accounting excludes the admission-time first
        # token of each (re)admission: 3 submits + one per resume —
        # NOT the replayed output, which would inflate this by ~24
        resumes = tl_events.count("resume")
        assert b.tokens_emitted == len(stamps) - 3 - resumes
        assert snap["phases"]["decode"]["tokens"] == b.tokens_emitted
        # the profiler's observed wall window covers the stamp range
        # (same monotonic clock), so /debug/profile totals and the
        # timelines describe the SAME span of time
        assert snap["wall_s"] >= (max(stamps) - min(stamps)) - 1e-6
        busy = sum(v["total_s"] for p, v in snap["phases"].items()
                   if p != "idle")
        assert busy <= snap["wall_s"] + 1e-6
        assert busy >= 0.5 * (max(stamps) - min(stamps))
    finally:
        await b.close()


@pytest.mark.slow
async def test_debug_profile_endpoint_and_zero_seeded_families():
    """`/debug/profile` serves the anatomy; `/metrics` exposes every
    step-anatomy family zero-seeded over the closed phase/fn sets; the
    counter tracks ride `/debug/traces`."""
    import json

    from kubeflow_tpu.obs.exposition import parse_exposition
    from kubeflow_tpu.serving import server as server_lib

    engine, cfg = _engine()
    app = server_lib.create_serving_app(
        {"m": engine}, continuous=True, max_batch=2)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        gen = np.random.default_rng(9)
        rs = await asyncio.gather(*(
            client.post("/v1/models/m:generate",
                        json={"tokens": [gen.integers(
                            0, cfg.vocab_size, 5).tolist()],
                            "max_new": 4})
            for _ in range(2)))
        assert all(r.status == 200 for r in rs)

        prof = await (await client.get("/debug/profile")).json()
        m = prof["models"]["m"]
        assert set(SERVING_PHASES) <= set(m["phases"])
        assert m["phases"]["decode"]["count"] >= 1
        assert m["phases"]["decode"]["tokens"] > 0
        # a batcher without a draft model watches no spec program
        assert set(m["recompiles"]) == (
            set(WATCHED_SERVING_FNS) - {"spec_draft", "spec_verify"})
        assert 0 < m["goodput"]["goodput_ratio"] <= 1
        # /debug/profile totals reconcile: phases sum into the wall
        busy = sum(v["total_s"] for p, v in m["phases"].items()
                   if p != "idle")
        assert busy <= m["wall_s"] * 1.05

        fams = parse_exposition(
            await (await client.get("/metrics")).text())
        phase_counts = {
            dict(labels)["phase"]
            for (s, labels) in fams["serving_step_phase_seconds"]["samples"]
            if s.endswith("_count")}
        assert phase_counts == set(SERVING_PHASES)  # zero-seeded
        fns = {dict(labels)["fn"]
               for (_s, labels) in
               fams["serving_recompiles_total"]["samples"]}
        assert fns == set(WATCHED_SERVING_FNS)
        for fam in ("serving_goodput_ratio", "serving_bubble_fraction",
                    "serving_kv_blocks_high_water",
                    "serving_step_tokens"):
            assert fam in fams, fam
        # goodput gauge reflects the collector at scrape time
        key = ("serving_goodput_ratio", (("model", "m"),))
        assert fams["serving_goodput_ratio"]["samples"][key] > 0

        traces = json.loads(
            await (await client.get("/debug/traces")).text())
        counters = [e for e in traces["traceEvents"]
                    if e.get("ph") == "C"]
        assert counters, "profiler counter tracks missing"
        assert all(e["name"].startswith("m.") for e in counters)
        assert any(e["name"] == "m.phase_seconds" for e in counters)
    finally:
        await client.close()


@pytest.mark.slow
def test_trainer_compile_watch_and_phase_histograms():
    """The trainer shares the plane: a batch-shape change retraces the
    jitted step EXACTLY once (counter + span), steady state is flat,
    and train_step_phase_seconds aggregates step + host_gap."""
    from kubeflow_tpu.models import llama
    from kubeflow_tpu.parallel import MeshSpec, create_mesh
    from kubeflow_tpu.train.trainer import TrainConfig, Trainer

    cfg = llama.LLAMA_TINY
    mesh = create_mesh(MeshSpec(data=2, fsdp=2, tensor=2))
    tr = Trainer(
        mesh=mesh,
        apply_fn=lambda p, t: llama.apply(p, cfg, t),
        init_fn=lambda k: llama.init(k, cfg),
        logical_axes=llama.param_logical_axes(cfg),
        train_config=TrainConfig(learning_rate=1e-2, warmup_steps=2,
                                 total_steps=50),
        tracer=obs.Tracer(),
    )
    state = tr.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                      jnp.int32)
    tgt = jnp.roll(tok, -1, axis=1)
    for _ in range(3):
        state, _ = tr.step(state, tok, tgt)
    assert tr._compile_watch.counts() == {"train_step": 0}
    tok2 = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)),
                       jnp.int32)
    state, _ = tr.step(state, tok2, jnp.roll(tok2, -1, axis=1))
    assert tr._compile_watch.counts() == {"train_step": 1}
    # the watch wraps nothing: the retrace is booked when the step's
    # own cache is next read, in a `recompile` span that names the
    # program
    spans = [s for t in tr.tracer.traces(name="recompile")
             for s in t["spans"]]
    assert len(spans) == 1
    assert spans[0]["attrs"]["fn"] == "train_step"
    assert spans[0]["attrs"]["program"] == "_step"

    t = tr.profiler.totals()
    assert t["step"] > 0 and tr.profiler.phase_tokens()["step"] > 0
    assert t["host_gap"] > 0      # gaps between the 4 steps
    # the labeled histogram saw the same samples
    assert tr.phase_seconds.quantile(0.5, phase="step") is not None
