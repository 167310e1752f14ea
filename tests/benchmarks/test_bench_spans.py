"""The reduction of the program's own spans and scopes
(`benchmarks/spans.py`) on hand-made lists, a CPU-traced run of the
tiny closed cell whose profile holds those spans, and the new readers'
silence off the chip. Times read on the CPU are only checked for
adding up."""

from __future__ import annotations

import os

import pytest

import manifest_rules
import tiny_cells
from benchmarks import harness, spans
from benchmarks.models import llama as model
from manifest_rules import SCOPE_METRICS, SPAN_METRICS

REPO = tiny_cells.REPO


def span(name, start, dur, **stats):
    return (name, start, dur, stats)


# -- host spans ---------------------------------------------------------

WORKER = [
    span("sched.iteration", 0.0, 10.0, active=4),
    span("sched.admit", 1.0, 2.0),
    span("sched.prefill", 1.5, 1.0),          # nested in admit
    span("sched.prefill_chunk", 3.0, 2.0),
    span("sched.decode", 5.0, 3.0),
    span("sched.idle", 12.0, 2.0),
    span("sched.iteration", 15.0, 1.0),
    span("sched.decode", 15.25, 0.5),
]
EXECUTOR = [span("dispatch.prefill_chunk", 3.1, 1.8, tokens=256, finish=1),
            span("dispatch.decode", 5.1, 0.2, steps=4)]


def test_an_iteration_less_the_phases_that_wait_is_the_hosts_time():
    line = spans.worker_line([EXECUTOR, WORKER], "sched.iteration")
    assert line is WORKER
    # 10 - 2 (prefill_chunk) - 3 (decode); the executor's spans lie
    # inside the iteration in time but on another line: not taken away
    assert spans.span_less(
        line, "sched.iteration", ["sched.decode", "sched.prefill_chunk"]
    ) == pytest.approx([5.0, 0.5])
    assert spans.span_less(line + EXECUTOR[:0], "sched.iteration", []) \
        == pytest.approx([10.0, 1.0])
    assert spans.worker_line([EXECUTOR], "sched.iteration") == []
    assert spans.worker_line([], "sched.iteration") == []


def test_nested_spans_cut_into_innermost_pieces():
    assert spans.innermost_segments(WORKER) == [
        (0.0, 1.0, "sched.iteration"), (1.0, 1.5, "sched.admit"),
        (1.5, 2.5, "sched.prefill"), (2.5, 3.0, "sched.admit"),
        (3.0, 5.0, "sched.prefill_chunk"), (5.0, 8.0, "sched.decode"),
        (8.0, 10.0, "sched.iteration"), (12.0, 14.0, "sched.idle"),
        (15.0, 15.25, "sched.iteration"), (15.25, 15.75, "sched.decode"),
        (15.75, 16.0, "sched.iteration")]


def test_idle_gaps_of_a_known_pattern_fall_under_known_phases():
    # one program from 0 to 16; the device is busy except in four gaps
    progs = [("jit__step(1)", 0.0, 16.0)]
    ops = [("a", 0.0, 0.5), ("b", 0.75, 1.0),     # idle 0.5-0.75
           ("c", 1.75, 3.75),                     # idle 5.5-6.0
           ("d", 6.0, 5.0),                       # idle 11.0-13.0
           ("e", 13.0, 2.5),                      # idle 15.5-16.0
           ("nested in d", 7.0, 1.0)]
    idle = spans.idle_intervals(ops, progs)
    assert idle == pytest.approx([(0.5, 0.75), (5.5, 6.0), (11.0, 13.0),
                                  (15.5, 16.0)])
    by_span = spans.idle_by_span(idle, spans.worker_segments([WORKER]))
    assert by_span == pytest.approx({
        "sched.iteration": 0.25 + 0.25,        # 0.5-0.75 and 15.75-16
        "sched.decode": 0.5 + 0.25,            # 5.5-6 and 15.5-15.75
        "sched.idle": 1.0,                     # 12-13
        spans.OUTSIDE: 1.0})                   # 11-12: between iterations
    assert sum(by_span.values()) == pytest.approx(
        sum(b - a for a, b in idle))
    assert spans.idle_intervals([], progs) == []


def test_the_trainers_gap_is_what_lies_between_two_steps():
    line = [span("train.step", 0.0, 0.01, tokens=4096),
            span("train.step", 0.37, 0.01, tokens=4096),
            span("train.step", 0.75, 0.02, tokens=4096)]
    assert spans.gaps_between(line, "train.step") \
        == pytest.approx([0.36, 0.37])
    segments = spans.worker_segments([line])
    # the anatomy is step and host_gap: the gap is all that is no step
    assert [name for _, _, name in segments] == [
        "train.host_gap", "train.step", "train.host_gap", "train.step",
        "train.host_gap", "train.step", "train.host_gap"]
    assert spans.idle_by_span([(-1.0, -0.5), (5.0, 5.5)], segments) \
        == {"train.host_gap": 1.0}
    by_span = spans.idle_by_span([(0.3, 0.375)], segments)
    assert by_span == pytest.approx({"train.host_gap": 0.07,
                                     "train.step": 0.005})


def test_the_three_parts_of_a_first_token():
    line = [span("sched.first_token", 1.0, 0.0, request="a", slices=3,
                 queue_wait_us=2000.0, prefill_us=150000.0,
                 prefill_wait_us=848000.0),
            span("sched.first_token", 2.0, 0.0, request="b", slices=1,
                 queue_wait_us=4000.0, prefill_us=50000.0,
                 prefill_wait_us=146000.0)]
    assert spans.stat_values(line, "sched.first_token", "queue_wait_us") \
        == [2000.0, 4000.0]
    assert spans.stat_values(line, "sched.first_token", "absent") == []

    from benchmarks.readers import spans as readers

    prof = spans.Profile(programs=[], ops=[], lines=[WORKER, line])
    assert readers._stats(prof, "sched.first_token", "prefill_us") \
        == [150000.0, 50000.0]
    total = sum(sum(readers._stats(prof, "sched.first_token", s))
                for s in ("queue_wait_us", "prefill_wait_us", "prefill_us"))
    assert total == pytest.approx(1.2e6)
    assert 100 * (848000.0 + 146000.0) / total == pytest.approx(82.8333333)


# -- scopes -------------------------------------------------------------

def hlo(name, opcode):
    """An operation's event name on the TPU: its HLO instruction,
    without its metadata (that is a record of its own in the file)."""
    return (f"%{name} = bf16[16,1,8,128]{{3,2,1,0:T(8,128)(2,1)}} "
            f"{opcode}(bf16[16,8,128]{{2,1,0}} %p.1)")


@pytest.mark.parametrize("op_name, scope", [
    # as the TPU writes them (PERF.md section 6, PR 25)
    ("jit(_step)/while/body/closed_call/while/body/closed_call/kv_write/"
     "squeeze:", "kv_write"),
    ("jit(_step)/while/body/closed_call/while/body/closed_call/"
     "paged_attention/paged_attention/pallas_call:", "paged_attention"),
    # the XLA gather path runs the decode entry inside the paged one:
    # the outermost scope names the work
    ("jit(_step)/jit(main)/while/body/paged_attention/decode_attention/"
     "dot_general", "paged_attention"),
    ("jit(_step)/transpose(jvp(while))/body/"
     "transpose(jvp(flash_attention))/pallas_call:", "flash_attention"),
    ("jit(_step)/while/body/checkpoint/rematted_computation/mlp/"
     "dot_general:", "mlp"),
    ("jit(_step)/while/body/closed_call/while/body/dynamic_slice:",
     spans.UNSCOPED),
    # a name that only contains a scope's is not that scope
    ("jit(_step)/jit(main)/not_mlp/kv_write_back/add", spans.UNSCOPED),
    ("", spans.UNSCOPED), (None, spans.UNSCOPED),
])
def test_the_scope_is_a_path_component_of_the_op_name(op_name, scope):
    assert spans.op_scope(op_name) == scope


STEP, APPEND = 5005, 6006               # two programs' fingerprints
BODY = "jit(_step)/while/body/closed_call/"
TRACE_OPS = [(hlo("while.4", "while"), 1.0, 10.0),
             (hlo("fusion.7", "fusion"), 2.0, 3.0),
             (hlo("closed_call.3", "custom-call"), 6.0, 4.0),
             (hlo("copy.1", "copy"), 7.0, 1.0),      # nested in the call
             (hlo("fusion.7", "fusion"), 20.0, 0.5)]
TRACE_PROGS = [(f"jit__step({STEP})", 0.5, 11.0),
               (f"jit__step({STEP})", 12.0, 1.0),
               (f"jit__append_rows({APPEND})", 19.0, 2.0)]
# two programs number their operations alike: the same event name, each
# program's own op_name. The while and the copy have no record.
TRACE_NAMES = {
    (STEP, hlo("fusion.7", "fusion")): BODY + "kv_write/squeeze:",
    (STEP, hlo("closed_call.3", "custom-call")):
        BODY + "paged_attention/paged_attention/pallas_call:",
    (APPEND, hlo("fusion.7", "fusion")):
        "jit(_append_rows)/while/body/closed_call/mlp/dot_general:"}


def test_own_device_time_by_program_and_scope():
    got = spans.scope_seconds(TRACE_OPS, TRACE_PROGS, TRACE_NAMES)
    assert got == {
        "jit__step": {"kv_write": pytest.approx(3.0),
                      "paged_attention": pytest.approx(3.0),
                      # the while's own time and the copy without a name
                      spans.UNSCOPED: pytest.approx(3.0 + 1.0)},
        "jit__append_rows": {"mlp": pytest.approx(0.5)}}
    # the scopes of a program sum to the union of its operations
    assert sum(got["jit__step"].values()) == pytest.approx(10.0)
    prof = spans.Profile(programs=TRACE_PROGS, ops=TRACE_OPS, lines=[],
                         op_names=TRACE_NAMES)
    assert prof.executions("jit__step") == 2
    assert prof.executions("jit__append_rows") == 1
    assert prof.window_s() == pytest.approx(20.5)
    assert prof.by_scope == got
    assert any("kv_write" in row for row in spans.tables(prof))


def test_an_operation_that_starts_where_the_last_ends_is_no_child_of_it():
    """In float seconds an end can round to after the next start (this
    pair is from a v5e trace); counted in whole nanoseconds it cannot,
    and the enclosing `while` keeps both out of its own time."""
    a, b = 42920727 * 1e-9, 20 * 1e-9    # as `read_xplane` makes them
    assert a + b > 0.042920747          # the float fault itself
    ops = [(hlo("while.1", "while"), 0.042, 0.002),
           (hlo("add_rsqrt_fusion.5", "fusion"), a, b),
           (hlo("fusion.140", "fusion"), 0.042920747, 4.5263e-05)]
    names = {(STEP, ops[1][0]): BODY + "norm/rsqrt:",
             (STEP, ops[2][0]): BODY + "mlp/dot_general:"}
    got = spans.scope_seconds(ops, [(f"jit__step({STEP})", 0.042, 0.002)],
                              names)["jit__step"]
    assert got["norm"] == pytest.approx(2e-08)
    assert got["mlp"] == pytest.approx(4.5263e-05)
    assert got[spans.UNSCOPED] == pytest.approx(0.002 - 2e-08 - 4.5263e-05)


def test_op_names_are_read_from_the_metadata_records_of_the_file(tmp_path):
    """`ProfileData` shows an event's name and own stats, not the
    stats of its metadata record, where the TPU keeps `tf_op` and
    `program_id`: `read_op_names` reads the wire format itself."""
    from jax.profiler import ProfileData

    text = """
    planes {
      id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Ops"
              events { metadata_id: 7 offset_ps: 1000 duration_ps: 500 } }
      event_metadata { key: 7 value { id: 7 name: "%fusion.7 = bf16[4] fusion()"
        stats { metadata_id: 2 uint64_value: 9007080041550839226 }
        stats { metadata_id: 1 str_value: "jit(_step)/while/body/kv_write/squeeze:" } } }
      event_metadata { key: 8 value { id: 8 name: "%fusion.8 = bf16[4] fusion()"
        stats { metadata_id: 2 uint64_value: 5 }
        stats { metadata_id: 1 ref_value: 3 } } }
      event_metadata { key: 9 value { id: 9 name: "%while.1 = () while()"
        stats { metadata_id: 2 uint64_value: 5 } } }
      stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
      stat_metadata { key: 2 value { id: 2 name: "program_id" } }
      stat_metadata { key: 3 value { id: 3 name: "jit(_step)/mlp/dot_general:" } }
    }
    planes {
      id: 2 name: "/host:CPU"
      event_metadata { key: 1 value { id: 1 name: "sched.iteration"
        stats { metadata_id: 2 uint64_value: 5 }
        stats { metadata_id: 1 str_value: "not a device's" } } }
      stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
      stat_metadata { key: 2 value { id: 2 name: "program_id" } }
    }
    """
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert spans.read_op_names(str(path)) == {
        (9007080041550839226, "%fusion.7 = bf16[4] fusion()"):
            "jit(_step)/while/body/kv_write/squeeze:",
        (5, "%fusion.8 = bf16[4] fusion()"): "jit(_step)/mlp/dot_general:"}
    assert spans.fingerprint("jit__step(9007080041550839226)") \
        == 9007080041550839226
    assert spans.fingerprint("?") is None


# -- a CPU-traced run: the spans are in the profile ----------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_spans"))
    tiny_cells.make_checkout(root)
    line = tiny_cells.run(root, "tiny.closed", trace=True)
    cell = harness.load_cell(root, "tiny.closed")
    xplane = spans.newest_xplane(harness.trace_dir(cell))
    return line, cell, spans.read_host_spans(xplane)


def test_the_traced_profile_holds_the_schedulers_spans_on_one_line(traced):
    line, _, lines = traced
    assert line["correct"] is True, line["problems"]
    worker = spans.worker_line(lines, "sched.iteration")
    iterations = [s for s in worker if s[0] == "sched.iteration"]
    assert len(iterations) >= 3
    for stat in ("active", "prefilling", "pending", "inflight",
                 "pool_in_use"):
        assert all(stat in s[3] for s in iterations)
    # the phases are the iterations' children, on the same line
    names = {s[0] for s in worker}
    assert {"sched.prefill_chunk", "sched.decode", "sched.detokenize"} \
        <= names
    eps = 1e-6
    first = min(i[1] for i in iterations)
    last = max(i[1] + i[2] for i in iterations)
    for name in ("sched.prefill_chunk", "sched.decode"):
        for _, start, dur, _ in (s for s in worker if s[0] == name):
            # a span is written when it closes: at the profile's two
            # edges a phase can be there without its iteration
            if start < first or start >= last:
                continue
            assert any(i[1] - eps <= start
                       and start + dur <= i[1] + i[2] + eps
                       for i in iterations), (name, start)
    host = spans.span_less(worker, "sched.iteration",
                           ["sched.decode", "sched.prefill_chunk"])
    assert all(0.0 <= h <= i[2] for h, i in zip(host, iterations))
    # the enqueues are the executor threads', not the worker's
    dispatched = [s for ln in lines if ln is not worker for s in ln]
    assert {"dispatch.decode", "dispatch.prefill_chunk"} \
        <= {s[0] for s in dispatched}
    assert not any(s[0].startswith("dispatch.") for s in worker)
    assert all("steps" in s[3] for s in dispatched
               if s[0] == "dispatch.decode")


def test_one_first_token_span_per_first_token_with_parts_within_ttft(traced):
    _, _, lines = traced
    firsts = [s for ln in lines for s in ln if s[0] == "sched.first_token"]
    assert len(firsts) >= 3
    ids = [s[3]["request"] for s in firsts]
    assert len(set(ids)) == len(ids)           # one a request
    for _, _, _, st in firsts:
        assert st["slices"] >= 1 and st["prompt_tokens"] >= 4
        assert st["prefill_us"] > 0
        assert min(st["queue_wait_us"], st["prefill_wait_us"]) >= 0
    # the chunked path emits the first token from the finishing slice:
    # every first token in the trace follows a finishing dispatch
    finishing = sum(s[0] == "dispatch.prefill_chunk" and s[3]["finish"] == 1
                    for ln in lines for s in ln)
    assert abs(finishing - len(firsts)) <= 1   # the profile's two edges


def test_a_traced_train_run_holds_one_train_step_span_a_step(tmp_path):
    root = str(tmp_path)
    tiny_cells.make_checkout(root)
    line = tiny_cells.run(root, "tiny.job", trace=True, seconds=0.3)
    assert line["correct"] is True, line["problems"]
    assert not (SPAN_METRICS | SCOPE_METRICS) & set(line["metrics"])
    cell = harness.load_cell(root, "tiny.job")
    lines = spans.read_host_spans(
        spans.newest_xplane(harness.trace_dir(cell)))
    worker = spans.worker_line(lines, "train.step")
    steps = [s for s in worker if s[0] == "train.step"]
    assert len(steps) == 3                  # the kind traces three steps
    assert all(s[3]["tokens"] == 64 for s in steps)
    gaps = spans.gaps_between(worker, "train.step")
    assert len(gaps) == 2 and all(g > 0 for g in gaps)
    names = [n for _, _, n in spans.worker_segments(lines)]
    assert names == ["train.host_gap", "train.step"] * 3 + ["train.host_gap"]


def test_the_timeline_and_the_trace_say_the_same_parts():
    """The parts of a first token are the timeline's own: they sum to
    no more than its time to the first token."""
    from kubeflow_tpu.obs.timeline import RequestTimeline

    now = [10.0]
    tl = RequestTimeline("r", prompt_tokens=40, clock=lambda: now[0])
    tl.event("enqueue")
    now[0] = 10.002
    tl.event("admit", slot=1, prefill_computed=40, prefill_reused=8)
    tl.prefill_s, tl.prefill_slices = 0.150, 3
    now[0] = 11.0
    tl.token()
    d = tl.to_dict()
    assert d["queue_wait_s"] == pytest.approx(0.002)
    assert d["prefill_s"] == 0.150 and d["prefill_slices"] == 3
    assert d["prefill_wait_s"] == pytest.approx(1.0 - 0.002 - 0.150)
    assert d["queue_wait_s"] + d["prefill_s"] + d["prefill_wait_s"] \
        == pytest.approx(d["ttft_s"])
    assert tl.prefill_reused == 8
    # own slices that outlast the clock's reading never make a wait < 0
    tl.prefill_s = 5.0
    assert tl.prefill_wait_s == 0.0


# -- the readers --------------------------------------------------------

def _reader(name):
    return manifest_rules.reader(REPO, name)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS | SCOPE_METRICS))
def test_every_new_reader_is_silent_off_the_chip(traced, name):
    """A CPU run puts no time under a metric's name, though the profile
    is there and holds the spans."""
    line, cell, _ = traced
    assert name not in line["metrics"]
    run = harness.Run(end_to_end={}, attempted=0, failed=0, problems=[],
                      counters={"traced_decode_steps": 8, "decode_steps": 8,
                                "decode_tokens": 24,
                                "mean_context_tokens": 30.0})
    reader, args = _reader(name)
    ctx = harness.Context(run=run, cell=cell, model=model, peaks=None)
    assert reader(ctx, **args) is None


def test_the_readers_read_a_profile_where_there_are_peaks(
        traced, monkeypatch):
    """What the chip run does, on hand-made lists: the readers' own
    arithmetic, with the profile's loading stubbed out."""
    _, cell, _ = traced
    ops = [(hlo("f.1", "fusion"), 0.0, 4.0),
           (hlo("c.1", "custom-call"), 4.0, 2.0),
           (hlo("f.2", "fusion"), 9.0, 8.0),
           (hlo("f.3", "fusion"), 18.0, 1.0)]
    prof = spans.Profile(
        programs=[(f"jit__step({STEP})", 0.0, 8.0),
                  (f"jit__step({STEP})", 9.0, 8.0),
                  (f"jit__append_rows({APPEND})", 18.0, 2.0)],
        ops=ops,
        op_names={
            (STEP, ops[0][0]): BODY + "kv_write/scatter:",
            (STEP, ops[1][0]): BODY + "paged_attention/pallas_call:",
            (STEP, ops[2][0]): BODY + "mlp/dot_general:",
            (APPEND, ops[3][0]): "jit(_append_rows)/while/body/closed_call/"
                                 "prefill_append/prefill_append/pallas_call:"},
        lines=[WORKER, EXECUTOR,
               [span("sched.first_token", 1.0, 0.0, queue_wait_us=2000.0,
                     prefill_us=150000.0, prefill_wait_us=848000.0)]])
    monkeypatch.setattr(spans, "load", lambda _dir: prof)
    run = harness.Run(end_to_end={}, attempted=0, failed=0, problems=[],
                      counters={"traced_decode_steps": 8, "decode_steps": 100,
                                "decode_tokens": 1200,
                                "mean_context_tokens": 500.0})
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    cell = harness.Cell(name=cell.name, chips=1, manifest=cell.manifest,
                        traffic=cell.traffic, root=cell.root,
                        config=harness.read_json(os.path.join(
                            REPO, "benchmarks/configs/mistral-7b-serve.json")))
    ctx = harness.Context(run=run, cell=cell, model=model, peaks=peaks)

    def read(name):
        reader, args = _reader(name)
        return reader(ctx, **args)

    assert read("sched.iter_host_ms") == pytest.approx(1e3 * (5.0 + 0.5) / 2)
    assert read("sched.queue_wait_ms") == pytest.approx(2.0)
    assert read("sched.prefill_wait_share") == pytest.approx(84.8)
    assert read("step.kv_write_ms") == pytest.approx(1e3 * 4.0 / 8)
    assert read("kernel.paged_attention_ms") == pytest.approx(1e3 * 2.0 / 8)
    assert read("kernel.prefill_append_ms") == pytest.approx(1e3 * 1.0)
    assert read("train.flash_attention_ms") is None
    assert read("train.host_gap_ms") is None          # no train.step span
    # 12 slots x 500 tokens x 65 536 B over 819 GB/s over 250 ms
    assert read("kernel.paged_attention_bw_share") == pytest.approx(
        100 * 12 * 500 * 65536 / 819e9 / 0.25)
    # idle: 6-9 (under decode and the iteration), 17-18 and 19-20 (after
    # the last span); none under sched.idle (12-14, when the device was
    # busy): all of it the host's, over a window of 20
    assert read("device.idle_host_share") == pytest.approx(100 * 5.0 / 20.0)


# -- the manifest's rule ---------------------------------------------------

def test_each_new_metric_moves_a_metric_its_cells_report():
    manifest_rules.check_span_and_scope_metrics(REPO)
