"""The benchmark's own arithmetic: the trace reduction on hand-made
interval lists, the sizes ISSUE 24 states for both configurations, and
the traffic generator's promise that a seed changes every input and
none of the work."""

from __future__ import annotations

import os

import pytest

import manifest_rules
from benchmarks import devtrace, harness, trafficgen
from benchmarks.kinds import train as train_kind
from benchmarks.models import llama as model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    return harness.read_json(
        os.path.join(REPO, "benchmarks", "configs", name + ".json"))


# -- devtrace ---------------------------------------------------------------

def test_overlapping_operations_are_counted_once():
    ops = [("a", 0.0, 2.0), ("b", 1.0, 2.0), ("c", 2.5, 0.25),
           ("d", 5.0, 1.0)]
    assert devtrace.union_seconds(ops) == pytest.approx(4.0)
    assert devtrace.union_seconds(list(reversed(ops))) == pytest.approx(4.0)
    assert devtrace.union_seconds([]) == 0.0


def test_idle_share_of_a_known_pattern_is_exact():
    # busy 1 ms of every 4, for 10 s
    ops = [("op", 0.004 * i, 0.001) for i in range(2500)]
    progs = [("jit_f(1)", 0.004 * i, 0.001) for i in range(2500)]
    trace = devtrace.reduce([(progs, ops)], window_s=10.0)
    assert trace.busy_s == pytest.approx(2.5)
    assert 1 - trace.busy_s / trace.window_s == pytest.approx(0.75)
    assert trace.idle_gaps == [["before jit_f", pytest.approx(2499 * 0.003)]]


def test_busy_time_is_the_mean_over_the_chips_that_ran():
    chip = lambda busy: ([("jit_f(1)", 0.0, busy)], [("op", 0.0, busy)])
    trace = devtrace.reduce([chip(1.0), chip(3.0), ([], [])], window_s=4.0)
    assert trace.busy_s == pytest.approx(2.0)
    assert devtrace.reduce([([], [])], window_s=1.0) is None


def test_time_per_program_and_the_top_ten_order():
    progs = [("jit__step(111)", 0.0, 0.020), ("jit__append_rows(7)", 0.02, 0.1),
             ("jit__step(111)", 0.2, 0.030), ("jit__step(222)", 0.3, 0.040),
             ("jit__append_rows(7)", 0.4, 0.3), ("jit__append_rows(7)", 0.8, 0.2)]
    ops = [(f"op{i}", 2.0 * i, 0.1 * (i + 1)) for i in range(12)]
    ops += [("op3", 40.0, 5.0)]
    trace = devtrace.reduce([(progs, ops)], window_s=50.0)
    assert trace.program_total_s("jit__step") == pytest.approx(0.090)
    assert trace.program_median_s("jit__append_rows") == pytest.approx(0.2)
    assert trace.program_total_s("jit__nothing") is None
    # outside every program here, so labelled "?"
    names = [name for name, _ in trace.device_ops]
    assert len(names) == 10
    assert names[:3] == ["?: op3", "?: op11", "?: op10"]
    assert trace.device_ops[0][1] == pytest.approx(5.4)
    assert names[-1] == "?: op2"           # op0 and op1 fall off the end


def test_nested_operations_count_their_own_time_once():
    hlo = ("%while.4 = (s32[]{:T(128)}, bf16[16,4096]{1,0:T(8,128)(2,1)}) "
           "while((s32[]{:T(128)}, bf16[16,4096]{1,0}) %tuple.1), "
           "condition=%cond, body=%body")
    ops = [(hlo, 1.0, 10.0),
           ("%fusion.7 = bf16[4]{0} fusion(bf16[4]{0} %p), kind=kLoop", 2.0, 3.0),
           ("%closed_call.3 = bf16[4]{0} custom-call(bf16[4]{0} %q)", 6.0, 4.0),
           ("%copy.1 = bf16[4]{0} copy(bf16[4]{0} %r)", 7.0, 1.0),
           ("%fusion.7 = bf16[4]{0} fusion(bf16[4]{0} %p), kind=kLoop", 20.0, 0.5)]
    progs = [("jit__step(5)", 0.5, 11.0), ("jit__append_rows(6)", 19.0, 2.0)]
    own = devtrace.self_time(ops)
    assert [o for _, _, o in own] == pytest.approx([3.0, 3.0, 3.0, 1.0, 0.5])
    trace = devtrace.reduce([(progs, ops)], window_s=30.0)
    assert trace.busy_s == pytest.approx(10.5)
    assert trace.device_ops == [
        ["jit__step: %closed_call.3 custom-call", pytest.approx(3.0)],
        ["jit__step: %fusion.7 fusion", pytest.approx(3.0)],
        ["jit__step: %while.4 while", pytest.approx(3.0)],
        ["jit__step: %copy.1 copy", pytest.approx(1.0)],
        ["jit__append_rows: %fusion.7 fusion", pytest.approx(0.5)]]


def test_trace_readers_find_nothing_without_a_trace():
    from benchmarks.readers import devtrace as readers

    run = harness.Run(end_to_end={}, counters={"traced_decode_steps": 8},
                      attempted=0, failed=0, problems=[])
    ctx = harness.Context(run=run, cell=None, model=model, peaks=None)
    assert readers.program_median_ms(ctx, program="jit__step") is None
    run.trace = devtrace.reduce(
        [([("jit__step(1)", 0.0, 0.08)], [("op", 0.0, 0.08)])], 1.0)
    assert readers.program_ms_per_count(
        ctx, program="jit__step", count="traced_decode_steps") \
        == pytest.approx(10.0)


# -- sizes, FLOPs and bytes (the figures of ISSUE 24) ---------------------

def test_sizes_of_both_configurations():
    serve, train = _config("mistral-7b-serve"), _config("mistral-7b-train")
    assert model.layer_params(serve) == 218_112_000
    assert round(model.layer_params(serve) / 1e6, 1) == 218.1
    assert round(model.embed_params(serve) / 1e6, 1) == 134.2
    assert round(model.num_params(train) / 1e9, 3) == 1.577
    assert round(model.param_bytes(serve) / 1e9, 1) == 7.5
    assert round(model.kv_block_bytes(serve) / 1e6, 2) == 4.19
    assert round(model.train_flops_per_token(train, 2048) / 1e9, 1) == 9.3
    # a decode step reads 7.2 GB of weights, and K and V of its contexts
    assert round(model.decode_bytes_per_step(serve, 0) / 1e9, 1) == 7.2
    per_token = model.decode_bytes_per_step(serve, 1) \
        - model.decode_bytes_per_step(serve, 0)
    assert per_token == 2 * 16 * 8 * 128 * 2


def test_sizes_agree_with_the_program_own_count():
    """The yardstick's closed form against `models/llama.py`'s shapes."""
    from kubeflow_tpu.models import llama

    for name in ("mistral-7b-serve", "mistral-7b-train"):
        c = _config(name)
        assert model.num_params(c) == llama.num_params(model.program_config(c))


@pytest.mark.parametrize("name", ["mistral-7b-serve", "mistral-7b-train"])
def test_every_width_is_the_source_and_only_depth_is_reduced(name):
    source = {"hidden_size": 4096, "intermediate_size": 14336,
              "num_attention_heads": 32, "num_key_value_heads": 8,
              "head_dim": 128, "vocab_size": 32768, "rope_theta": 1000000.0,
              "rms_norm_eps": 1e-05, "sliding_window": None,
              "tie_word_embeddings": False, "torch_dtype": "bfloat16",
              "max_position_embeddings": 32768}
    c = _config(name)
    assert {k: c[k] for k in source} == source
    assert list(c["reduced"]) == ["num_hidden_layers"]
    assert c["reduced"]["num_hidden_layers"]["here"] == c["num_hidden_layers"]
    manifest = harness.read_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = {e["name"]: e for e in manifest["configs"]}[name]
    assert entry["reduced"] == ["num_hidden_layers"]


def test_fit_depth_is_smoke_trains_rule():
    c = _config("mistral-7b-train")
    other = model.other_params(c)
    args = (model.layer_params(c), other, 2, 32, 1)
    assert train_kind.fit_depth(*args, device_bytes=16.9e9) == 6
    assert train_kind.fit_depth(*args, device_bytes=16.8e9) == 5
    assert train_kind.fit_depth(model.layer_params(c), other, 2, 32, 4,
                                device_bytes=16.9e9) == 27


def test_peaks_table_refuses_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9 mega")


# -- traffic --------------------------------------------------------------

STEADY = harness.read_json(
    os.path.join(REPO, "benchmarks", "traffic", "steady.json"))


def _draw(seed, n):
    stream = trafficgen.RequestStream(STEADY, seed, 32768)
    return [stream.next() for _ in range(n)]


def test_steady_traffic_holds_the_parameters_of_the_issue():
    assert STEADY["arrivals"] == {"process": "closed", "clients": 16}
    assert STEADY["prompt_tokens"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.9,
        "min": 16, "max": 3072}
    assert STEADY["output_tokens"] == {
        "dist": "lognormal", "median": 96, "sigma": 0.7,
        "min": 8, "max": 512}
    prompts = trafficgen.strata(STEADY["prompt_tokens"], 64)
    assert min(prompts) >= 16 and max(prompts) <= 3072
    assert sorted(prompts)[32] == pytest.approx(384, rel=0.03)
    c = _config("mistral-7b-serve")
    outputs = trafficgen.strata(STEADY["output_tokens"], 64)
    assert max(prompts) + max(outputs) <= c["engine"]["max_len"]


def test_a_seed_changes_every_input_and_none_of_the_work():
    a, b = _draw(3, 128), _draw(2**33 + 11, 128)
    shape = lambda reqs: [(len(p), n) for p, n in reqs]
    assert shape(a) == shape(b)            # the same sizes, the same order
    assert a[0][0] != b[0][0]              # other ids
    assert a == _draw(3, 128)              # the same seed, the same inputs
    assert all(0 <= t < 32768 for p, _ in a[:8] for t in p)
    for lo in (0, 64):                     # every cycle holds every stratum
        assert sorted(len(p) for p, _ in a[lo:lo + 64]) \
            == sorted(trafficgen.strata(STEADY["prompt_tokens"], 64))
        assert sorted(n for _, n in a[lo:lo + 64]) \
            == sorted(trafficgen.strata(STEADY["output_tokens"], 64))
    assert shape(a[:64]) != shape(a[64:])  # cycles differ in order
    other = trafficgen.RequestStream({**STEADY, "order_seed": 1}, 3, 32768)
    assert shape([other.next() for _ in range(64)]) != shape(a[:64])


def test_poisson_offsets_keep_their_rate_under_every_order():
    for order_seed in (0, 7, 2**31 + 3):
        offs = trafficgen.poisson_offsets(2.0, order_seed, 64.0)
        assert offs == sorted(offs) and 0 < offs[0] and offs[-1] < 64.0
        assert len(offs) == pytest.approx(128, abs=6)
    gaps = trafficgen.exponential_strata(2.0, 64)
    assert sum(gaps) / 64 == pytest.approx(0.5)
    assert trafficgen.poisson_offsets(2.0, 7, 64.0) \
        != trafficgen.poisson_offsets(2.0, 8, 64.0)
    assert trafficgen.poisson_offsets(2.0, 7, 64.0) \
        == trafficgen.poisson_offsets(2.0, 7, 64.0)


def test_percentile_is_the_nearest_rank():
    xs = [float(i) for i in range(1, 101)]
    assert trafficgen.percentile(xs, 0.90) == 91.0
    assert trafficgen.percentile(xs, 0.95) == 96.0
    assert trafficgen.percentile([], 0.9) is None


# -- the manifest against its data files ----------------------------------

def test_every_manifest_entry_finds_its_files_and_readers():
    manifest_rules.check_files_and_readers(REPO)
