#!/usr/bin/env python
"""Serving load test: concurrent clients through the REST server.

The control-plane loadtest measures reconcile fan-out; this is its
serving twin — N concurrent clients against a real server process, all
riding the dynamic batcher. Reports throughput, latency percentiles,
and the coalescing evidence (mean effective batch), one JSON line
(machine-readable like bench.py / loadtest.py).

    python loadtest/serving_loadtest.py --clients 16 --requests 96
    python loadtest/serving_loadtest.py --mode continuous

`--mode continuous` swaps the window Batcher for slot-based continuous
batching (serving/continuous.py) — same clients, same requests, so the
two JSON lines are directly comparable; its coalescing evidence is
occupancy (mean occupied slots per decode step) instead of mean
effective batch.

`--mode fleet` stands N continuous replicas behind the fleet router
(kubeflow_tpu.fleet) and drives the ROUTER with the same clients and
requests — the JSON line adds the affinity hit rate (replica
prefix-cache deltas) and routing-reason counts, so affinity vs
`--fleet-policy roundrobin` is a direct prefix-hit A/B, and
`--fleet-kill-one` proves retry/fallback completes every request when
a replica dies mid-run.

`--mode fleet --fleet-kv-pressure` is the cache-tier A/B (ISSUE 19):
the same seeded repeated-prompt workload through a control fleet
(router peer hints off, no spill tier) and a tier fleet (X-KV-Peer
hints + host-RAM spill), both under a block pool sized to force
eviction. Seed responses are the recompute oracle every routed
response must match token-for-token; the run fails unless the tier
fleet's measured fleet-wide hit rate closes at least half of the
affinity-vs-counterfactual gap the control arm's `/fleet/cache`
reports.

`--mode chaos` is the fleet fault-injection harness: replicas behind a
router whose dispatch path runs a SEEDED `fleet.chaos.ChaosInjector`
(drop / delay / duplicate / heartbeat blackhole), plus the two
process-level faults this script owns — SIGKILL one replica mid-run
and instant-drain (live KV migration) another while generations are in
flight. Every response, one-shot or streamed, is compared token-for-
token against a fault-free oracle; the run FAILS unless client-visible
failures and token mismatches are both zero, the wedged-transfer probe
rolls back without leaking a pool block, and p95 stays bounded. The
JSON line records the injected-fault ledger and the drain-to-exit
time.

`--mode chaos --closed-loop` swaps the fault-injection arm for the
closed-loop recovery arm (ISSUE 16): the router runs its SLO-burn
controller live, the harness SIGKILLs the WHOLE fleet mid-flood and
then acts as dumb infra — booting a replacement replica only when the
controller's scale_out floor at /fleet/autoscale exceeds live
capacity. The controller is the only recovery path; the run fails
unless availability burn clears within one short window, every
request eventually completes token-exact, and the fired decision is
booked `recovered` in the conservation-checked /fleet/decisions
ledger (printed as the run's audit table).

`--mode disagg` is the disaggregated-pools A/B (ISSUE 12): a fleet
split into prefill/decode pools (prefill replicas fill paged KV
blocks and ship them to the decode pool over /v1/migrate/in, the
router pins each generate to the decode replica holding its prefix)
against a symmetric fleet of EQUAL total replica count, both serving
the same mixed long-prompt/short-decode workload. Outputs are
compared request-for-request across the arms (sharpened lm_head:
token parity is exact), and the disagg arm SIGKILLs one prefill
replica after the timed window — zero client failures is the pass
bar. The JSON line carries both arms' throughput plus the handoff
outcome counts and shipped KV bytes.

`--mode tenants` is the noisy-neighbor A/B for the multi-tenant QoS
scheduler (kubeflow_tpu.tenancy): a batch-class tenant floods the
server with long generations while an interactive tenant streams
short ones and measures time-to-first-token. The run executes BOTH
arms — fair-share + priority + preemption ON (tenancy configured)
and OFF (tenant-blind FIFO) — against identical workloads and
reports interactive TTFT percentiles side by side, plus the
preemption/throughput evidence that batch work kept flowing.

`--mode scenario` replays a trace file (or a seeded generated shape,
`--scenario gen:flash-crowd --seed 7`) open-loop against a single
continuous server or the full router+fleet stack
(`--scenario-target fleet`), asserting the trace's declarative
`expect` block on the outcome. `--scenario-fidelity-pct N` runs the
record/replay round-trip: replay the scenario, RECORD it back off the
server's timeline store, replay the recording on a fresh identical
server, and fail unless recorded-replay p95 TTFT lands within N% of
the original. The scenario engine itself lives in
`kubeflow_tpu.scenarios`; this mode is the harness wiring.

Hermetic by default (tiny model, CPU): the number is a CONTROL-PLANE
number (batching, HTTP, queueing) — model throughput on hardware is
bench.py's job.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import random
import socket
import statistics
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO =os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


SERVER_CODE = r'''
import os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
sys.path.insert(0, {repo!r})
import jax; jax.config.update("jax_platforms", "cpu")
from aiohttp import web
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.engine import InferenceEngine, LLAMA_FAMILY, EngineConfig
from kubeflow_tpu.serving import server as srv
cfg = llama.LLAMA_TINY
params = llama.init(jax.random.key(0), cfg)
eng = InferenceEngine(params, cfg, LLAMA_FAMILY, EngineConfig(max_len=128))
app = srv.create_serving_app({{"tiny": eng}}, batch_window_ms={window_ms},
                             max_batch={max_batch},
                             continuous={continuous}, warmup={continuous},
                             pipeline_depth={pipeline_depth})
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''


ROUTER_CODE = r'''
import sys
sys.path.insert(0, {repo!r})
from aiohttp import web
from kubeflow_tpu.fleet.router import create_router_app
app = create_router_app(block_size={block_size}, policy={policy!r},
                        hedge_after_s={hedge_after_s},
                        peer_hints={peer_hints})
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''

# One fleet replica: continuous batching + warmup, kv_block_size sized
# for the loadtest's short prompts (the radix cache only caches FULL
# blocks — the default 64 would cache nothing of a 24-token prompt),
# registered with the router and heartbeating fast enough that a short
# timed window sees fresh queue stats.
FLEET_REPLICA_CODE = r'''
import os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
sys.path.insert(0, {repo!r})
import jax; jax.config.update("jax_platforms", "cpu")
from aiohttp import web
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.engine import InferenceEngine, LLAMA_FAMILY, EngineConfig
from kubeflow_tpu.serving import server as srv
cfg = llama.LLAMA_TINY
params = llama.init(jax.random.key(0), cfg)
eng = InferenceEngine(params, cfg, LLAMA_FAMILY, EngineConfig(max_len=128))
app = srv.create_serving_app({{"tiny": eng}}, continuous=True, warmup=True,
                             kv_block_size={block_size})
srv.enable_fleet_registration(app, {router!r},
                              "http://127.0.0.1:{port}",
                              replica_id="replica-{idx}", period_s=0.5)
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''


# KV-pressure-arm replica (ISSUE 19): FLEET_REPLICA_CODE with the
# chaos arm's sharpened lm_head (token parity against a recompute
# oracle must be exact across batch shapes) plus the cache-tier knobs
# — a pool small enough that parked prefixes get evicted under load,
# and a spill budget (None = tier off, the control arm).
KV_REPLICA_CODE = r'''
import os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
sys.path.insert(0, {repo!r})
import jax; jax.config.update("jax_platforms", "cpu")
from aiohttp import web
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.engine import InferenceEngine, LLAMA_FAMILY, EngineConfig
from kubeflow_tpu.serving import server as srv
cfg = llama.LLAMA_TINY
params = dict(llama.init(jax.random.key(0), cfg))
params["lm_head"] = params["lm_head"] * 50.0
eng = InferenceEngine(params, cfg, LLAMA_FAMILY, EngineConfig(max_len=128))
app = srv.create_serving_app({{"tiny": eng}}, continuous=True, warmup=True,
                             kv_block_size={block_size},
                             kv_pool_blocks={pool_blocks},
                             kv_spill_bytes={spill_bytes})
srv.enable_fleet_registration(app, {router!r},
                              "http://127.0.0.1:{port}",
                              replica_id="replica-{idx}", period_s=0.5)
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''


# Chaos-arm router: same fleet router, with a seeded ChaosInjector on
# the dispatch path and hedging OFF (a hedge is an intentional
# duplicate — it would alias with the injector's duplicate fault and
# muddy the ledger). The blackhole is armed at construction: the first
# N heartbeats from replica-1 vanish, so the sweeper walks the
# degraded path on a live process while the run warms up.
CHAOS_ROUTER_CODE = r'''
import sys
sys.path.insert(0, {repo!r})
from aiohttp import web
from kubeflow_tpu.fleet.chaos import ChaosInjector
from kubeflow_tpu.fleet.router import create_router_app
chaos = ChaosInjector({seed}, drop_rate={drop_rate},
                      delay_rate={delay_rate}, delay_s={delay_s},
                      duplicate_rate={duplicate_rate})
chaos.blackhole("replica-1", {blackhole_beats})
app = create_router_app(block_size={block_size}, policy="affinity",
                        hedge_after_s=0.0, retries={retries},
                        backoff_s=0.05, chaos=chaos)
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''

# Chaos-arm replica: FLEET_REPLICA_CODE with a sharpened lm_head
# (x50, the test suite's idiom) so greedy argmax cannot flip across
# batch shapes — the token-exactness oracle requires byte-for-byte
# deterministic generations no matter how requests coalesce, migrate,
# or replay after a crash.
CHAOS_REPLICA_CODE = r'''
import os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
sys.path.insert(0, {repo!r})
import jax; jax.config.update("jax_platforms", "cpu")
from aiohttp import web
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.engine import InferenceEngine, LLAMA_FAMILY, EngineConfig
from kubeflow_tpu.serving import server as srv
cfg = llama.LLAMA_TINY
params = dict(llama.init(jax.random.key(0), cfg))
params["lm_head"] = params["lm_head"] * 50.0
eng = InferenceEngine(params, cfg, LLAMA_FAMILY, EngineConfig(max_len=128))
app = srv.create_serving_app({{"tiny": eng}}, continuous=True, warmup=True,
                             kv_block_size={block_size})
srv.enable_fleet_registration(app, {router!r},
                              "http://127.0.0.1:{port}",
                              replica_id="replica-{idx}", period_s=0.5)
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''


# Closed-loop router (--mode chaos --closed-loop): the fleet router
# with ONE declarative policy — availability short-window burn over
# threshold fires scale_out — and the controller loop running live.
# The short SLO window is shrunk from the prod 60 s so "burn clears
# within one short window" is a seconds-scale assertion, and retries
# are capped low so a dead fleet turns into 503s (availability budget
# spend, the controller's evidence) in about a second instead of
# hiding the outage inside a long retry ladder.
CLOSED_LOOP_ROUTER_CODE = r'''
import sys
sys.path.insert(0, {repo!r})
from aiohttp import web
from kubeflow_tpu.fleet import control
from kubeflow_tpu.fleet.router import FLEET_KEY, create_router_app
pol = control.Policy(
    name="availability_burn_scale_out",
    signal=control.Signal(
        "slo_burn_rate",
        {{"slo": "fleet_availability", "window": "short"}},
        source="local"),
    threshold=1.0, clear=0.5, cooldown_s={cooldown_s},
    verify_window_s={verify_s}, action="scale_out")
app = create_router_app(block_size={block_size}, policy="affinity",
                        hedge_after_s=0.0, retries={retries},
                        backoff_s=0.05, policies=[pol],
                        control_interval_s={interval_s})
app[FLEET_KEY].obs.slo.windows["short"] = {short_window_s}
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''


# Disagg-arm replica: CHAOS_REPLICA_CODE (sharpened lm_head — the
# handoff parity oracle needs byte-exact greedy generations) plus a
# --pool role. A "prefill" replica serves :prefill handoffs and ships
# KV blocks; a "decode" replica imports them; "mixed" is the
# symmetric control arm.
DISAGG_REPLICA_CODE = r'''
import os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
sys.path.insert(0, {repo!r})
import jax; jax.config.update("jax_platforms", "cpu")
from aiohttp import web
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.engine import InferenceEngine, LLAMA_FAMILY, EngineConfig
from kubeflow_tpu.serving import server as srv
cfg = llama.LLAMA_TINY
params = dict(llama.init(jax.random.key(0), cfg))
params["lm_head"] = params["lm_head"] * 50.0
eng = InferenceEngine(params, cfg, LLAMA_FAMILY, EngineConfig(max_len={max_len}))
app = srv.create_serving_app({{"tiny": eng}}, continuous=True, warmup=True,
                             kv_block_size={block_size}, pool={pool!r})
srv.enable_fleet_registration(app, {router!r},
                              "http://127.0.0.1:{port}",
                              replica_id="replica-{idx}", period_s=0.5)
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''


# Rollout-arm router (--mode rollout): the live-deployment plane
# (ISSUE 18) running for real — the RolloutManager loop ticks fast,
# the bake window is seconds-scale, and the TTFT SLO threshold sits
# between a healthy CPU generate and the bad arm's planted defect
# delay so the canary judge discriminates the two versions.
ROLLOUT_ROUTER_CODE = r'''
import sys
sys.path.insert(0, {repo!r})
from aiohttp import web
from kubeflow_tpu.fleet.router import create_router_app
app = create_router_app(block_size={block_size}, policy="affinity",
                        hedge_after_s=0.0, retries={retries},
                        backoff_s=0.05,
                        rollout_interval_s={interval_s},
                        rollout_bake_s={bake_s},
                        rollout_min_probes={min_probes},
                        rollout_burn_threshold=2.0,
                        rollout_ttft_slo_s={ttft_slo_s},
                        rollout_confirm_timeout_s=60.0)
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''

# Rollout-arm replica: CHAOS_REPLICA_CODE (sharpened lm_head — the
# mid-roll parity oracle needs byte-exact greedy generations) plus a
# seed-keyed reloader, so `POST /v1/reload {"source": {"seed": N}}`
# swaps to DISTINGUISHABLE weights without anyone writing checkpoints.
ROLLOUT_REPLICA_CODE = r'''
import os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
sys.path.insert(0, {repo!r})
import jax; jax.config.update("jax_platforms", "cpu")
from aiohttp import web
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.engine import InferenceEngine, LLAMA_FAMILY, EngineConfig
from kubeflow_tpu.serving import server as srv
cfg = llama.LLAMA_TINY

def mk_params(seed):
    params = dict(llama.init(jax.random.key(seed), cfg))
    params["lm_head"] = params["lm_head"] * 50.0
    return params

def reloader(name, engine, source):
    if "seed" not in source:
        raise ValueError("rollout loadtest reloads are seed-sourced")
    return mk_params(int(source["seed"]))

eng = InferenceEngine(mk_params(0), cfg, LLAMA_FAMILY,
                      EngineConfig(max_len=128))
app = srv.create_serving_app({{"tiny": eng}}, continuous=True, warmup=True,
                             kv_block_size={block_size},
                             model_version="seed-0", reloader=reloader)
srv.enable_fleet_registration(app, {router!r},
                              "http://127.0.0.1:{port}",
                              replica_id="replica-{idx}", period_s=0.5)
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''


TENANT_SERVER_CODE = r'''
import os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
sys.path.insert(0, {repo!r})
import jax; jax.config.update("jax_platforms", "cpu")
from aiohttp import web
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.engine import InferenceEngine, LLAMA_FAMILY, EngineConfig
from kubeflow_tpu.serving import server as srv
from kubeflow_tpu.tenancy import config_from_dict
cfg = llama.LLAMA_TINY
params = llama.init(jax.random.key(0), cfg)
eng = InferenceEngine(params, cfg, LLAMA_FAMILY, EngineConfig(max_len=128))
tenancy = config_from_dict({{"tenants": {{
    "live": {{"priority": "interactive"}},
    "bulk": {{"priority": "batch"}},
}}}})
app = srv.create_serving_app({{"tiny": eng}}, continuous=True, warmup=True,
                             max_batch={max_batch},
                             prefill_chunk_tokens=(
                                 {chunk} or srv.PREFILL_CHUNK_TOKENS),
                             tenancy=tenancy if {qos} else None,
                             slo_ttft_s={{"interactive": {slo_ttft_s}}})
if not {qos}:
    # classification-only: the batcher stays tenant-blind FIFO, but the
    # SLO engine still attributes live-tenant requests to the
    # interactive class, so both arms feed the SAME burn-rate gauge
    # and the A/B contrast is scheduler policy, not accounting.
    app[srv.TENANCY_KEY] = tenancy
web.run_app(app, host="127.0.0.1", port={port}, print=None)
'''


# Elastic-training coordinator: the trainer-fleet membership plane.
# Fast staleness windows (vs the prod 6s/20s defaults) so a SIGKILLed
# worker is declared dead — and the survivors' generation bumps —
# within a couple of seconds of the fault.
TRAIN_COORDINATOR_CODE = r'''
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
from aiohttp import web
from kubeflow_tpu.train.elastic import (
    ElasticCoordinator, create_coordinator_app,
)
coord = ElasticCoordinator(min_replicas={min_replicas},
                           degraded_after_s={degraded_s},
                           dead_after_s={dead_s},
                           slo_short_window_s={slo_short_s},
                           restart_burn_hold_s={burn_hold_s})
web.run_app(create_coordinator_app(coord), host="127.0.0.1",
            port={port}, print=None)
'''

# One elastic trainer worker. 8 virtual CPU devices so any live world
# size up to 8 can form a mesh (the worker takes a device SUBSET sized
# to the world). RESULT line is the harness's per-worker oracle:
# final_step / restores / corrupt_restores / world_size.
TRAIN_WORKER_CODE = r'''
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
import json
from kubeflow_tpu.train.elastic import WorkerConfig, run_worker
result = run_worker(WorkerConfig(
    coordinator_url={coordinator!r},
    replica_id={rid!r},
    ckpt_dir={ckpt!r},
    total_steps={steps},
    save_every={save_every},
    slow_save_s={slow_save_s},
    loss_log={loss_log!r}))
print("RESULT " + json.dumps(result), flush=True)
'''


def _get_json(url: str, timeout: float = 5.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _post_json(url: str, body: dict | None, timeout: float = 60.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body or {}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _sse_generate(base: str, body: dict, timeout: float = 120.0) -> list[int]:
    """POST a streaming generate and collect token ids from the SSE
    frames (the router re-emits one token per event; the terminal
    frame carries done+total). Raises on a missing/err terminal frame
    or a total that disagrees with the tokens actually received —
    either would be a duplicate/gap the splice failed to hide."""
    req = urllib.request.Request(
        f"{base}/v1/models/tiny:generate",
        data=json.dumps(dict(body, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    toks: list[int] = []
    final: dict | None = None
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for line in r:
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[len(b"data: "):])
            if ev.get("done") or "error" in ev:
                final = ev
                break
            t = ev.get("tokens")
            if t:
                toks.extend(int(x) for x in t[0])
    if final is None or not final.get("done"):
        raise AssertionError(f"stream ended without done frame: {final}")
    if final.get("total") != len(toks):
        raise AssertionError(
            f"stream total {final.get('total')} != {len(toks)} tokens "
            "received — the failover splice dropped or duplicated")
    return toks


def _scrape_metrics(base: str) -> dict:
    """GET /metrics and strict-parse it (the loadtest doubles as a
    contract check: an exposition the parser rejects fails the run)."""
    from kubeflow_tpu.obs.exposition import parse_exposition
    with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
        return parse_exposition(r.read().decode())


def _burn_rate(families: dict, slo: str, window: str) -> float:
    """slo_burn_rate{slo=...,window=...} — KeyError means the gauge
    family regressed (it is zero-seeded, so absence is a bug)."""
    samples = families["slo_burn_rate"]["samples"]
    return samples[("slo_burn_rate",
                    (("slo", slo), ("window", window)))]


def _scrape_federated(base: str) -> dict:
    """GET /elastic/metrics (the coordinator's federated fleet view)
    and strict-parse it — same contract-check stance as /metrics."""
    from kubeflow_tpu.obs.exposition import parse_exposition
    with urllib.request.urlopen(f"{base}/elastic/metrics",
                                timeout=10) as r:
        return parse_exposition(r.read().decode())


def _hist_quantile_bracket(families: dict, family: str, q: float,
                           **labels) -> tuple[float, float]:
    """(lo, hi] bucket bracket containing the q-quantile of a server
    histogram, from cumulative bucket counts. hi may be +inf."""
    want = tuple(sorted(labels.items()))
    buckets = []
    for (sname, lbls), v in families[family]["samples"].items():
        if sname != f"{family}_bucket":
            continue
        if tuple(kv for kv in lbls if kv[0] != "le") != want:
            continue
        le = dict(lbls)["le"]
        buckets.append(
            (float("inf") if le == "+Inf" else float(le), v))
    if not buckets:
        raise AssertionError(
            f"{family}: no buckets with labels {labels} — did the "
            f"tenant label on the server-side histogram regress?")
    buckets.sort()
    total = buckets[-1][1]
    lo = 0.0
    for le, cum in buckets:
        if cum >= q * total - 1e-9:
            return lo, le
        lo = le
    return lo, float("inf")


def run_fleet(clients: int, requests: int, max_new: int, *,
              replicas: int = 2, policy: str = "affinity",
              block_size: int = 8, kill_one: bool = False,
              hedge_after_s: float = 10.0) -> dict:
    """N replicas behind the fleet router; clients hit the ROUTER.
    Reports the single-server JSON schema plus the fleet evidence:
    affinity hit rate (replica prefix-cache deltas over the timed
    window), routing-reason counts, and — with --fleet-kill-one — that
    killing a replica mid-run loses zero requests."""
    import tempfile

    router_port = free_port()
    rep_ports = [free_port() for _ in range(replicas)]
    router_base = f"http://127.0.0.1:{router_port}"
    log = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".log", prefix="kftpu-fleetload-", delete=False)
    procs: list[subprocess.Popen] = []
    try:
        procs.append(subprocess.Popen(
            [sys.executable, "-c",
             ROUTER_CODE.format(repo=REPO, port=router_port,
                                block_size=block_size, policy=policy,
                                hedge_after_s=hedge_after_s,
                                peer_hints=True)],
            stdout=log, stderr=subprocess.STDOUT))
        for idx, port in enumerate(rep_ports):
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 FLEET_REPLICA_CODE.format(
                     repo=REPO, port=port, idx=idx,
                     router=router_base, block_size=block_size)],
                stdout=log, stderr=subprocess.STDOUT))

        deadline = time.monotonic() + 180
        ready = False
        while time.monotonic() < deadline:
            if any(p.poll() is not None for p in procs):
                break
            try:
                counts = _get_json(
                    f"{router_base}/fleet/replicas")["counts"]
                if counts["ready"] >= replicas:
                    ready = True
                    break
            except Exception:
                pass
            time.sleep(0.5)
        if not ready:
            log.flush()
            with open(log.name) as f:
                tail = "\n".join(f.read().splitlines()[-30:])
            rcs = [p.poll() for p in procs]
            raise RuntimeError(
                f"fleet never became ready (rcs={rcs}):\n{tail}")

        def post(base: str, body: dict, timeout: float = 120.0) -> dict:
            req = urllib.request.Request(
                f"{base}/v1/models/tiny:generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return json.loads(r.read())

        # Warm each replica DIRECTLY (compiles admission-group shapes
        # beyond warmup's buckets) with a prompt FULLY disjoint from
        # the measured set — the radix cache matches partial blocks
        # (copy-on-write seeds), so even one shared leading token
        # counts as a request-level "hit"; warming through the router,
        # or any shared token 0, would saturate the A/B's metric.
        prompt_len = 3 * block_size
        warm_prompt = [255, 99] + [5 + t % 200
                                   for t in range(prompt_len - 2)]

        def warm(i: int) -> None:
            base = f"http://127.0.0.1:{rep_ports[i % replicas]}"
            post(base, {"tokens": [warm_prompt], "max_new": max_new})

        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            for _ in range(3):
                list(ex.map(warm, range(max(clients, replicas))))

        # K distinct prompts, each repeated ~requests/K times: the
        # workload where prefix affinity pays. Prompts differ from
        # token 0 (and from the warm prompt), so a repeat is the ONLY
        # source of cache reuse — the first touch of each prompt on
        # each replica is an honest miss.
        k = max(1, requests // 4)
        prompts = [[3 + j % 250, 100] + [7 + (j + t) % 200
                                         for t in range(prompt_len - 2)]
                   for j in range(k)]
        # Shuffled (seeded) prompt order, exact repeat counts: a plain
        # `i % k` cycle aliases with round-robin's `i % replicas`
        # whenever k divides evenly — every repeat of a prompt would
        # land on the same replica BY COINCIDENCE and the control arm
        # would measure affinity it does not have.
        prompt_order = [i % k for i in range(requests)]
        random.Random(0).shuffle(prompt_order)

        def prefix_stats(port: int) -> tuple[int, int, int, int]:
            m = _get_json(
                f"http://127.0.0.1:{port}/v1/models")["models"][0]
            pc = m.get("prefix_cache", {})
            return (pc.get("hits", 0), pc.get("misses", 0),
                    pc.get("tokens_reused", 0),
                    pc.get("tokens_prefilled", 0))

        stats0 = {p: prefix_stats(p) for p in rep_ports}
        route0 = _get_json(f"{router_base}/fleet/stats")
        cache0 = _get_json(f"{router_base}/fleet/cache")

        failures = 0
        latencies: list[float] = []
        lock = __import__("threading").Lock()

        def one(i: int) -> float:
            t0 = time.perf_counter()
            try:
                out = post(router_base,
                           {"tokens": [prompts[prompt_order[i]]],
                            "max_new": max_new})
                assert len(out["tokens"][0]) == max_new, out
            except Exception:
                nonlocal failures
                with lock:
                    failures += 1
                raise
            return time.perf_counter() - t0

        killed = None
        t0 = time.perf_counter()
        if kill_one:
            half = requests // 2
            with concurrent.futures.ThreadPoolExecutor(clients) as ex:
                latencies = list(ex.map(one, range(half)))
            # snapshot the victim's cache stats BEFORE it dies, then
            # SIGKILL it mid-run (terminate() would run the graceful
            # path — deregister + drain — and the router would never
            # see a failure): the router must absorb the crash via
            # note_failure + retry/fallback with zero client errors
            killed = replicas - 1
            stats_prekill = prefix_stats(rep_ports[killed])
            procs[1 + killed].kill()
            procs[1 + killed].wait()
            with concurrent.futures.ThreadPoolExecutor(clients) as ex:
                latencies += list(ex.map(one, range(half, requests)))
        else:
            with concurrent.futures.ThreadPoolExecutor(clients) as ex:
                latencies = list(ex.map(one, range(requests)))
        wall = time.perf_counter() - t0

        hits = misses = reused = prefilled = 0
        for pi, port in enumerate(rep_ports):
            if killed is not None and pi == killed:
                s1 = stats_prekill
            else:
                s1 = prefix_stats(port)
            hits += s1[0] - stats0[port][0]
            misses += s1[1] - stats0[port][1]
            reused += s1[2] - stats0[port][2]
            prefilled += s1[3] - stats0[port][3]
        route1 = _get_json(f"{router_base}/fleet/stats")
        reasons = {r: int(route1["route_total"][r]
                          - route0["route_total"][r])
                   for r in route1["route_total"]}
        # fleet cache observatory (ISSUE 13): the router's
        # counterfactual counter books every routed request that
        # missed on its replica while a PEER's heartbeat digest had
        # the prefix hot — the hits a cross-replica cache tier would
        # have converted. Counterfactual fleet hit rate = (actual hits
        # + convertible misses) / lookups; the gap over the affinity
        # hit rate is the headroom a shared tier buys. Digests are
        # top-K and heartbeat-lagged, so clamp at 1.0.
        cache1 = _get_json(f"{router_base}/fleet/cache")
        remote = int(cache1["remote_hits_total"]
                     - cache0["remote_hits_total"])
        affinity_rate = (round(hits / (hits + misses), 3)
                         if hits + misses else 0.0)
        counterfactual = (min(1.0, round((hits + remote)
                                         / (hits + misses), 3))
                          if hits + misses else 0.0)
        assert counterfactual >= affinity_rate, (
            f"counterfactual fleet hit rate {counterfactual} < "
            f"measured affinity rate {affinity_rate}")
        print(f"# fleet cache: affinity_hit_rate={affinity_rate} "
              f"counterfactual_hit_rate={counterfactual} "
              f"remote_hits={remote} "
              f"headroom={round(counterfactual - affinity_rate, 3)} "
              f"shared_prefixes={cache1.get('shared_prefixes', 0)}",
              file=sys.stderr)

        latencies.sort()
        q = statistics.quantiles(latencies, n=20)
        return {
            "metric": "serving_rest_throughput",
            "mode": "fleet",
            "fleet_replicas": replicas,
            "policy": policy,
            "clients": clients,
            "requests": requests,
            "max_new": max_new,
            "kv_block_size": block_size,
            "distinct_prompts": k,
            "requests_per_sec": round(requests / wall, 2),
            "tokens_per_sec": round(requests * max_new / wall, 1),
            "p50_s": round(q[9], 3),
            "p95_s": round(q[18], 3),
            "wall_s": round(wall, 2),
            "prefix_hits": hits,
            "prefix_misses": misses,
            "affinity_hit_rate": affinity_rate,
            "fleet_remote_hits": remote,
            "counterfactual_hit_rate": counterfactual,
            "cache_headroom": round(counterfactual - affinity_rate, 3),
            # prompt cells served from cache / prompt cells total —
            # the bandwidth view of the same A/B (a hit that reuses 2
            # of 24 tokens is not much of a win)
            "token_reuse_rate": (round(reused / (reused + prefilled), 3)
                                 if reused + prefilled else 0.0),
            "route_reasons": reasons,
            "hedge_wins": int(route1["hedge_wins"]
                              - route0["hedge_wins"]),
            "killed_replica": killed,
            "client_failures": failures,
        }
    finally:
        log.close()
        os.unlink(log.name)
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def run_fleet_kv_pressure(clients: int, requests: int, max_new: int, *,
                          replicas: int = 2, block_size: int = 8,
                          hedge_after_s: float = 10.0,
                          pool_blocks: int = 0,
                          spill_bytes: int = 32 << 20) -> dict:
    """KV-pressure cache-tier A/B (ISSUE 19): the same repeated-prompt
    workload run through two sequential fleets — a CONTROL fleet
    (router peer hints off, no spill tier) and a TIER fleet (X-KV-Peer
    hints + host-RAM spill) — with every replica's block pool sized
    small enough that parked prefixes get evicted under load.

    Each distinct prompt is seeded cache-clean on replica j%N before
    the timed window; those seed responses ARE the recompute oracle
    every routed response (peer-fetched, restored, or recomputed) must
    match token-for-token (sharpened lm_head, so parity is exact).
    Seeds that land off the prompt's rendezvous target are exactly the
    misses `/fleet/cache` books as counterfactual remote hits in the
    control arm. The run prints measured fleet-wide hit rate vs the
    control arm's affinity rate vs that counterfactual ceiling, and
    FAILS unless the tier closes at least half the gap."""
    import tempfile
    import threading

    prompt_len = 3 * block_size
    warm_prompt = [255, 99] + [5 + t % 200 for t in range(prompt_len - 2)]
    k = max(2, requests // 4)
    if pool_blocks <= 0:
        # auto-size for pressure: room for the 8 active slots plus
        # roughly HALF the parked-prefix demand the seeded workload
        # generates per replica (~3.5 full blocks per distinct prompt
        # between affinity parks and peer imports) — parked prefixes
        # MUST evict for the spill tier to have anything to do
        seq_blocks = -(-(prompt_len + max_new) // block_size)
        pool_blocks = 8 * seq_blocks + max(8, (7 * k) // (4 * replicas))
    prompts = [[3 + j % 250, 100] + [7 + (j + t) % 200
                                     for t in range(prompt_len - 2)]
               for j in range(k)]
    prompt_order = [i % k for i in range(requests)]
    random.Random(0).shuffle(prompt_order)

    def arm(peer_hints: bool, arm_spill: int | None) -> dict:
        router_port = free_port()
        rep_ports = [free_port() for _ in range(replicas)]
        router_base = f"http://127.0.0.1:{router_port}"
        log = tempfile.NamedTemporaryFile(
            mode="w+", suffix=".log", prefix="kftpu-kvfleet-",
            delete=False)
        procs: list[subprocess.Popen] = []
        try:
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 ROUTER_CODE.format(repo=REPO, port=router_port,
                                    block_size=block_size,
                                    policy="affinity",
                                    hedge_after_s=hedge_after_s,
                                    peer_hints=peer_hints)],
                stdout=log, stderr=subprocess.STDOUT))
            for idx, port in enumerate(rep_ports):
                procs.append(subprocess.Popen(
                    [sys.executable, "-c",
                     KV_REPLICA_CODE.format(
                         repo=REPO, port=port, idx=idx,
                         router=router_base, block_size=block_size,
                         pool_blocks=pool_blocks,
                         spill_bytes=arm_spill)],
                    stdout=log, stderr=subprocess.STDOUT))

            deadline = time.monotonic() + 180
            ready = False
            while time.monotonic() < deadline:
                if any(p.poll() is not None for p in procs):
                    break
                try:
                    counts = _get_json(
                        f"{router_base}/fleet/replicas")["counts"]
                    if counts["ready"] >= replicas:
                        ready = True
                        break
                except Exception:
                    pass
                time.sleep(0.5)
            if not ready:
                log.flush()
                with open(log.name) as f:
                    tail = "\n".join(f.read().splitlines()[-30:])
                rcs = [p.poll() for p in procs]
                raise RuntimeError(
                    f"kv fleet never became ready (rcs={rcs}):\n{tail}")

            def post(base: str, body: dict,
                     timeout: float = 120.0) -> dict:
                req = urllib.request.Request(
                    f"{base}/v1/models/tiny:generate",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return json.loads(r.read())

            def warm(i: int) -> None:
                base = f"http://127.0.0.1:{rep_ports[i % replicas]}"
                post(base, {"tokens": [warm_prompt],
                            "max_new": max_new})

            with concurrent.futures.ThreadPoolExecutor(clients) as ex:
                for _ in range(3):
                    list(ex.map(warm, range(max(clients, replicas))))

            # Seed pass = the recompute oracle: each distinct prompt
            # computed once, cache-clean, DIRECTLY on replica j%N
            # (sequential — one active sequence, so nothing evicts
            # during seeding). Prompts whose rendezvous target is a
            # DIFFERENT replica are the peer-heat the tier converts.
            oracle = []
            for j, prompt in enumerate(prompts):
                base = f"http://127.0.0.1:{rep_ports[j % replicas]}"
                out = post(base, {"tokens": [prompt],
                                  "max_new": max_new})
                oracle.append(out["tokens"][0])
            # a few 0.5s heartbeats so the seeded prefix digests reach
            # the router before the timed window routes against them
            time.sleep(1.5)

            def prefix_stats(port: int) -> tuple[int, int, int, int]:
                m = _get_json(
                    f"http://127.0.0.1:{port}/v1/models")["models"][0]
                pc = m.get("prefix_cache", {})
                return (pc.get("hits", 0), pc.get("misses", 0),
                        pc.get("tokens_reused", 0),
                        pc.get("tokens_prefilled", 0))

            stats0 = {p: prefix_stats(p) for p in rep_ports}
            cache0 = _get_json(f"{router_base}/fleet/cache")

            failures = 0
            mismatches: list[int] = []
            lock = threading.Lock()

            def one(i: int) -> float:
                j = prompt_order[i]
                t0 = time.perf_counter()
                try:
                    out = post(router_base,
                               {"tokens": [prompts[j]],
                                "max_new": max_new})
                except Exception:
                    nonlocal failures
                    with lock:
                        failures += 1
                    raise
                if out["tokens"][0] != oracle[j]:
                    with lock:
                        mismatches.append(j)
                return time.perf_counter() - t0

            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(clients) as ex:
                latencies = list(ex.map(one, range(requests)))
            wall = time.perf_counter() - t0

            hits = misses = reused = prefilled = 0
            for port in rep_ports:
                s1 = prefix_stats(port)
                hits += s1[0] - stats0[port][0]
                misses += s1[1] - stats0[port][1]
                reused += s1[2] - stats0[port][2]
                prefilled += s1[3] - stats0[port][3]
            cache1 = _get_json(f"{router_base}/fleet/cache")
            remote = int(cache1["remote_hits_total"]
                         - cache0["remote_hits_total"])

            fetch = {"ok": 0, "miss": 0, "failed": 0}
            restored_toks = peer_toks = 0
            demotions = restores = 0
            for port in rep_ports:
                fams = _scrape_metrics(f"http://127.0.0.1:{port}")

                def total(fam: str, sname: str | None = None,
                          **labels) -> int:
                    # sum over label subsets: these families carry a
                    # `model` label the A/B does not care about
                    want = set(labels.items())
                    return int(sum(
                        v for (sn, lbls), v in
                        fams.get(fam, {}).get("samples", {}).items()
                        if sn == (sname or fam) and want <= set(lbls)))

                for oc in fetch:
                    fetch[oc] += total("fleet_peer_fetch_total",
                                       outcome=oc)
                restored_toks += total("serving_prefill_tokens",
                                       "serving_prefill_tokens_sum",
                                       source="restored")
                peer_toks += total("serving_prefill_tokens",
                                   "serving_prefill_tokens_sum",
                                   source="peer_fetched")
                demotions += total("serving_kv_spill_demotions_total")
                restores += total("serving_kv_spill_restores_total")

            assert not mismatches, (
                f"{len(mismatches)} routed responses diverged from "
                f"the recompute oracle "
                f"(prompts {sorted(set(mismatches))[:5]})")
            latencies.sort()
            q = statistics.quantiles(latencies, n=20)
            lookups = hits + misses
            return {
                "oracle": oracle,
                "hits": hits, "misses": misses,
                "reused": reused, "prefilled": prefilled,
                "remote": remote,
                "rate": (round(hits / lookups, 3) if lookups else 0.0),
                "counterfactual": (min(1.0, round(
                    (hits + remote) / lookups, 3))
                    if lookups else 0.0),
                "fetch": fetch,
                "restored_tokens": restored_toks,
                "peer_fetched_tokens": peer_toks,
                "spill_demotions": demotions,
                "spill_restores": restores,
                "failures": failures,
                "wall": wall,
                "p50_s": round(q[9], 3),
                "p95_s": round(q[18], 3),
            }
        finally:
            log.close()
            os.unlink(log.name)
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()

    control = arm(False, None)
    tier = arm(True, spill_bytes)

    assert control["oracle"] == tier["oracle"], \
        "the two arms' recompute oracles diverged"
    assert control["failures"] == 0 and tier["failures"] == 0, (
        f"client failures: control={control['failures']} "
        f"tier={tier['failures']}")
    # hints off must mean ZERO peer traffic — otherwise the control
    # arm is not a control
    assert control["fetch"] == {"ok": 0, "miss": 0, "failed": 0}, (
        f"control arm peer-fetched with hints off: {control['fetch']}")
    assert control["spill_demotions"] == 0, \
        "control arm spilled with the tier disabled"
    assert tier["fetch"]["ok"] >= 1, (
        f"tier arm never completed a peer fetch: {tier['fetch']}")
    assert tier["spill_demotions"] >= 1, (
        "no spill demotions — the pool is not under pressure; "
        "lower --fleet-kv-pool-blocks")

    affinity = control["rate"]
    counterfactual = control["counterfactual"]
    measured = tier["rate"]
    gap = round(counterfactual - affinity, 3)
    assert gap > 0, (
        f"workload produced no affinity-vs-counterfactual gap "
        f"(affinity={affinity} counterfactual={counterfactual}) — "
        f"nothing for the tier to convert")
    closed = round((measured - affinity) / gap, 3)
    assert measured - affinity >= 0.5 * gap, (
        f"cache tier closed only {closed} of the gap: "
        f"affinity={affinity} measured={measured} "
        f"counterfactual={counterfactual} "
        f"(peer_fetch={tier['fetch']} restores={tier['spill_restores']})")
    print(f"# kv tier: affinity_hit_rate={affinity} "
          f"fleet_hit_rate={measured} "
          f"counterfactual_hit_rate={counterfactual} "
          f"gap_closed={closed} peer_fetch={tier['fetch']} "
          f"spill_demotions={tier['spill_demotions']} "
          f"spill_restores={tier['spill_restores']} "
          f"restored_tokens={tier['restored_tokens']} "
          f"peer_fetched_tokens={tier['peer_fetched_tokens']}",
          file=sys.stderr)

    return {
        "metric": "serving_fleet_kv_tier",
        "mode": "fleet-kv",
        "fleet_replicas": replicas,
        "clients": clients,
        "requests": requests,
        "max_new": max_new,
        "kv_block_size": block_size,
        "kv_pool_blocks": pool_blocks,
        "kv_spill_bytes": spill_bytes,
        "distinct_prompts": k,
        "affinity_hit_rate": affinity,
        "counterfactual_hit_rate": counterfactual,
        "fleet_hit_rate": measured,
        "gap": gap,
        "gap_closed": closed,
        "peer_fetch": tier["fetch"],
        "restored_tokens": tier["restored_tokens"],
        "peer_fetched_tokens": tier["peer_fetched_tokens"],
        "spill_demotions": tier["spill_demotions"],
        "spill_restores": tier["spill_restores"],
        "control_p95_s": control["p95_s"],
        "tier_p95_s": tier["p95_s"],
        "requests_per_sec": round(requests / tier["wall"], 2),
        "tokens_per_sec": round(requests * max_new / tier["wall"], 1),
        "wall_s": round(control["wall"] + tier["wall"], 2),
        "client_failures": 0,
    }


def run_disagg(clients: int, requests: int, max_new: int, *,
               prefill_replicas: int = 1, decode_replicas: int = 3,
               block_size: int = 8, long_every: int = 2,
               long_blocks: int = 28, max_len: int = 256,
               hedge_after_s: float = 10.0) -> dict:
    """Disaggregated-pools A/B (ISSUE 12). Two fleets of EQUAL total
    replica count serve the same mixed long-prompt/short-decode
    workload through the router:

    - arm A (disagg): `prefill_replicas` pool=prefill replicas +
      `decode_replicas` pool=decode replicas — long prompts prefill on
      the prefill pool and ship KV blocks to a decode replica over
      /v1/migrate/in; short prompts pin straight to the decode pool;
    - arm B (symmetric): the same total count of mixed replicas.

    Every request's output is captured; the symmetric arm doubles as
    the token-parity oracle (sharpened lm_head: greedy argmax cannot
    flip), so the handoff path must be byte-exact against it. After
    the timed window the disagg arm SIGKILLs one prefill replica and
    pushes extra traffic through: the handoff is best-effort by
    construction, so zero client failures is the pass bar."""
    total = prefill_replicas + decode_replicas
    # Long prompts must be EXPENSIVE relative to a decode step for the
    # split to pay: the prefill slices of `long_blocks` blocks delay
    # every decode slot on a mixed replica, which is the head-of-line
    # blocking the prefill pool absorbs.
    prompt_len = long_blocks * block_size
    if prompt_len + max_new > max_len:
        raise ValueError(
            f"long prompt {prompt_len} + max_new {max_new} exceeds "
            f"max_len {max_len}")
    short_len = block_size - 1          # short: below the handoff bar
    long_new = max(2, max_new // 8)     # long prompts decode briefly
    n_short = max(1, requests // 8)     # distinct short prompts (repeat)

    def prompt_for(i: int) -> tuple[list, int]:
        if i % long_every == 0:
            # fresh long prompt every time: the prefill-heavy traffic
            # whose head-of-line blocking disaggregation removes
            return ([3 + i % 250, 100] + [7 + (i + t) % 200
                                          for t in range(prompt_len - 2)],
                    long_new)
        j = i % n_short
        return ([9 + j % 200, 50] + [11 + (j + t) % 150
                                     for t in range(short_len - 2)],
                max_new)

    def arm(pools: list, kill_extra: bool) -> dict:
        import tempfile

        router_port = free_port()
        rep_ports = [free_port() for _ in pools]
        router_base = f"http://127.0.0.1:{router_port}"
        log = tempfile.NamedTemporaryFile(
            mode="w+", suffix=".log", prefix="kftpu-disagg-",
            delete=False)
        procs: list = []
        try:
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 ROUTER_CODE.format(repo=REPO, port=router_port,
                                    block_size=block_size,
                                    policy="affinity",
                                    hedge_after_s=hedge_after_s,
                                    peer_hints=True)],
                stdout=log, stderr=subprocess.STDOUT))
            for idx, (port, pool) in enumerate(zip(rep_ports, pools)):
                procs.append(subprocess.Popen(
                    [sys.executable, "-c",
                     DISAGG_REPLICA_CODE.format(
                         repo=REPO, port=port, idx=idx, pool=pool,
                         router=router_base, block_size=block_size,
                         max_len=max_len)],
                    stdout=log, stderr=subprocess.STDOUT))

            deadline = time.monotonic() + 180
            ready = False
            while time.monotonic() < deadline:
                if any(p.poll() is not None for p in procs):
                    break
                try:
                    snap = _get_json(f"{router_base}/fleet/replicas")
                    if snap["counts"]["ready"] >= len(pools):
                        ready = True
                        break
                except Exception:
                    pass
                time.sleep(0.5)
            if not ready:
                log.flush()
                with open(log.name) as f:
                    tail = "\n".join(f.read().splitlines()[-30:])
                rcs = [p.poll() for p in procs]
                raise RuntimeError(
                    f"disagg fleet never became ready (rcs={rcs}):"
                    f"\n{tail}")

            def post(base: str, body: dict,
                     timeout: float = 120.0) -> dict:
                req = urllib.request.Request(
                    f"{base}/v1/models/tiny:generate",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return json.loads(r.read())

            # direct warm on every replica: compile the admission
            # shapes for BOTH prompt classes before the timed window
            warm_long = [255, 99] + [5 + t % 200
                                     for t in range(prompt_len - 2)]
            warm_short = [254, 98] + [6 + t % 200
                                      for t in range(short_len - 2)]

            def warm(i: int) -> None:
                base = f"http://127.0.0.1:{rep_ports[i % len(pools)]}"
                post(base, {"tokens": [warm_long],
                            "max_new": long_new})
                post(base, {"tokens": [warm_short],
                            "max_new": max_new})

            with concurrent.futures.ThreadPoolExecutor(clients) as ex:
                for _ in range(2):
                    list(ex.map(warm, range(max(clients, len(pools)))))

            # routed warm: FRESH long prompts through the router so
            # the disagg arm compiles its whole handoff path (export
            # gather on the prefill pool, import scatter on every
            # decode replica) before the timed window — the symmetric
            # arm gets the same routed traffic for fairness
            def warm_routed(i: int) -> None:
                toks = [253 - i % 16, 97] + [4 + (i + t) % 190
                                             for t in range(prompt_len - 2)]
                post(router_base, {"tokens": [toks], "max_new": long_new})

            with concurrent.futures.ThreadPoolExecutor(clients) as ex:
                for _ in range(2):
                    list(ex.map(warm_routed,
                                range(max(clients, 2 * len(pools)))))

            failures = 0
            outputs: dict = {}
            lock = __import__("threading").Lock()

            def one(i: int) -> float:
                toks, new = prompt_for(i)
                t0 = time.perf_counter()
                try:
                    out = post(router_base,
                               {"tokens": [toks], "max_new": new})
                    assert len(out["tokens"][0]) == new, out
                except Exception:
                    nonlocal failures
                    with lock:
                        failures += 1
                    raise
                if i < requests:
                    # prompt_for(i) is deterministic, so request i is
                    # the SAME prompt in both arms — capture for the
                    # cross-arm parity check (kill-phase extras are
                    # failure-counted only)
                    with lock:
                        outputs[i] = out["tokens"][0]
                return time.perf_counter() - t0

            stats0 = _get_json(f"{router_base}/fleet/stats")
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(clients) as ex:
                latencies = list(ex.map(one, range(requests)))
            wall = time.perf_counter() - t0
            stats1 = _get_json(f"{router_base}/fleet/stats")

            killed = None
            if kill_extra:
                # SIGKILL the first prefill replica (terminate() would
                # deregister gracefully), then push extra traffic: the
                # handoff must fail OVER, never fail the client
                killed = pools.index("prefill")
                procs[1 + killed].kill()
                procs[1 + killed].wait()
                extra = max(8, requests // 4)
                with concurrent.futures.ThreadPoolExecutor(clients) as ex:
                    list(ex.map(one, range(requests,
                                           requests + extra)))

            toks_out = sum(
                (long_new if i % long_every == 0 else max_new)
                for i in range(requests))
            latencies.sort()
            q = statistics.quantiles(latencies, n=20)
            return {
                "wall_s": round(wall, 2),
                "tokens_per_sec": round(toks_out / wall, 1),
                "requests_per_sec": round(requests / wall, 2),
                "p50_s": round(q[9], 3),
                "p95_s": round(q[18], 3),
                "outputs": outputs,
                "client_failures": failures,
                "killed_replica": killed,
                "handoff": {
                    oc: int(stats1["handoff"][oc]
                            - stats0["handoff"][oc])
                    for oc in stats1["handoff"]},
                "handoff_bytes": int(stats1["handoff_bytes"]
                                     - stats0["handoff_bytes"]),
                "route_by_pool": {
                    pool: int(stats1["route_by_pool"][pool]
                              - stats0["route_by_pool"][pool])
                    for pool in stats1["route_by_pool"]},
            }
        finally:
            log.close()
            os.unlink(log.name)
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()

    sym = arm(["mixed"] * total, kill_extra=False)
    dis = arm(["prefill"] * prefill_replicas
              + ["decode"] * decode_replicas, kill_extra=True)

    # token parity: every prompt class the two arms both served must
    # decode identically — the handoff ships KV, not approximations
    shared = set(sym["outputs"]) & set(dis["outputs"])
    assert shared, "arms captured no common requests"
    mismatches = [i for i in sorted(shared)
                  if sym["outputs"][i] != dis["outputs"][i]]
    assert not mismatches, (
        f"handoff token parity broken for requests {mismatches[:5]}")
    assert dis["client_failures"] == 0, (
        f"{dis['client_failures']} client failures in the disagg arm "
        "(the handoff must be best-effort)")
    assert dis["handoff"]["ok"] > 0, (
        f"no handoff ever landed: {dis['handoff']}")

    return {
        "metric": "serving_disagg_throughput",
        "mode": "disagg",
        "prefill_replicas": prefill_replicas,
        "decode_replicas": decode_replicas,
        "total_replicas": total,
        "clients": clients,
        "requests": requests,
        "max_new": max_new,
        "long_every": long_every,
        "long_prompt_len": prompt_len,
        "short_prompt_len": short_len,
        "kv_block_size": block_size,
        "tokens_per_sec": dis["tokens_per_sec"],
        "requests_per_sec": dis["requests_per_sec"],
        "p50_s": dis["p50_s"],
        "p95_s": dis["p95_s"],
        "wall_s": dis["wall_s"],
        "symmetric_tokens_per_sec": sym["tokens_per_sec"],
        "symmetric_p50_s": sym["p50_s"],
        "symmetric_p95_s": sym["p95_s"],
        "disagg_speedup": round(
            dis["tokens_per_sec"] / sym["tokens_per_sec"], 3),
        "handoff": dis["handoff"],
        "handoff_bytes": dis["handoff_bytes"],
        "route_by_pool": dis["route_by_pool"],
        "token_parity": True,
        "parity_requests": len(shared),
        "killed_prefill_replica": dis["killed_replica"],
        "client_failures": dis["client_failures"],
    }


def run_chaos(clients: int, requests: int, max_new: int, *,
              replicas: int = 3, block_size: int = 8, seed: int = 1,
              drop_rate: float = 0.08, delay_rate: float = 0.08,
              delay_s: float = 0.02, duplicate_rate: float = 0.05,
              blackhole_beats: int = 14, retries: int = 6) -> dict:
    """The fleet fault-injection run. N replicas behind a chaos-armed
    router; every third request streams, the rest are one-shot, and
    ALL of them are compared token-for-token against a fault-free
    oracle taken directly from a replica before the faults start.
    Mid-run the harness SIGKILLs the last replica (crash failover, no
    graceful path) and instant-drains replica-0 (live KV migration to
    the survivors) while the second half is in flight; afterwards it
    probes a wedged migration transfer against a survivor and checks
    the rollback leaked nothing. The run raises unless client-visible
    failures and token mismatches are both zero."""
    import tempfile

    router_port = free_port()
    rep_ports = [free_port() for _ in range(replicas)]
    router_base = f"http://127.0.0.1:{router_port}"
    log = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".log", prefix="kftpu-chaosload-", delete=False)
    procs: list[subprocess.Popen] = []
    try:
        procs.append(subprocess.Popen(
            [sys.executable, "-c",
             CHAOS_ROUTER_CODE.format(
                 repo=REPO, port=router_port, block_size=block_size,
                 seed=seed, drop_rate=drop_rate, delay_rate=delay_rate,
                 delay_s=delay_s, duplicate_rate=duplicate_rate,
                 blackhole_beats=blackhole_beats, retries=retries)],
            stdout=log, stderr=subprocess.STDOUT))
        for idx, port in enumerate(rep_ports):
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 CHAOS_REPLICA_CODE.format(
                     repo=REPO, port=port, idx=idx,
                     router=router_base, block_size=block_size)],
                stdout=log, stderr=subprocess.STDOUT))

        # the armed heartbeat blackhole can hold replica-1 DEGRADED for
        # stretches of the warmup window — the poll just needs one
        # moment where every replica's beat has landed
        deadline = time.monotonic() + 240
        ready = False
        while time.monotonic() < deadline:
            if any(p.poll() is not None for p in procs):
                break
            try:
                counts = _get_json(
                    f"{router_base}/fleet/replicas")["counts"]
                if counts["ready"] >= replicas:
                    ready = True
                    break
            except Exception:
                pass
            time.sleep(0.5)
        if not ready:
            log.flush()
            with open(log.name) as f:
                tail = "\n".join(f.read().splitlines()[-30:])
            rcs = [p.poll() for p in procs]
            raise RuntimeError(
                f"chaos fleet never became ready (rcs={rcs}):\n{tail}")

        def post(base: str, body: dict, timeout: float = 120.0) -> dict:
            return _post_json(f"{base}/v1/models/tiny:generate", body,
                              timeout=timeout)

        # Warm every replica directly (compile the batch shapes before
        # timing); first token 255 keeps the warm prompt's radix line
        # disjoint from the measured prompts (3..10) and the wedge
        # probe (509).
        prompt_len = 3 * block_size
        warm_prompt = [255, 99] + [5 + t % 200
                                   for t in range(prompt_len - 2)]

        def warm(i: int) -> None:
            base = f"http://127.0.0.1:{rep_ports[i % replicas]}"
            post(base, {"tokens": [warm_prompt], "max_new": max_new})

        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            for _ in range(3):
                list(ex.map(warm, range(max(clients, replicas))))

        # Fault-free oracle: greedy outputs per distinct prompt, taken
        # DIRECTLY from replica-0 (no router, no injector). Sharpened
        # lm_head makes these byte-reproducible however the chaos
        # phase batches, migrates, or replays them.
        k = max(1, requests // 6)
        prompts = [[3 + j % 250, 100] + [7 + (j + t) % 200
                                         for t in range(prompt_len - 2)]
                   for j in range(k)]
        rep0 = f"http://127.0.0.1:{rep_ports[0]}"
        oracle = [post(rep0, {"tokens": [pr], "max_new": max_new})
                  ["tokens"][0] for pr in prompts]

        prompt_order = [i % k for i in range(requests)]
        random.Random(seed).shuffle(prompt_order)
        route0 = _get_json(f"{router_base}/fleet/stats")

        failures: list[str] = []
        mismatches: list[str] = []
        lock = __import__("threading").Lock()

        def one(i: int) -> float | None:
            j = prompt_order[i]
            body = {"tokens": [prompts[j]], "max_new": max_new}
            t0 = time.perf_counter()
            try:
                if i % 3 == 0:
                    got = _sse_generate(router_base, body)
                else:
                    got = post(router_base, body)["tokens"][0]
            except Exception as e:  # noqa: BLE001 — tallied, asserted
                with lock:
                    failures.append(f"req {i}: {type(e).__name__}: {e}")
                return None
            if [int(t) for t in got] != [int(t) for t in oracle[j]]:
                with lock:
                    mismatches.append(
                        f"req {i} prompt {j}: {got} != {oracle[j]}")
            return time.perf_counter() - t0

        half = requests // 2
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            latencies = [x for x in ex.map(one, range(half))
                         if x is not None]
        # second half: both process-level faults land MID-BURST, while
        # generations are genuinely in flight — SIGKILL (not terminate,
        # which would run the graceful deregister+drain path) the last
        # replica, then instant-drain replica-0 THROUGH the router:
        # export + push of its live sequences must finish in seconds
        killed = replicas - 1
        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            futs = [ex.submit(one, i) for i in range(half, requests)]
            time.sleep(0.05)
            procs[1 + killed].kill()
            t_dr = time.perf_counter()
            dr = _post_json(f"{router_base}/fleet/drain",
                            {"id": "replica-0"}, timeout=60.0)
            drain_s = time.perf_counter() - t_dr
            latencies += [x for x in (f.result() for f in futs)
                          if x is not None]
        procs[1 + killed].wait()
        wall = time.perf_counter() - t0
        fwd = dr.get("replica") or {}
        if fwd.get("in_flight") != 0:
            raise AssertionError(
                f"drain left work in flight on replica-0: {dr}")
        try:
            _get_json(f"{rep0}/healthz", timeout=5)
            drained_health = 200
        except urllib.error.HTTPError as e:
            drained_health = e.code
        if drained_health != 503:
            raise AssertionError(
                f"drained replica still admits work "
                f"(healthz={drained_health})")

        # wedge probe against the survivor: a mid-transfer fault must
        # roll back without leaking a single pool block, and the same
        # record must import cleanly afterwards
        from kubeflow_tpu.models import llama as _llama
        from kubeflow_tpu.serving import migration as _mig
        import numpy as _np
        _cfg = _llama.LLAMA_TINY
        geom = {"block_size": block_size,
                "num_kv_heads": int(_cfg.num_kv_heads),
                "head_dim": int(_cfg.head_dim),
                "num_layers": int(_cfg.num_layers)}
        kv_shape = (geom["num_layers"], 1, block_size,
                    geom["num_kv_heads"], geom["head_dim"])
        probe = _mig.pack_record(
            request_id="chaos-wedge-probe", tenant="", ns="",
            tokens=[509 - t for t in range(block_size + 1)], out=[],
            lps=[], max_new=4, sampling={}, geometry=geom,
            kv=(_np.zeros(kv_shape, _np.float32),
                _np.zeros(kv_shape, _np.float32)))
        surv = f"http://127.0.0.1:{rep_ports[1]}"

        def _free_blocks() -> int:
            return _get_json(f"{surv}/healthz")["models"]["tiny"][
                "kv_blocks_free"]

        free0 = _free_blocks()
        try:
            _post_json(f"{surv}/v1/migrate/in",
                       {"model": "tiny", "record": probe, "wedge": True})
            raise AssertionError("wedged import reported success")
        except urllib.error.HTTPError as e:
            wedge_body = e.read().decode()
            if e.code != 500 or "wedged" not in wedge_body:
                raise AssertionError(
                    f"wedge probe: {e.code} {wedge_body}") from e
        if _free_blocks() != free0:
            raise AssertionError(
                f"wedged import leaked pool blocks: {free0} -> "
                f"{_free_blocks()}")
        imported = _post_json(f"{surv}/v1/migrate/in",
                              {"model": "tiny", "record": probe})
        if imported.get("blocks") != 1 or _free_blocks() != free0 - 1:
            raise AssertionError(f"clean re-import failed: {imported}")

        route1 = _get_json(f"{router_base}/fleet/stats")
        try:
            # no policies configured on this arm, so the table shows
            # an empty-but-conserved ledger — the closed-loop arm is
            # where decisions appear; printing both keeps the two
            # chaos arms' audit output symmetric
            _print_decision_table(
                _get_json(f"{router_base}/fleet/decisions"))
        except Exception:
            pass
        ledger = route1.get("chaos") or {}
        if sum(ledger.values()) <= 0:
            raise AssertionError(
                f"no faults were injected (ledger {ledger}) — the "
                "chaos arm ran fault-free")
        if failures:
            raise AssertionError(
                f"{len(failures)} client-visible failures under "
                f"chaos: {failures[:5]}")
        if mismatches:
            raise AssertionError(
                f"{len(mismatches)} token mismatches vs the fault-free "
                f"oracle: {mismatches[:3]}")
        latencies.sort()
        q = statistics.quantiles(latencies, n=20)
        if q[18] >= 30.0:
            raise AssertionError(
                f"p95 {q[18]:.1f}s unbounded under chaos (retry storm "
                "or wedged dispatch)")
        return {
            "metric": "serving_chaos",
            "mode": "chaos",
            "fleet_replicas": replicas,
            "clients": clients,
            "requests": requests,
            "max_new": max_new,
            "kv_block_size": block_size,
            "seed": seed,
            "drop_rate": drop_rate,
            "delay_rate": delay_rate,
            "duplicate_rate": duplicate_rate,
            "stream_requests": sum(1 for i in range(requests)
                                   if i % 3 == 0),
            "requests_per_sec": round(requests / wall, 2),
            "tokens_per_sec": round(requests * max_new / wall, 1),
            "p50_s": round(q[9], 3),
            "p95_s": round(q[18], 3),
            "wall_s": round(wall, 2),
            "injected": ledger,
            "failover": int(route1["failover"] - route0["failover"]),
            "retries": int(route1["route_total"].get("retry", 0)
                           - route0["route_total"].get("retry", 0)),
            "killed_replica": killed,
            "drain_s": round(drain_s, 3),
            "drain_under_2s": drain_s < 2.0,
            "drain_migrated": int(fwd.get("migrated", 0)),
            "drain_failed": int(fwd.get("failed", 0)),
            "migrate_s": fwd.get("migrate_s"),
            "wedge_rollback_ok": True,
            "client_failures": 0,
            "token_mismatches": 0,
        }
    finally:
        log.close()
        os.unlink(log.name)
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def _print_decision_table(dec: dict, *, limit: int = 20) -> None:
    """Render a /fleet/decisions payload as the run's audit table (on
    stderr — stdout stays the one machine-readable JSON line)."""
    print("decision ledger "
          f"(evaluations={dec.get('evaluations')} "
          f"conserved={dec.get('conserved')}):", file=sys.stderr)
    for pol, ocs in sorted((dec.get("by_policy") or {}).items()):
        booked = {k: v for k, v in sorted(ocs.items()) if v}
        print(f"  {pol}: {booked}", file=sys.stderr)
    rows = (dec.get("records") or [])[-limit:]
    if rows:
        print(f"  last {len(rows)} records "
              "(outcome/action/verdict/signal):", file=sys.stderr)
    for r in rows:
        ev = r.get("evidence") or {}
        sig = ev.get("signal")
        print(f"    {r.get('policy'):<28} {r.get('outcome'):<22} "
              f"{str(r.get('action') or '-'):<14} "
              f"{str(r.get('verdict') or '-'):<14} "
              f"{sig if sig is None else round(float(sig), 3)}",
              file=sys.stderr)


def run_chaos_closed_loop(clients: int, requests: int, max_new: int, *,
                          replicas: int = 1, block_size: int = 8,
                          retries: int = 2, interval_s: float = 1.0,
                          short_window_s: float = 10.0,
                          cooldown_s: float = 60.0,
                          verify_window_s: float = 75.0) -> dict:
    """The closed-loop recovery arm (--mode chaos --closed-loop): the
    CONTROLLER is the only recovery path. A flood runs against the
    fleet while the harness SIGKILLs every replica process; routed
    requests start 503ing, the router's own availability burn gauge
    breaches, and the controller's scale_out policy raises the desired
    floor at /fleet/autoscale. The harness plays the dumb infra half
    of the loop: it polls that endpoint and boots a replacement
    replica ONLY when `controller_floor` exceeds live capacity — never
    on the demand-based recommendation (which asks for min_replicas
    whenever the fleet is empty, controller or not). Clients retry on
    503/connection errors, so the pass bar is zero requests that never
    completed, token-exact outputs vs the pre-fault oracle, burn back
    under 1.0 within one short window of the replacement turning
    routable, and the fired decision booked `recovered` in
    /fleet/decisions."""
    import tempfile
    import threading

    router_port = free_port()
    rep_ports = [free_port() for _ in range(replicas)]
    router_base = f"http://127.0.0.1:{router_port}"
    log = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".log", prefix="kftpu-closedloop-",
        delete=False)
    procs: list[subprocess.Popen] = []

    def boot_replica(idx: int, port: int) -> None:
        procs.append(subprocess.Popen(
            [sys.executable, "-c",
             CHAOS_REPLICA_CODE.format(
                 repo=REPO, port=port, idx=idx,
                 router=router_base, block_size=block_size)],
            stdout=log, stderr=subprocess.STDOUT))

    try:
        procs.append(subprocess.Popen(
            [sys.executable, "-c",
             CLOSED_LOOP_ROUTER_CODE.format(
                 repo=REPO, port=router_port, block_size=block_size,
                 retries=retries, interval_s=interval_s,
                 short_window_s=short_window_s, cooldown_s=cooldown_s,
                 verify_s=verify_window_s)],
            stdout=log, stderr=subprocess.STDOUT))
        for idx, port in enumerate(rep_ports):
            boot_replica(idx, port)

        def live_count() -> int:
            counts = _get_json(f"{router_base}/fleet/replicas")["counts"]
            return counts.get("ready", 0) + counts.get("degraded", 0)

        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if any(p.poll() is not None for p in procs):
                break
            try:
                if live_count() >= replicas:
                    break
            except Exception:
                pass
            time.sleep(0.5)
        try:
            ready = live_count() >= replicas
        except Exception:
            ready = False
        if not ready:
            log.flush()
            with open(log.name) as f:
                tail = "\n".join(f.read().splitlines()[-30:])
            raise RuntimeError(
                f"closed-loop fleet never became ready "
                f"(rcs={[p.poll() for p in procs]}):\n{tail}")

        def post(base: str, body: dict, timeout: float = 120.0) -> dict:
            return _post_json(f"{base}/v1/models/tiny:generate", body,
                              timeout=timeout)

        prompt_len = 3 * block_size
        warm_prompt = [255, 99] + [5 + t % 200
                                   for t in range(prompt_len - 2)]
        for port in rep_ports:
            post(f"http://127.0.0.1:{port}",
                 {"tokens": [warm_prompt], "max_new": max_new})

        # fault-free oracle straight off replica-0 (sharpened lm_head:
        # byte-reproducible on the replacement replica too, which
        # boots from the identical seed)
        k = max(1, requests // 6)
        prompts = [[3 + j % 250, 100] + [7 + (j + t) % 200
                                         for t in range(prompt_len - 2)]
                   for j in range(k)]
        rep0 = f"http://127.0.0.1:{rep_ports[0]}"
        oracle = [post(rep0, {"tokens": [pr], "max_new": max_new})
                  ["tokens"][0] for pr in prompts]

        prompt_order = [i % k for i in range(requests)]
        random.Random(1).shuffle(prompt_order)

        failures: list[str] = []
        mismatches: list[str] = []
        lock = threading.Lock()

        def one(i: int, deadline_s: float) -> None:
            """One request, retried through the outage: a 503 (or a
            dead-router blip) is the router honestly reporting zero
            capacity — the client backs off and retries until the
            controller has restored the fleet or the deadline says
            the loop never closed."""
            j = prompt_order[i]
            body = {"tokens": [prompts[j]], "max_new": max_new}
            stop = time.monotonic() + deadline_s
            while True:
                try:
                    got = post(router_base, body)["tokens"][0]
                    break
                except Exception as e:  # noqa: BLE001 — retried
                    if time.monotonic() >= stop:
                        with lock:
                            failures.append(
                                f"req {i}: {type(e).__name__}: {e}")
                        return
                    time.sleep(0.5)
            if [int(t) for t in got] != [int(t) for t in oracle[j]]:
                with lock:
                    mismatches.append(
                        f"req {i} prompt {j}: {got} != {oracle[j]}")

        # infra poller: the dumb half of the loop. Boots a replacement
        # replica only while the CONTROLLER floor exceeds live+booted
        # capacity.
        stop_infra = threading.Event()
        booted: list[int] = []
        infra_floor_seen = [0]

        def infra() -> None:
            while not stop_infra.is_set():
                try:
                    rec = _get_json(f"{router_base}/fleet/autoscale")
                    floor = int(rec.get("controller_floor", 0))
                    infra_floor_seen[0] = max(infra_floor_seen[0],
                                              floor)
                    if floor > live_count() + len(booted):
                        port = free_port()
                        boot_replica(replicas + len(booted), port)
                        booted.append(port)
                except Exception:
                    pass
                stop_infra.wait(0.5)

        infra_thread = threading.Thread(target=infra, daemon=True)
        infra_thread.start()

        half = requests // 2
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            list(ex.map(lambda i: one(i, 60.0), range(half)))
        # second half: SIGKILL every replica mid-burst — total
        # capacity loss, nothing recovers unless the controller fires
        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            futs = [ex.submit(one, i, 240.0)
                    for i in range(half, requests)]
            time.sleep(0.05)
            t_kill = time.perf_counter()
            for pproc in procs[1:1 + replicas]:
                pproc.kill()
            for f in futs:
                f.result()
        wall = time.perf_counter() - t0

        # replacement routable?
        t_routable = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if live_count() >= 1:
                    t_routable = time.perf_counter()
                    break
            except Exception:
                pass
            time.sleep(0.5)
        if t_routable is None:
            raise AssertionError(
                "no replacement replica ever turned routable — the "
                f"closed loop never actuated (floor seen: "
                f"{infra_floor_seen[0]}, booted: {len(booted)})")

        # burn back under 1.0 within one short window of routable
        burn_final = None
        deadline = time.monotonic() + short_window_s + 30.0
        while time.monotonic() < deadline:
            fams = _scrape_metrics(router_base)
            burn_final = _burn_rate(fams, "fleet_availability", "short")
            if burn_final < 1.0:
                break
            time.sleep(1.0)
        recovered_s = time.perf_counter() - t_kill
        if burn_final is None or burn_final >= 1.0:
            raise AssertionError(
                f"availability burn never cleared after recovery "
                f"(last {burn_final})")

        # the fired decision must book `recovered` once the verify
        # window lapses (the controller resolves on its own ticks)
        verdict = None
        fired_rec = None
        deadline = time.monotonic() + verify_window_s + 45.0
        while time.monotonic() < deadline:
            dec = _get_json(f"{router_base}/fleet/decisions")
            fired = [r for r in dec.get("records", [])
                     if r.get("outcome") == "fired"]
            if fired and all(r.get("verdict") != "pending"
                             for r in fired):
                fired_rec = fired[-1]
                verdict = fired_rec.get("verdict")
                break
            time.sleep(1.0)
        dec = _get_json(f"{router_base}/fleet/decisions")
        _print_decision_table(dec)
        if not dec.get("conserved"):
            raise AssertionError(
                f"decision ledger lost an evaluation: {dec}")
        if dec["outcomes"].get("fired", 0) < 1:
            raise AssertionError(
                f"controller never fired: {dec['outcomes']}")
        if verdict != "recovered":
            raise AssertionError(
                f"fired decision verdict {verdict!r}, want "
                f"'recovered' (record {fired_rec})")
        stop_infra.set()
        infra_thread.join(timeout=5)

        if failures:
            raise AssertionError(
                f"{len(failures)} requests never completed through "
                f"the outage: {failures[:5]}")
        if mismatches:
            raise AssertionError(
                f"{len(mismatches)} token mismatches vs the "
                f"fault-free oracle: {mismatches[:3]}")

        fams = _scrape_metrics(router_base)
        budget_left = fams["slo_error_budget_remaining"]["samples"][
            ("slo_error_budget_remaining",
             (("slo", "fleet_availability"),))]
        return {
            "metric": "serving_chaos_closed_loop",
            "mode": "chaos",
            "closed_loop": True,
            "fleet_replicas": replicas,
            "clients": clients,
            "requests": requests,
            "max_new": max_new,
            "kv_block_size": block_size,
            "short_window_s": short_window_s,
            "wall_s": round(wall, 2),
            "replacements_booted": len(booted),
            "controller_floor_peak": infra_floor_seen[0],
            "outage_to_routable_s": round(t_routable - t_kill, 2),
            "outage_to_burn_clear_s": round(recovered_s, 2),
            "burn_final": round(burn_final, 3),
            "error_budget_remaining": round(budget_left, 4),
            "decisions": dec["outcomes"],
            "actions_fired": dec["outcomes"].get("fired", 0),
            "verdict": verdict,
            "ledger_conserved": True,
            "client_failures": 0,
            "token_mismatches": 0,
        }
    finally:
        log.close()
        os.unlink(log.name)
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def _train_arm(workdir: str, *, replicas: int, steps: int,
               save_every: int, kill: str | None,
               slow_save_s: float, slo_short_s: float = 6.0) -> dict:
    """One elastic-training gang: a coordinator + `replicas` workers on
    a shared checkpoint dir. `kill` selects the fault:

    - None: fault-free run (the loss oracle).
    - "mid-step": SIGKILL a NON-chief worker once every member is past
      2*save_every+1 (so a committed resume point exists) while it is
      between checkpoints.
    - "mid-save": give the CHIEF a widened post-dispatch save window
      (slow_save_s) and SIGKILL it while /elastic/world shows its phase
      == "saving" — the step dir exists on disk but its COMMITTED
      marker cannot have landed, so the survivors must detect the
      partial save, fall back to the last committed step, and re-save
      over the stale dir.

    Survivors must run to `steps` at world N-1 with zero corrupt
    restores. Returns per-worker RESULT dicts, the merged step->loss
    curve (last write wins — replays after a restore overwrite), and
    the coordinator's restart counter.
    """
    os.makedirs(workdir, exist_ok=True)
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    ckpt_dir = os.path.join(workdir, "ckpt")
    rids = [f"tr{i}" for i in range(replicas)]
    chief_rid, victim_rid = rids[0], rids[-1]
    if kill == "mid-save":
        victim_rid = chief_rid
    logs = {rid: os.path.join(workdir, f"{rid}.log") for rid in rids}
    loss_logs = {rid: os.path.join(workdir, f"{rid}.loss.jsonl")
                 for rid in rids}
    coord_log = open(os.path.join(workdir, "coord.log"), "w")
    procs: dict[str, subprocess.Popen] = {}
    worker_logs: dict[str, object] = {}
    try:
        coord = subprocess.Popen(
            [sys.executable, "-c",
             TRAIN_COORDINATOR_CODE.format(
                 repo=REPO, port=port, min_replicas=replicas,
                 degraded_s=1.0, dead_s=2.5,
                 slo_short_s=slo_short_s, burn_hold_s=3.0)],
            stdout=coord_log, stderr=subprocess.STDOUT)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                _get_json(f"{base}/elastic/world")
                break
            except Exception:
                if coord.poll() is not None:
                    raise RuntimeError(
                        f"train coordinator died rc={coord.poll()}")
                time.sleep(0.2)
        else:
            raise RuntimeError("train coordinator never came up")
        for rid in rids:
            f = open(logs[rid], "w")
            worker_logs[rid] = f
            procs[rid] = subprocess.Popen(
                [sys.executable, "-c",
                 TRAIN_WORKER_CODE.format(
                     repo=REPO, coordinator=base, rid=rid,
                     ckpt=ckpt_dir, steps=steps, save_every=save_every,
                     slow_save_s=(slow_save_s if rid == victim_rid
                                  and kill == "mid-save" else 0.0),
                     loss_log=loss_logs[rid])],
                stdout=f, stderr=subprocess.STDOUT)

        def world() -> dict:
            return _get_json(f"{base}/elastic/world")

        def tail(rid: str) -> str:
            worker_logs[rid].flush()
            with open(logs[rid]) as f:
                return "\n".join(f.read().splitlines()[-25:])

        # formation: every worker registered and stepping (first jit
        # compile takes tens of seconds on CPU — the background
        # heartbeater keeps them alive through it)
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            w = world()
            if w["world_size"] == replicas and w["ready"]:
                break
            dead = [r for r, p in procs.items() if p.poll() is not None]
            if dead:
                raise RuntimeError(
                    f"worker(s) {dead} died during formation:\n"
                    + tail(dead[0]))
            time.sleep(0.3)
        else:
            raise AssertionError(
                f"gang never formed at {replicas} replicas: {world()}")

        # Federation check: with the whole gang live, /elastic/metrics
        # must strict-parse and show fleet_federation_up == 1 for the
        # coordinator AND every worker (a worker's first enriched
        # heartbeat can lag registration by an interval, so retry
        # briefly before calling it a regression). The worker goodput
        # ledgers must also arrive conserved: the summed per-cause
        # counters equal the summed wall-clock gauge.
        deadline = time.monotonic() + 30
        while True:
            efams = _scrape_federated(base)
            up = {lbls[0][1]: v for (_, lbls), v in
                  efams["fleet_federation_up"]["samples"].items()}
            down = [r for r in ("coordinator", *rids) if up.get(r) != 1.0]
            if not down:
                break
            if time.monotonic() >= deadline:
                raise AssertionError(
                    f"/elastic/metrics never federated {down}: {up}")
            time.sleep(0.2)
        booked = sum(
            efams["train_goodput_seconds_total"]["samples"].values())
        walls = sum(
            efams["train_goodput_wall_seconds"]["samples"].values())
        if abs(booked - walls) > 1e-3 + 1e-4 * max(walls, 1.0):
            raise AssertionError(
                f"federated goodput ledger not conserved: booked "
                f"{booked} != wall {walls}")

        killed_at = None
        if kill is not None:
            # Arm the fault one save interval in: the first save is
            # dispatched (its COMMITTED marker flushes when the
            # surviving chief's rebuild() closes the old checkpointer),
            # and — critically — EARLY enough that the survivors hit
            # the soft-lockstep wall (kill_step + lag + 1 < steps) and
            # are still mid-run when dead-detection bumps the
            # generation. Killing later lets a fast survivor finish
            # before the restart fires and the arm proves nothing.
            resume_floor = save_every
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                w = world()
                step_map = w.get("steps", {})
                phases = w.get("phases", {})
                if kill == "mid-step":
                    if step_map and all(
                            s is not None and s >= resume_floor
                            for s in step_map.values()):
                        break
                else:  # mid-save: catch the chief inside the window
                    if (phases.get(victim_rid) == "saving"
                            and (step_map.get(victim_rid) or 0)
                            >= 2 * save_every):
                        break
                if procs[victim_rid].poll() is not None:
                    raise RuntimeError(
                        f"victim {victim_rid} exited before the kill:\n"
                        + tail(victim_rid))
                time.sleep(0.02)
            else:
                raise AssertionError(
                    f"{kill} kill window never opened: {world()}")
            if kill == "mid-save":
                # Let the async writer get the step dir onto disk
                # first — the COMMITTED marker still cannot appear
                # until the NEXT save's flush, so this lands the kill
                # in the worst spot: bytes present, marker absent. The
                # survivor must skip the uncommitted dir at restore and
                # re-save over it.
                time.sleep(slow_save_s * 0.6)
            procs[victim_rid].kill()
            procs[victim_rid].wait()
            killed_at = dict(world().get("steps", {}))

        survivors = [r for r in rids if r != victim_rid or kill is None]
        # While the survivors run down the rebuild -> restore -> replay
        # path, poll the coordinator's burn gauges: a SIGKILL arm must
        # drive slo_burn_rate{slo=train_goodput,window=short} over the
        # 1.0 alert line while the gang is re-spending worker-seconds,
        # and the lost member must open the restart-burn hold.
        burn_peak = {"train_goodput": 0.0, "train_restart_burn": 0.0}
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            try:
                fams = _scrape_metrics(base)
                for slo in burn_peak:
                    burn_peak[slo] = max(
                        burn_peak[slo], _burn_rate(fams, slo, "short"))
            except Exception:
                if coord.poll() is not None:
                    raise RuntimeError(
                        f"train coordinator died rc={coord.poll()} "
                        "mid-arm")
                # transient scrape hiccup: the next poll retries
            if all(procs[r].poll() is not None for r in survivors):
                break
            time.sleep(0.2)
        else:
            hung = [r for r in survivors if procs[r].poll() is None]
            raise AssertionError(
                f"survivor(s) {hung} hung after the {kill} kill "
                f"(world {world()}):\n" + tail(hung[0]))
        for rid in survivors:
            if procs[rid].returncode != 0:
                raise AssertionError(
                    f"survivor {rid} exited rc={procs[rid].returncode} "
                    f"after the {kill} kill:\n" + tail(rid))

        # Recovery: once the fleet is done no new bad events arrive, so
        # after one short window the burn gauge must drop back under
        # the alert line (this is exactly when the page would clear).
        burn_final = {}
        if kill is not None:
            time.sleep(slo_short_s + 1.0)
            fams = _scrape_metrics(base)
            burn_final = {
                slo: _burn_rate(fams, slo, "short") for slo in burn_peak}

        results = {}
        for rid in survivors:
            worker_logs[rid].flush()
            with open(logs[rid]) as f:
                lines = [ln for ln in f.read().splitlines()
                         if ln.startswith("RESULT ")]
            if not lines:
                raise AssertionError(
                    f"worker {rid} printed no RESULT line:\n"
                    + tail(rid))
            results[rid] = json.loads(lines[-1][len("RESULT "):])

        # merged loss curve: later lines overwrite (a replay after a
        # restore re-runs steps — determinism means the overwrite is a
        # no-op up to resharding noise, which the parity gate bounds)
        losses: dict[int, float] = {}
        for rid in rids:
            if not os.path.exists(loss_logs[rid]):
                continue
            with open(loss_logs[rid]) as f:
                for ln in f:
                    rec = json.loads(ln)
                    losses[int(rec["step"])] = float(rec["loss"])

        fams = _scrape_metrics(base)
        restarts = sum(
            fams["train_restarts_total"]["samples"].values())
        fleet_goodput = world().get("goodput") or {}
        committed = sorted(
            int(d) for d in os.listdir(ckpt_dir)
            if d.isdigit() and os.path.exists(
                os.path.join(ckpt_dir, d, "COMMITTED")))
        uncommitted = sorted(
            int(d) for d in os.listdir(ckpt_dir)
            if d.isdigit() and not os.path.exists(
                os.path.join(ckpt_dir, d, "COMMITTED")))
        return {
            "results": results,
            "losses": losses,
            "restarts": restarts,
            "killed_at": killed_at,
            "victim": victim_rid if kill else None,
            "committed_steps": committed,
            "uncommitted_steps": uncommitted,
            "fleet_goodput": fleet_goodput,
            "burn_peak": burn_peak,
            "burn_final": burn_final,
        }
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        coord.terminate()
        try:
            coord.wait(timeout=10)
        except subprocess.TimeoutExpired:
            coord.kill()
            coord.wait()
        coord_log.close()
        for f in worker_logs.values():
            f.close()


def run_rollout(clients: int, requests: int, max_new: int, *,
                replicas: int = 4, block_size: int = 8,
                bake_s: float = 4.0, defect_delay_s: float = 3.0,
                retries: int = 6) -> dict:
    """The live-deployment run (ISSUE 18). N replicas on seed-0
    weights behind a rollout-armed router; client threads flood the
    router CONTINUOUSLY while the harness publishes version seed-1 and
    the RolloutManager canaries, bakes, and rolls it across the whole
    fleet — so every phase (canary drain+reload, bake, each promote
    drain+reload) lands under live traffic. Token safety is judged
    retroactively: every flood response must byte-match the seed-0
    oracle or the seed-1 oracle (both taken directly from replica-0,
    before publish and after promote) — version-aware migration means
    there is no third, mixed-weights outcome. Then the bad arm:
    seed-2-bad ships a planted TTFT defect wider than the canary SLO,
    and must be auto-rolled-back by the burn judge with the fleet
    healed to seed-1, every phase conserved in the ledger. The run
    raises unless client failures and token mismatches are both zero
    and both arms reach their terminal verdicts."""
    import tempfile
    import threading

    router_port = free_port()
    rep_ports = [free_port() for _ in range(replicas)]
    router_base = f"http://127.0.0.1:{router_port}"
    log = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".log", prefix="kftpu-rolloutload-",
        delete=False)
    procs: list[subprocess.Popen] = []
    try:
        procs.append(subprocess.Popen(
            [sys.executable, "-c",
             ROLLOUT_ROUTER_CODE.format(
                 repo=REPO, port=router_port, block_size=block_size,
                 retries=retries, interval_s=0.25, bake_s=bake_s,
                 min_probes=3, ttft_slo_s=2.0)],
            stdout=log, stderr=subprocess.STDOUT))
        for idx, port in enumerate(rep_ports):
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 ROLLOUT_REPLICA_CODE.format(
                     repo=REPO, port=port, idx=idx,
                     router=router_base, block_size=block_size)],
                stdout=log, stderr=subprocess.STDOUT))

        def tail_fail(msg: str) -> RuntimeError:
            log.flush()
            with open(log.name) as f:
                tail = "\n".join(f.read().splitlines()[-30:])
            rcs = [p.poll() for p in procs]
            return RuntimeError(f"{msg} (rcs={rcs}):\n{tail}")

        deadline = time.monotonic() + 240
        ready = False
        while time.monotonic() < deadline:
            if any(p.poll() is not None for p in procs):
                break
            try:
                counts = _get_json(
                    f"{router_base}/fleet/replicas")["counts"]
                if counts["ready"] >= replicas:
                    ready = True
                    break
            except Exception:
                pass
            time.sleep(0.5)
        if not ready:
            raise tail_fail("rollout fleet never became ready")

        def post(base: str, body: dict, timeout: float = 120.0) -> dict:
            return _post_json(f"{base}/v1/models/tiny:generate", body,
                              timeout=timeout)

        # warm every replica's batch shapes before anything is timed;
        # token 255 keeps the warm prompt's radix line disjoint from
        # the measured prompts (3..10) and the canary probe ([1])
        prompt_len = 3 * block_size
        warm_prompt = [255, 99] + [5 + t % 200
                                   for t in range(prompt_len - 2)]

        def warm(i: int) -> None:
            base = f"http://127.0.0.1:{rep_ports[i % replicas]}"
            post(base, {"tokens": [warm_prompt], "max_new": max_new})

        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            for _ in range(3):
                list(ex.map(warm, range(max(clients, replicas))))

        # both oracles come DIRECTLY from replica-0 — seed-0 now,
        # seed-1 after the promote finishes (same process, new weights)
        k = max(1, requests // 6)
        prompts = [[3 + j % 250, 100] + [7 + (j + t) % 200
                                         for t in range(prompt_len - 2)]
                   for j in range(k)]
        rep0 = f"http://127.0.0.1:{rep_ports[0]}"
        oracle0 = [post(rep0, {"tokens": [pr], "max_new": max_new})
                   ["tokens"][0] for pr in prompts]

        # continuous flood: client threads hammer the router until the
        # roll completes, so canary/bake/promote ALL land under load
        stop_flood = threading.Event()
        lock = threading.Lock()
        responses: list[tuple[int, list]] = []
        failures: list[str] = []
        latencies: list[float] = []

        def flooder(worker: int) -> None:
            i = 0
            while not stop_flood.is_set():
                j = (worker * 7919 + i * 31) % k
                body = {"tokens": [prompts[j]], "max_new": max_new}
                t0 = time.perf_counter()
                try:
                    if i % 3 == 0:
                        got = _sse_generate(router_base, body)
                    else:
                        got = post(router_base, body)["tokens"][0]
                except Exception as e:  # noqa: BLE001 — tallied below
                    with lock:
                        failures.append(
                            f"worker {worker} req {i}: "
                            f"{type(e).__name__}: {e}")
                    i += 1
                    continue
                with lock:
                    responses.append((j, [int(t) for t in got]))
                    latencies.append(time.perf_counter() - t0)
                i += 1

        threads = [threading.Thread(target=flooder, args=(w,))
                   for w in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(0.5)  # flood established before the publish lands

        pub = _post_json(f"{router_base}/fleet/versions",
                         {"version": "seed-1", "model": "tiny",
                          "source": {"seed": 1}})
        if not pub.get("published"):
            raise AssertionError(f"seed-1 publish refused: {pub}")

        def phase_of(version: str) -> str | None:
            book = _get_json(f"{router_base}/fleet/rollouts")
            return (book["rollouts"].get(version) or {}).get("phase")

        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            ph = phase_of("seed-1")
            if ph == "completed":
                break
            if ph in ("rolled_back",):
                raise tail_fail("healthy seed-1 rollout rolled back")
            time.sleep(0.5)
        else:
            raise tail_fail(
                f"seed-1 never completed (phase={phase_of('seed-1')})")
        promote_wall = time.perf_counter() - t0

        # one more beat of post-promote traffic, then stop the flood
        time.sleep(1.0)
        stop_flood.set()
        for t in threads:
            t.join(timeout=120)
        wall = time.perf_counter() - t0

        reps = _get_json(f"{router_base}/fleet/replicas")["replicas"]
        wrong = {r["id"]: r["version"] for r in reps
                 if r["version"] != "seed-1"}
        if wrong:
            raise AssertionError(
                f"promote completed but replicas still off-version: "
                f"{wrong}")

        oracle1 = [post(rep0, {"tokens": [pr], "max_new": max_new})
                   ["tokens"][0] for pr in prompts]
        for j in range(k):
            if oracle0[j] == oracle1[j]:
                raise AssertionError(
                    f"prompt {j}: seed-0 and seed-1 oracles agree — "
                    "the weight swap is not observable")

        served_old = served_new = 0
        mismatches: list[str] = []
        for j, got in responses:
            if got == [int(t) for t in oracle0[j]]:
                served_old += 1
            elif got == [int(t) for t in oracle1[j]]:
                served_new += 1
            else:
                mismatches.append(f"prompt {j}: {got}")
        if failures:
            raise AssertionError(
                f"{len(failures)} client-visible failures during the "
                f"roll: {failures[:5]}")
        if mismatches:
            raise AssertionError(
                f"{len(mismatches)} responses match NEITHER oracle "
                f"(mixed-weight generation?): {mismatches[:3]}")
        if len(responses) < requests:
            raise AssertionError(
                f"flood too thin: {len(responses)} < {requests} "
                "responses across the roll")
        if not served_old or not served_new:
            raise AssertionError(
                f"roll was not observed mid-flood (served_old="
                f"{served_old} served_new={served_new})")

        book = _get_json(f"{router_base}/fleet/rollouts")
        hist = book["rollouts"]["seed-1"]["history"]
        want = ["published", "canarying", "baking", "promoting",
                "completed"]
        if hist != want:
            raise AssertionError(f"seed-1 history {hist} != {want}")
        if not book["conserved"]:
            raise AssertionError(f"rollout ledger not conserved: {book}")
        if book["manager"]["current"] != "seed-1":
            raise AssertionError(
                f"fleet current is {book['manager']['current']!r}, "
                "not seed-1")
        canary_good = next(
            (r["evidence"].get("canary") for r in book["records"]
             if r["version"] == "seed-1" and r["phase"] == "canarying"),
            None)

        # ---- bad arm: planted TTFT defect must burn the canary SLO
        # and auto-rollback, healing the fleet to seed-1 ----
        pub = _post_json(
            f"{router_base}/fleet/versions",
            {"version": "seed-2-bad", "model": "tiny",
             "source": {"seed": 2,
                        "defect": {"ttft_delay_s": defect_delay_s}}})
        if not pub.get("published"):
            raise AssertionError(f"seed-2-bad publish refused: {pub}")
        t_bad = time.perf_counter()
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            ph = phase_of("seed-2-bad")
            if ph == "rolled_back":
                break
            if ph == "completed":
                raise tail_fail("defective seed-2-bad was PROMOTED")
            time.sleep(0.5)
        else:
            raise tail_fail(
                "seed-2-bad never rolled back "
                f"(phase={phase_of('seed-2-bad')})")
        rollback_wall = time.perf_counter() - t_bad

        book = _get_json(f"{router_base}/fleet/rollouts")
        hist = book["rollouts"]["seed-2-bad"]["history"]
        want = ["published", "canarying", "baking", "rolled_back"]
        if hist != want:
            raise AssertionError(f"seed-2-bad history {hist} != {want}")
        if not book["conserved"]:
            raise AssertionError(f"rollout ledger not conserved: {book}")
        if book["manager"]["current"] != "seed-1":
            raise AssertionError(
                "rollback left current at "
                f"{book['manager']['current']!r}")
        if book["manager"]["active"] is not None:
            raise AssertionError(
                f"rollback left a live rollout: {book['manager']}")
        if book["active"] != 0:
            raise AssertionError(
                f"ledger still counts {book['active']} active rollouts")
        canary_bad = next(
            (r["evidence"].get("canary") for r in book["records"]
             if r["version"] == "seed-2-bad"
             and r["phase"] == "canarying"), None)

        reps = _get_json(f"{router_base}/fleet/replicas")["replicas"]
        wrong = {r["id"]: r["version"] for r in reps
                 if r["version"] != "seed-1"}
        if wrong:
            raise AssertionError(
                f"rollback left replicas off seed-1: {wrong}")

        # the healed ex-canary must serve seed-1 tokens with the
        # defect CLEARED — fast first token, oracle-exact output
        heal_base = router_base
        if canary_bad is not None:
            for idx, port in enumerate(rep_ports):
                if canary_bad == f"replica-{idx}":
                    heal_base = f"http://127.0.0.1:{port}"
        t_h = time.perf_counter()
        healed = post(heal_base, {"tokens": [prompts[0]],
                                  "max_new": max_new})["tokens"][0]
        heal_lat = time.perf_counter() - t_h
        if [int(t) for t in healed] != [int(t) for t in oracle1[0]]:
            raise AssertionError(
                f"healed canary serves wrong tokens: {healed} != "
                f"{oracle1[0]}")
        if heal_lat >= defect_delay_s:
            raise AssertionError(
                f"healed canary still defect-slow ({heal_lat:.2f}s >= "
                f"{defect_delay_s}s)")

        latencies.sort()
        q = statistics.quantiles(latencies, n=20)
        return {
            "metric": "serving_rollout",
            "mode": "rollout",
            "fleet_replicas": replicas,
            "clients": clients,
            "requests": len(responses),
            "max_new": max_new,
            "kv_block_size": block_size,
            "bake_s": bake_s,
            "requests_per_sec": round(len(responses) / wall, 2),
            "tokens_per_sec": round(len(responses) * max_new / wall, 1),
            "p50_s": round(q[9], 3),
            "p95_s": round(q[18], 3),
            "wall_s": round(wall, 2),
            "promote_wall_s": round(promote_wall, 2),
            "rollback_wall_s": round(rollback_wall, 2),
            "served_old_version": served_old,
            "served_new_version": served_new,
            "canary_good": canary_good,
            "canary_bad": canary_bad,
            "good_verdict": "completed",
            "bad_verdict": "rolled_back",
            "ledger_conserved": True,
            "transitions": book["transitions"],
            "client_failures": 0,
            "token_mismatches": 0,
        }
    finally:
        log.close()
        os.unlink(log.name)
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def run_train_chaos(*, replicas: int = 2, steps: int = 8,
                    save_every: int = 2,
                    slow_save_s: float = 1.5,
                    slo_short_s: float = 6.0) -> dict:
    """The elastic-training fault-injection run. Three gangs on fresh
    checkpoint dirs: a fault-free single-replica oracle for the loss
    curve, then a mid-step SIGKILL of a non-chief worker, then a
    mid-checkpoint-save SIGKILL of the chief. Each chaos gang must
    auto-resume at replicas-1 from the last COMMITTED checkpoint, run
    to completion with zero corrupt restores, and reproduce the
    oracle's loss curve step-for-step (replicated execution makes the
    global batch a pure function of (seed, step), so parity is a hard
    assertion, not a similarity score)."""
    import tempfile

    if replicas < 2:
        raise ValueError("train chaos needs >= 2 replicas "
                         "(one to kill, one to survive)")
    root = tempfile.mkdtemp(prefix="kftpu-trainchaos-")
    t0 = time.perf_counter()
    try:
        oracle = _train_arm(
            os.path.join(root, "oracle"), replicas=1, steps=steps,
            save_every=save_every, kill=None, slow_save_s=0.0,
            slo_short_s=slo_short_s)
        arms = {"oracle": oracle}
        scenarios = {}
        for kill in ("mid-step", "mid-save"):
            arm = _train_arm(
                os.path.join(root, kill), replicas=replicas,
                steps=steps, save_every=save_every, kill=kill,
                slow_save_s=slow_save_s, slo_short_s=slo_short_s)
            arms[kill] = arm
            for rid, res in arm["results"].items():
                if res["final_step"] != steps:
                    raise AssertionError(
                        f"{kill}: survivor {rid} stopped at step "
                        f"{res['final_step']} != {steps}")
                if res["corrupt_restores"] != 0:
                    raise AssertionError(
                        f"{kill}: survivor {rid} hit "
                        f"{res['corrupt_restores']} corrupt restores")
                if res["world_size"] != replicas - 1:
                    raise AssertionError(
                        f"{kill}: survivor {rid} finished at world "
                        f"{res['world_size']} != {replicas - 1}")
                if res["restores"] < 2:
                    raise AssertionError(
                        f"{kill}: survivor {rid} never restarted "
                        f"(restores={res['restores']})")
            if arm["restarts"] < 1:
                raise AssertionError(
                    f"{kill}: coordinator counted no restarts")
            missing = [s for s in range(1, steps + 1)
                       if s not in arm["losses"]]
            if missing:
                raise AssertionError(
                    f"{kill}: loss curve has holes at steps {missing}")
            div = max(abs(arm["losses"][s] - oracle["losses"][s])
                      for s in range(1, steps + 1))
            if div > 5e-4:
                raise AssertionError(
                    f"{kill}: loss curve diverged from the fault-free "
                    f"oracle by {div} (> 5e-4)")
            # Goodput forensics. Only the mid-save arm is GUARANTEED
            # replay seconds: its survivor is the non-chief, rewound to
            # the last COMMITTED step well below its own high-water
            # mark. The mid-step arm's survivor IS the chief, which
            # restores at its own latest save — at most one step back,
            # and that step re-compiles on the rebuilt trainer, so its
            # wall books to `compile`, not `replay`.
            gp = arm["fleet_goodput"].get("seconds", {})
            if kill == "mid-save" and not gp.get("replay", 0.0) > 0.0:
                raise AssertionError(
                    f"{kill}: restart re-ran steps but the fleet ledger "
                    f"booked no replay seconds: {gp}")
            # BOUNDED replay in every arm: less than the productive
            # time, or the checkpoint cadence is broken and restarts
            # cost more than the run itself.
            if gp.get("replay", 0.0) >= gp.get("productive", 0.0):
                raise AssertionError(
                    f"{kill}: replay burn unbounded — "
                    f"{gp['replay']:.2f}s replay >= "
                    f"{gp.get('productive', 0.0):.2f}s productive")
            # Burn-rate plane: the short-window train_goodput gauge
            # must cross the 1.0 alert line while the gang replays, the
            # restart hold must page, and both must clear one short
            # window after the fleet resumes and finishes.
            for slo in ("train_goodput", "train_restart_burn"):
                if arm["burn_peak"][slo] <= 1.0:
                    raise AssertionError(
                        f"{kill}: slo_burn_rate{{slo={slo}}} never "
                        f"crossed the alert line "
                        f"(peak {arm['burn_peak'][slo]:.2f})")
                if arm["burn_final"][slo] >= 1.0:
                    raise AssertionError(
                        f"{kill}: slo_burn_rate{{slo={slo}}} did not "
                        f"recover after resume "
                        f"(still {arm['burn_final'][slo]:.2f})")
            scenarios[kill.replace("-", "_")] = {
                "victim": arm["victim"],
                "killed_at_steps": arm["killed_at"],
                "survivor_world_size": replicas - 1,
                "restarts": arm["restarts"],
                "restores": {rid: r["restores"]
                             for rid, r in arm["results"].items()},
                "committed_steps": arm["committed_steps"],
                "uncommitted_steps": arm["uncommitted_steps"],
                "max_loss_divergence": div,
                "goodput": arm["fleet_goodput"],
                "burn_peak_short": arm["burn_peak"],
                "burn_final_short": arm["burn_final"],
            }
        # Goodput summary: where did every fleet worker-second go, per
        # arm? (fleet ledger, cumulative across worker incarnations)
        print("goodput summary (fleet worker-seconds per arm):",
              file=sys.stderr)
        hdr = (f"  {'arm':<10} {'prod':>8} {'replay':>8} {'ckpt':>8} "
               f"{'compile':>8} {'stall':>8} {'idle':>8} {'frac':>6}")
        print(hdr, file=sys.stderr)
        for name, arm in arms.items():
            gp = arm["fleet_goodput"]
            s = gp.get("seconds", {})
            print(f"  {name:<10}"
                  f" {s.get('productive', 0.0):>8.2f}"
                  f" {s.get('replay', 0.0):>8.2f}"
                  f" {s.get('checkpoint_save', 0.0) + s.get('checkpoint_restore', 0.0):>8.2f}"
                  f" {s.get('compile', 0.0):>8.2f}"
                  f" {s.get('stall', 0.0):>8.2f}"
                  f" {s.get('idle', 0.0):>8.2f}"
                  f" {gp.get('fraction', 0.0):>6.3f}",
                  file=sys.stderr)
        oracle_gp = oracle["fleet_goodput"]
        wall = time.perf_counter() - t0
        return {
            "metric": "train_chaos",
            "mode": "train-chaos",
            "replicas": replicas,
            "steps": steps,
            "save_every": save_every,
            "slow_save_s": slow_save_s,
            "oracle_final_loss": oracle["losses"][steps],
            "oracle_goodput_fraction": round(
                oracle_gp.get("fraction", 0.0), 4),
            "scenarios": scenarios,
            "corrupt_restores": 0,
            "wall_s": round(wall, 2),
        }
    finally:
        import shutil
        shutil.rmtree(root, ignore_errors=True)


def _tenant_arm(qos: bool, *, bulk_clients: int, live_requests: int,
                bulk_prompt_len: int, prefill_chunk_tokens: int,
                bulk_max_new: int, live_max_new: int,
                max_batch: int, slo_ttft_s: float) -> dict:
    """One arm of the noisy-neighbor A/B: flood with batch-class work,
    stream interactive requests through the backlog, measure TTFT.
    Also scrapes the server's own view — the interactive burn-rate
    gauge and the TTFT histogram — so the A/B doubles as an SLO-plane
    check (client-measured and server-exposed latency must agree)."""
    import tempfile
    import threading

    port = free_port()
    base = f"http://127.0.0.1:{port}"
    log = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".log", prefix="kftpu-tenload-", delete=False)
    proc = subprocess.Popen(
        [sys.executable, "-c",
         TENANT_SERVER_CODE.format(repo=REPO, port=port, qos=qos,
                                   max_batch=max_batch,
                                   chunk=prefill_chunk_tokens,
                                   slo_ttft_s=slo_ttft_s)],
        stdout=log, stderr=subprocess.STDOUT)

    def post(body: dict, tenant: str, timeout: float = 180.0) -> dict:
        req = urllib.request.Request(
            f"{base}/v1/models/tiny:generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json",
                     "X-Tenant": tenant})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    try:
        deadline = time.monotonic() + 180
        ready = False
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                break
            try:
                urllib.request.urlopen(f"{base}/v1/models", timeout=2)
                ready = True
                break
            except Exception:
                time.sleep(0.5)
        if not ready:
            log.flush()
            with open(log.name) as f:
                tail = "\n".join(f.read().splitlines()[-20:])
            raise RuntimeError(
                f"server never came up (rc={proc.returncode}):\n{tail}")
        def live_ttft(i: int) -> float:
            """One streamed interactive request; TTFT = first SSE
            token event on the wire (the serving_ttft definition)."""
            req = urllib.request.Request(
                f"{base}/v1/models/tiny:generate",
                data=json.dumps({"tokens": [[9 + i % 5, 8, 7, 6]],
                                 "max_new": live_max_new,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Tenant": "live"})
            t0 = time.perf_counter()
            ttft = None
            with urllib.request.urlopen(req, timeout=180) as r:
                for line in r:
                    if line.startswith(b"data:") and ttft is None:
                        ttft = time.perf_counter() - t0
                    # drain to the terminal event so the slot retires
            assert ttft is not None
            return ttft

        # warm the admission-group shapes both workloads will hit
        # (bulk-sized and live-sized), concurrently like run() does.
        # The live warmup STREAMS: the one-shot path observes TTFT at
        # generation end, and that inflated sample would pollute the
        # interactive SLO set both arms' burn gauges are asserted on.
        def bulk_prompt(i: int) -> list[int]:
            """Distinct per call: identical prompts would collapse
            into radix prefix hits after the first retirement and the
            flood would stop exercising prefill at all."""
            return [5 + (i * 31 + j * 7) % 480
                    for j in range(bulk_prompt_len)]

        with concurrent.futures.ThreadPoolExecutor(bulk_clients) as ex:
            for r in range(2):
                list(ex.map(
                    lambda i: post(
                        {"tokens": [bulk_prompt(-1 - i - r * 64)],
                         "max_new": bulk_max_new}, "bulk"),
                    range(bulk_clients)))
        live_ttft(0)

        stop = threading.Event()
        bulk_done = [0]
        bulk_429 = [0]
        lock = threading.Lock()

        def bulk_loop(tid: int) -> None:
            # the noisy neighbor: keep a long generation in flight per
            # thread until the interactive phase is over
            i = 0
            while not stop.is_set():
                i += 1
                try:
                    post({"tokens": [
                              bulk_prompt(i * bulk_clients + tid)],
                          "max_new": bulk_max_new}, "bulk")
                    with lock:
                        bulk_done[0] += 1
                except urllib.error.HTTPError as e:
                    if e.code != 429:
                        raise
                    with lock:
                        bulk_429[0] += 1
                    e.close()
                    time.sleep(0.05)

        threads = [threading.Thread(target=bulk_loop, args=(t,),
                                    daemon=True)
                   for t in range(bulk_clients)]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(1.5)  # let the backlog build before measuring

        ttfts = []
        for i in range(live_requests):
            ttfts.append(live_ttft(i))
            time.sleep(0.2)
        # scrape while the interactive observations are still inside
        # the burn engine's short (60 s) window — before waiting out
        # the bulk threads' in-flight generations
        families = _scrape_metrics(base)
        stop.set()
        for t in threads:
            t.join(timeout=180)
        wall = time.perf_counter() - t_start

        m = _get_json(f"{base}/v1/models")["models"][0]
        tstats = m.get("tenants", {})
        ttfts.sort()
        q = statistics.quantiles(ttfts, n=20) if len(ttfts) >= 2 \
            else list(ttfts) * 19
        burn = _burn_rate(families, "serving_ttft_interactive", "short")
        lo, hi = _hist_quantile_bracket(
            families, "serving_time_to_first_token_seconds", 0.95,
            model="tiny", tenant="live")
        # client p95 must land in (a generously widened) server p95
        # bucket bracket: same requests, measured from both ends of the
        # wire. Catches mislabeled observations and unit slips, not
        # statistical noise — hence the wide slack.
        if not (lo * 0.5 - 1e-3 <= q[18]
                and (hi == float("inf") or q[18] <= hi * 3 + 0.05)):
            raise AssertionError(
                f"client p95 TTFT {q[18]:.3f}s disagrees with the "
                f"server-side histogram p95 bucket ({lo:g}, {hi:g}] "
                f"(qos={qos})")
        return {
            "qos": qos,
            "ttft_p50_s": round(q[9], 3),
            "ttft_p95_s": round(q[18], 3),
            "slo_burn_interactive_short": round(burn, 2),
            "ttft_server_p95_bracket_s": [
                lo, None if hi == float("inf") else hi],
            "bulk_completed": bulk_done[0],
            "bulk_throttled_429": bulk_429[0],
            "bulk_tokens_per_sec": round(
                bulk_done[0] * bulk_max_new / wall, 1),
            "preemptions": tstats.get("bulk", {}).get("preempted", 0),
        }
    finally:
        log.close()
        os.unlink(log.name)
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def run_tenants(*, bulk_clients: int = 8, live_requests: int = 8,
                bulk_max_new: int = 64, live_max_new: int = 8,
                bulk_prompt_len: int = 4, prefill_chunk_tokens: int = 0,
                max_batch: int = 4, slo_ttft_s: float = 0.03,
                slo_alert_burn: float = 6.0) -> dict:
    """Noisy-neighbor A/B: identical flood + interactive workloads,
    once with the QoS scheduler on and once tenant-blind. The headline
    number is the interactive TTFT ratio — how much of the batch
    tenant's backlog the interactive tenant no longer waits behind.

    The SLO plane rides the same A/B: both arms run the interactive
    TTFT objective at `slo_ttft_s` (set between the two arms' expected
    p95s so the threshold discriminates policy, not machine speed),
    and the run asserts the server's own `slo_burn_rate` gauge tells
    the story — above the fast-burn alert line (`slo_alert_burn`,
    default 6x budget: the conventional page threshold) when QoS is
    off, below it when QoS is on."""
    on = _tenant_arm(True, bulk_clients=bulk_clients,
                     live_requests=live_requests,
                     bulk_max_new=bulk_max_new,
                     live_max_new=live_max_new,
                     bulk_prompt_len=bulk_prompt_len,
                     prefill_chunk_tokens=prefill_chunk_tokens,
                     max_batch=max_batch,
                     slo_ttft_s=slo_ttft_s)
    off = _tenant_arm(False, bulk_clients=bulk_clients,
                      live_requests=live_requests,
                      bulk_max_new=bulk_max_new,
                      live_max_new=live_max_new,
                      bulk_prompt_len=bulk_prompt_len,
                      prefill_chunk_tokens=prefill_chunk_tokens,
                      max_batch=max_batch,
                      slo_ttft_s=slo_ttft_s)
    burn_on = on["slo_burn_interactive_short"]
    burn_off = off["slo_burn_interactive_short"]
    if burn_off <= burn_on:
        raise AssertionError(
            f"interactive burn rate did not rise when QoS was turned "
            f"off: qos_on={burn_on} qos_off={burn_off} "
            f"(slo_ttft_s={slo_ttft_s})")
    if burn_off < slo_alert_burn:
        raise AssertionError(
            f"qos_off burn {burn_off} below the alert line "
            f"{slo_alert_burn} — the flood is not violating the "
            f"{slo_ttft_s}s interactive TTFT objective; lower "
            f"--slo-ttft-s or raise the bulk load")
    if burn_on >= slo_alert_burn:
        raise AssertionError(
            f"qos_on burn {burn_on} at/above the alert line "
            f"{slo_alert_burn} — the scheduler is not protecting the "
            f"interactive class at the {slo_ttft_s}s objective")
    return {
        "metric": "serving_tenant_qos",
        "mode": "tenants",
        "bulk_clients": bulk_clients,
        "live_requests": live_requests,
        "bulk_max_new": bulk_max_new,
        "live_max_new": live_max_new,
        "bulk_prompt_len": bulk_prompt_len,
        "prefill_chunk_tokens": prefill_chunk_tokens,
        "max_batch": max_batch,
        "slo_ttft_s": slo_ttft_s,
        "slo_alert_burn": slo_alert_burn,
        "qos_on": on,
        "qos_off": off,
        "ttft_p95_improvement": (
            round(off["ttft_p95_s"] / on["ttft_p95_s"], 2)
            if on["ttft_p95_s"] else 0.0),
    }


def _load_scenario(spec: str, seed: int):
    """`gen:<shape>` generates with the explicit seed; anything else
    is a trace file path."""
    from kubeflow_tpu import scenarios
    if spec.startswith("gen:"):
        return scenarios.generate(spec[len("gen:"):], seed)
    return scenarios.read_trace(spec)


def run_scenario(scenario: str, *, seed: int = 0, speed: float = 1.0,
                 target: str = "single", replicas: int = 2,
                 block_size: int = 8, max_batch: int = 8,
                 fidelity_pct: float = 0.0) -> dict:
    """Replay a scenario open-loop against a live stack and judge the
    trace's `expect` block. `target="single"` is one continuous
    server; `target="fleet"` is N replicas behind the fleet router —
    the replay code is identical, which is the point: one trace, any
    topology.

    With `fidelity_pct > 0` (single target only — timelines live on
    replicas, not the router), the run also closes the record/replay
    loop: capture the just-replayed run off the server's timeline
    store by the replayer's own request ids, replay the RECORDING on
    a fresh identical server, and fail unless recorded-replay p95
    TTFT is within fidelity_pct percent of the original's."""
    import tempfile

    from kubeflow_tpu import scenarios

    trace = _load_scenario(scenario, seed)
    worst = max(r.prompt_tokens + r.max_new for r in trace.requests)
    if worst > 120:
        # the harness engine runs max_len=128; fail before boot, by
        # name, not after 180s of mysterious 4xx
        raise ValueError(
            f"scenario {trace.name!r} needs prompt+max_new <= 120 "
            f"for the loadtest's tiny engine (worst request asks "
            f"{worst}); regenerate with smaller params")

    def wait_ready(base: str, procs: list, log) -> None:
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if any(p.poll() is not None for p in procs):
                break
            try:
                if target == "fleet":
                    counts = _get_json(f"{base}/fleet/replicas")["counts"]
                    if counts["ready"] >= replicas:
                        return
                else:
                    urllib.request.urlopen(f"{base}/v1/models",
                                           timeout=2)
                    return
            except Exception:
                pass
            time.sleep(0.5)
        log.flush()
        with open(log.name) as f:
            tail = "\n".join(f.read().splitlines()[-30:])
        rcs = [p.poll() for p in procs]
        raise RuntimeError(
            f"scenario target never became ready (rcs={rcs}):\n{tail}")

    def warm(base: str, tr) -> None:
        # compile every prompt shape the trace will touch BEFORE the
        # clock matters — the fidelity arm compares p95 TTFT across
        # two servers, so a first-touch XLA compile landing inside one
        # arm's timed window and not the other's would swamp the
        # comparison with compiler noise
        lengths = sorted({r.prompt_tokens for r in tr.requests})

        def one(n: int) -> None:
            req = urllib.request.Request(
                f"{base}/v1/models/tiny:generate",
                data=json.dumps({"tokens": [[5 + i % 480
                                             for i in range(n)]],
                                 "max_new": 2}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                r.read()

        with concurrent.futures.ThreadPoolExecutor(8) as ex:
            list(ex.map(one, lengths))
            # concurrent bursts compile the coalesced admission-group
            # shapes (same idiom as run()'s warmup)
            for _ in range(3):
                list(ex.map(one, [4] * 8))

    def replay_against(base: str, tr, run_speed: float) -> dict:
        tgt = scenarios.HttpTarget(base, model="tiny", seed=tr.seed,
                                   speed=run_speed)
        # one worker per request: under a saturating flood the
        # backlog's open connections must never exhaust the pool, or
        # dispatch blocks and the replay silently goes closed-loop
        records = scenarios.replay(tr, tgt, speed=run_speed,
                                   max_workers=len(tr.requests) + 8)
        return scenarios.summarize(tr, records, speed=run_speed)

    def boot():
        log = tempfile.NamedTemporaryFile(
            mode="w+", suffix=".log", prefix="kftpu-scenario-",
            delete=False)
        procs: list[subprocess.Popen] = []
        if target == "fleet":
            router_port = free_port()
            base = f"http://127.0.0.1:{router_port}"
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 ROUTER_CODE.format(repo=REPO, port=router_port,
                                    block_size=block_size,
                                    policy="affinity",
                                    hedge_after_s=10.0,
                                    peer_hints=True)],
                stdout=log, stderr=subprocess.STDOUT))
            for idx in range(replicas):
                port = free_port()
                procs.append(subprocess.Popen(
                    [sys.executable, "-c",
                     FLEET_REPLICA_CODE.format(
                         repo=REPO, port=port, idx=idx, router=base,
                         block_size=block_size)],
                    stdout=log, stderr=subprocess.STDOUT))
        else:
            port = free_port()
            base = f"http://127.0.0.1:{port}"
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 SERVER_CODE.format(repo=REPO, port=port, window_ms=5,
                                    max_batch=max_batch,
                                    continuous=True,
                                    pipeline_depth=None)],
                stdout=log, stderr=subprocess.STDOUT))
        return procs, log, base

    def teardown(procs: list, log) -> None:
        log.close()
        os.unlink(log.name)
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    procs, log, base = boot()
    try:
        wait_ready(base, procs, log)
        warm(base, trace)
        result = replay_against(base, trace, speed)
        expect_failures = scenarios.check_expect(trace.expect, result)

        out = {
            "metric": "scenario_replay",
            "mode": "scenario",
            "scenario": trace.name,
            "generator": trace.generator or "file",
            "target": target,
            **({"replicas": replicas} if target == "fleet" else {}),
            **result,
            "expect_failures": expect_failures,
        }

        if fidelity_pct > 0:
            import dataclasses

            # capture by the replayer's OWN request ids: warmup posts
            # share the store but must not pollute the recording
            recorded = scenarios.record_from_server(
                base, ids=[r.id for r in trace.requests],
                name=f"{trace.name}-recorded")
            out["recorded_requests"] = len(recorded.requests)
            if len(recorded.requests) != len(trace.requests):
                raise AssertionError(
                    f"recording lost requests: {len(trace.requests)} "
                    f"replayed, {len(recorded.requests)} captured")
            # PAIRED comparison: replay the original trace and the
            # recording SIMULTANEOUSLY, interleaved, against the same
            # warm engine. Sequential A-then-B comparisons on a shared
            # CPU box fold +-15% run-to-run service drift into the
            # metric; interleaving makes both arms ride the exact same
            # queue and the same service-rate fluctuations, so the
            # only thing that can separate their TTFT distributions is
            # the recording itself being unfaithful (lost requests,
            # shifted arrivals, wrong lengths). Ids are disambiguated
            # by arm prefix; the derived prompt contents therefore
            # differ per arm (same lengths), so no radix reuse crosses
            # the arms. Original offsets are divided by --speed (the
            # pace the original actually replayed at); recorded
            # offsets are already wall-time.
            def scale(r):
                return dataclasses.replace(
                    r, id="o!" + r.id, at=round(r.at / speed, 6),
                    abandon_at=(None if r.abandon_at is None
                                else round(r.abandon_at / speed, 6)))

            paired_reqs = ([scale(r) for r in trace.requests]
                           + [dataclasses.replace(r, id="r!" + r.id)
                              for r in recorded.requests])
            paired = scenarios.Trace(
                name=f"{trace.name}-paired", requests=paired_reqs,
                seed=trace.seed, generator="paired")
            tgt = scenarios.HttpTarget(base, model="tiny",
                                       seed=trace.seed)
            precs = scenarios.replay(
                paired, tgt, max_workers=len(paired_reqs) + 8)

            def arm_stats(prefix: str) -> dict:
                rs = [r for r in precs if r["id"].startswith(prefix)]
                ttfts = sorted(r["ttft_s"] for r in rs
                               if r["ttft_s"] is not None)
                return {
                    "ttft_p95_s": round(
                        ttfts[min(len(ttfts) - 1,
                                  int(0.95 * len(ttfts)))], 6)
                    if ttfts else None,
                    "client_failures": sum(1 for r in rs
                                           if not r["ok"]),
                    "abandoned": sum(1 for r in rs if r["abandoned"]),
                }

            orig_arm, rec_arm = arm_stats("o!"), arm_stats("r!")
            p95a, p95b = orig_arm["ttft_p95_s"], rec_arm["ttft_p95_s"]
            delta = (abs(p95b - p95a) / p95a
                     if p95a else float("inf"))
            out["fidelity"] = {
                "orig_ttft_p95_s": p95a,
                "recorded_ttft_p95_s": p95b,
                "delta_frac": round(delta, 4),
                "max_frac": fidelity_pct / 100.0,
                "solo_ttft_p95_s": result["ttft_p95_s"],
                "orig_arm": orig_arm,
                "recorded_arm": rec_arm,
            }
            fails = orig_arm["client_failures"] \
                + rec_arm["client_failures"]
            if fails:
                raise AssertionError(
                    f"paired fidelity replay saw {fails} client "
                    f"failure(s)")
            if delta > fidelity_pct / 100.0:
                raise AssertionError(
                    f"record/replay fidelity: p95 TTFT moved "
                    f"{delta:.1%} (original arm {p95a}s -> recorded "
                    f"arm {p95b}s), budget {fidelity_pct}%")

        if expect_failures:
            raise AssertionError(
                f"scenario {trace.name!r} violated its expect block: "
                f"{expect_failures}")
        return out
    finally:
        teardown(procs, log)


def run(clients: int, requests: int, max_new: int,
        window_ms: int, mode: str = "window",
        spread: bool = False, pipeline_depth: int = 0) -> dict:
    import tempfile

    port = free_port()
    log = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".log", prefix="kftpu-srvload-", delete=False)
    proc = subprocess.Popen(
        [sys.executable, "-c",
         SERVER_CODE.format(repo=REPO, port=port, window_ms=window_ms,
                            max_batch=8,
                            continuous=(mode == "continuous"),
                            # unconditional: an invalid combination
                            # must hit create_serving_app's loud
                            # guard, not be silently dropped here
                            pipeline_depth=(pipeline_depth or None))],
        stdout=log, stderr=subprocess.STDOUT)
    base = f"http://127.0.0.1:{port}"

    def post(body: dict, timeout: float = 120.0) -> dict:
        req = urllib.request.Request(
            f"{base}/v1/models/tiny:generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    try:
        deadline = time.monotonic() + 120
        ready = False
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                break  # dead: fall through to the diagnostic raise
            try:
                urllib.request.urlopen(f"{base}/v1/models", timeout=2)
                ready = True
                break
            except Exception:
                time.sleep(0.5)
        if not ready:
            log.flush()
            with open(log.name) as f:
                tail = "\n".join(f.read().splitlines()[-20:])
            raise RuntimeError(
                f"server never came up (rc={proc.returncode}):\n{tail}")
        post({"tokens": [[1, 2, 3, 4]], "max_new": max_new})  # warm compile

        # Concurrent warmup bursts so the coalesced batch shapes the
        # batcher will use are compiled BEFORE timing starts; otherwise
        # p95 reports XLA compiles, not serving latency. Which
        # power-of-two row buckets form is arrival-order dependent, so
        # run THREE bursts — residual first-shape compiles are possible
        # but rare (documented flakiness, not a correctness issue).
        def warm(i: int) -> None:
            post({"tokens": [[1, 2, 3, 4]], "max_new": max_new})

        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            for _ in range(3):
                list(ex.map(warm, range(clients)))

        def batcher_stats() -> tuple[int, int, float]:
            with urllib.request.urlopen(f"{base}/v1/models",
                                        timeout=5) as r:
                m = json.loads(r.read())["models"][0]
            return (m.get("batched_requests", 0),
                    m.get("batcher_calls", 0),
                    m.get("occupancy", 0.0))

        req0, calls0, occ0 = batcher_stats()

        latencies: list[float] = []

        def ask(i: int) -> int:
            """Per-request max_new: uniform, or (--spread) cycling
            1/4x..1x so short and long requests coexist — the workload
            where continuous batching's early-exit matters (a window
            group runs every member to the group max)."""
            if not spread:
                return max_new
            return max(1, max_new * (1 + i % 4) // 4)

        def one(i: int) -> float:
            t0 = time.perf_counter()
            out = post({"tokens": [[1 + i % 7, 2, 3, 4]],
                        "max_new": ask(i)})
            assert len(out["tokens"][0]) == ask(i), out
            return time.perf_counter() - t0

        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            latencies = list(ex.map(one, range(requests)))
        wall = time.perf_counter() - t0
        total_tokens = sum(ask(i) for i in range(requests))
        # per-ask-size medians (spread mode): the fairness evidence —
        # a short ask coalesced into a window group pays the group's
        # longest member; continuous retires it at its own max_new
        by_ask: dict[int, list[float]] = {}
        for i, lat in enumerate(latencies):
            by_ask.setdefault(ask(i), []).append(lat)
        p50_by_ask = {k: round(statistics.median(v), 3)
                      for k, v in sorted(by_ask.items())}

        req1, calls1, occ1 = batcher_stats()
        d_req, d_calls = req1 - req0, calls1 - calls0
        latencies.sort()
        q = statistics.quantiles(latencies, n=20)
        out = {
            "metric": "serving_rest_throughput",
            "mode": mode,
            "clients": clients,
            "requests": requests,
            "max_new": max_new,
            "spread": spread,
            "batch_window_ms": window_ms,
            "requests_per_sec": round(requests / wall, 2),
            "tokens_per_sec": round(total_tokens / wall, 1),
            "p50_s": round(q[9], 3),
            "p95_s": round(q[18], 3),
            "wall_s": round(wall, 2),
        }
        if spread:
            out["p50_by_max_new"] = p50_by_ask
        if mode == "continuous":
            # occupancy over the TIMED window: /v1/models exposes the
            # cumulative ratio, so recover per-window tokens from
            # occ*calls at each end
            toks = occ1 * calls1 - occ0 * calls0
            out["occupancy"] = (round(toks / d_calls, 2)
                                if d_calls else 0.0)
            # record the depth the A/B ran at (0 = backend default) —
            # two depth runs must be distinguishable from their JSON
            out["pipeline_depth"] = pipeline_depth
        else:
            # coalescing evidence: >1 proves the batcher actually
            # merged concurrent requests during the timed window
            out["mean_effective_batch"] = (round(d_req / d_calls, 2)
                                           if d_calls else 0.0)
        return out
    finally:
        log.close()
        os.unlink(log.name)
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--clients", type=int, default=16)
    p.add_argument("--requests", type=int, default=96)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--batch-window-ms", type=int, default=5)
    p.add_argument("--mode",
                   choices=("window", "continuous", "fleet", "tenants",
                            "chaos", "train-chaos", "disagg",
                            "rollout", "scenario"),
                   default="window")
    p.add_argument("--scenario", default="",
                   help="scenario mode: a trace file path, or "
                        "gen:<shape> to generate one with --seed "
                        "(shapes: diurnal, flash-crowd, heavy-tail, "
                        "agent-swarm, abandon-retry, tenant-flood)")
    p.add_argument("--seed", type=int, default=0,
                   help="scenario mode: generator seed for "
                        "gen:<shape> — same seed, byte-identical "
                        "workload")
    p.add_argument("--scenario-speed", type=float, default=1.0,
                   help="scenario mode: time-scale for arrivals "
                        "(2.0 replays twice as fast)")
    p.add_argument("--scenario-target", choices=("single", "fleet"),
                   default="single",
                   help="scenario mode: one continuous server, or "
                        "--fleet-replicas behind the fleet router")
    p.add_argument("--scenario-max-batch", type=int, default=8,
                   help="scenario mode, single target: the server's "
                        "continuous-batching slot count — the "
                        "fidelity arm constrains it so the flood "
                        "queues structurally and p95 TTFT is set by "
                        "arrival order, not scheduler noise")
    p.add_argument("--scenario-fidelity-pct", type=float, default=0.0,
                   help="scenario mode: also record the replayed run "
                        "off the server's timeline store, replay the "
                        "recording on a fresh server, and fail if "
                        "recorded-replay p95 TTFT differs from the "
                        "original by more than this percent (0 = "
                        "skip the fidelity arm)")
    p.add_argument("--disagg-prefill", type=int, default=1,
                   help="disagg mode: prefill-pool replicas (arm A); "
                        "the symmetric arm gets prefill+decode mixed "
                        "replicas so total capacity matches")
    p.add_argument("--disagg-decode", type=int, default=3,
                   help="disagg mode: decode-pool replicas (arm A)")
    p.add_argument("--disagg-long-every", type=int, default=2,
                   help="disagg mode: every Nth request is a fresh "
                        "long prompt (prefill-heavy); the rest are "
                        "short repeated prompts (decode-heavy)")
    p.add_argument("--train-replicas", type=int, default=2,
                   help="train-chaos mode: trainer gang size (one "
                        "worker is SIGKILLed; the rest must finish at "
                        "N-1)")
    p.add_argument("--train-steps", type=int, default=8,
                   help="train-chaos mode: total optimizer steps per "
                        "gang")
    p.add_argument("--train-save-every", type=int, default=2,
                   help="train-chaos mode: checkpoint interval in "
                        "steps (the kill arms after 2 intervals so a "
                        "COMMITTED resume point exists)")
    p.add_argument("--train-slow-save-s", type=float, default=1.5,
                   help="train-chaos mode: post-dispatch sleep on the "
                        "chief's save path — widens the window where a "
                        "SIGKILL lands between the checkpoint write "
                        "and its COMMITTED marker")
    p.add_argument("--train-slo-short-s", type=float, default=6.0,
                   help="train-chaos mode: coordinator short SLO "
                        "window; the run waits one window after each "
                        "kill arm to assert the burn gauges clear")
    p.add_argument("--chaos-seed", type=int, default=1,
                   help="chaos mode: fault-plan seed (same seed, same "
                        "fault sequence)")
    p.add_argument("--chaos-drop-rate", type=float, default=0.08,
                   help="chaos mode: per-dispatch drop probability")
    p.add_argument("--chaos-delay-rate", type=float, default=0.08,
                   help="chaos mode: per-dispatch delay probability")
    p.add_argument("--chaos-duplicate-rate", type=float, default=0.05,
                   help="chaos mode: per-dispatch duplicate probability")
    p.add_argument("--chaos-blackhole-beats", type=int, default=14,
                   help="chaos mode: heartbeats to swallow from "
                        "replica-1 (>=13 walks the degraded path at "
                        "the default 6s staleness / 0.5s period)")
    p.add_argument("--closed-loop", action="store_true",
                   help="chaos mode: run the closed-loop recovery arm "
                        "instead of the fault-injection arm — SIGKILL "
                        "the whole fleet under flood and let the "
                        "router's burn-driven controller (scale_out "
                        "desired floor, polled by the harness as dumb "
                        "infra) be the ONLY recovery path; asserts "
                        "burn clears within one short window, zero "
                        "requests lost, and the fired decision books "
                        "`recovered` in /fleet/decisions")
    p.add_argument("--tenant-bulk-clients", type=int, default=8,
                   help="tenants mode: concurrent batch-class flooder "
                        "threads (the noisy neighbor); must exceed the "
                        "server's max_batch or nothing ever queues and "
                        "there is no backlog to measure against")
    p.add_argument("--tenant-bulk-prompt", type=int, default=4,
                   help="tenants mode: batch-class prompt length in "
                        "tokens — a long prompt stalls decode for "
                        "a slice at a time; --prefill-chunk-tokens "
                        "bounds the slice")
    p.add_argument("--prefill-chunk-tokens", type=int, default=0,
                   help="tenants mode: chunked-prefill token budget "
                        "for BOTH arms' servers (0 = the server's "
                        "default)")
    p.add_argument("--tenant-live-requests", type=int, default=8,
                   help="tenants mode: sequential interactive streams "
                        "measured for TTFT")
    p.add_argument("--slo-ttft-s", type=float, default=0.03,
                   help="tenants mode: interactive TTFT objective fed "
                        "to both arms' SLO engines; set between the "
                        "arms' expected p95s so the burn-rate gauge "
                        "discriminates scheduler policy")
    p.add_argument("--slo-alert-burn", type=float, default=6.0,
                   help="tenants mode: fast-burn alert line the "
                        "qos-off arm must exceed and the qos-on arm "
                        "must stay below")
    p.add_argument("--fleet-replicas", type=int, default=None,
                   help="fleet/chaos modes: serving replicas behind "
                        "the router (default 2; chaos defaults to 3 — "
                        "one to kill, one to drain, one survivor)")
    p.add_argument("--fleet-policy", choices=("affinity", "roundrobin"),
                   default="affinity",
                   help="fleet mode: routing policy (roundrobin is the "
                        "A/B control arm for the prefix-hit comparison)")
    p.add_argument("--fleet-kill-one", action="store_true",
                   help="fleet mode: kill one replica halfway through "
                        "the timed run (retry/fallback must complete "
                        "every request)")
    p.add_argument("--fleet-block-size", type=int, default=8,
                   help="fleet mode: kv_block_size on the replicas AND "
                        "the router's affinity-key block")
    p.add_argument("--fleet-hedge-after-s", type=float, default=10.0,
                   help="fleet mode: router hedge deadline (high "
                        "default: CPU compile stalls should retry, "
                        "not duplicate)")
    p.add_argument("--fleet-kv-pressure", action="store_true",
                   help="fleet mode: run the ISSUE-19 cache-tier A/B "
                        "instead of the policy A/B — a control fleet "
                        "(peer hints off, no spill) vs a tier fleet "
                        "(X-KV-Peer hints + host-RAM spill), both "
                        "with a block pool sized to force eviction; "
                        "asserts every response matches the recompute "
                        "oracle and the measured fleet-wide hit rate "
                        "closes >= half the affinity-vs-counterfactual "
                        "gap from /fleet/cache")
    p.add_argument("--fleet-kv-pool-blocks", type=int, default=0,
                   help="kv-pressure arm: per-replica KV pool blocks "
                        "(small enough that parked prefixes evict "
                        "under the seeded workload; 0 = auto-size "
                        "from the workload)")
    p.add_argument("--fleet-kv-spill-bytes", type=int,
                   default=32 << 20,
                   help="kv-pressure arm: host-RAM spill budget on "
                        "the TIER fleet's replicas (control always "
                        "runs with the tier off)")
    p.add_argument("--spread", action="store_true",
                   help="per-request max_new cycles 1/4x..1x of "
                        "--max-new (heterogeneous workload)")
    p.add_argument("--pipeline-depth", type=int, default=0,
                   help="continuous mode's dispatch-ahead depth "
                        "(0 = backend-aware default) — the knob the "
                        "depth-1-vs-2 A/B in docs/perf-notes.md used")
    args = p.parse_args()
    if args.requests < 2:
        p.error("--requests must be >= 2 (latency quantiles)")
    if args.pipeline_depth and args.mode != "continuous":
        p.error("--pipeline-depth requires --mode continuous")
    if args.pipeline_depth < 0:
        p.error("--pipeline-depth must be >= 0")
    if args.closed_loop and args.mode != "chaos":
        p.error("--closed-loop requires --mode chaos")
    if args.fleet_replicas is None:
        if args.mode == "chaos":
            # fault-injection needs kill+drain+survivor; the closed
            # loop needs total capacity loss, so a 1-replica fleet
            args.fleet_replicas = 1 if args.closed_loop else 3
        elif args.mode == "rollout":
            # the roll must walk canary + several promote steps so the
            # old and new version genuinely coexist under flood
            args.fleet_replicas = 4
        else:
            args.fleet_replicas = 2
    if args.fleet_kv_pressure and args.mode != "fleet":
        p.error("--fleet-kv-pressure requires --mode fleet")
    if args.mode == "fleet":
        if args.fleet_replicas < 1:
            p.error("--fleet-replicas must be >= 1")
        if args.fleet_kill_one and args.fleet_replicas < 2:
            p.error("--fleet-kill-one needs --fleet-replicas >= 2")
        if args.fleet_block_size < 1:
            p.error("--fleet-block-size must be >= 1")
        if args.fleet_kv_pressure:
            if args.fleet_kill_one:
                p.error("--fleet-kv-pressure and --fleet-kill-one are "
                        "separate arms — run them separately")
            if args.fleet_replicas < 2:
                p.error("--fleet-kv-pressure needs --fleet-replicas "
                        ">= 2 (peer fetch needs a peer)")
            if args.requests < 8:
                p.error("--fleet-kv-pressure needs --requests >= 8")
            if 0 < args.fleet_kv_pool_blocks < 16:
                p.error("--fleet-kv-pool-blocks must be >= 16 (the "
                        "pool must at least hold the active slots) "
                        "or 0 for auto-sizing")
            if args.fleet_kv_spill_bytes < 0:
                p.error("--fleet-kv-spill-bytes must be >= 0")
            result = run_fleet_kv_pressure(
                args.clients, args.requests, args.max_new,
                replicas=args.fleet_replicas,
                block_size=args.fleet_block_size,
                hedge_after_s=args.fleet_hedge_after_s,
                pool_blocks=args.fleet_kv_pool_blocks,
                spill_bytes=args.fleet_kv_spill_bytes)
        else:
            result = run_fleet(
                args.clients, args.requests, args.max_new,
                replicas=args.fleet_replicas, policy=args.fleet_policy,
                block_size=args.fleet_block_size,
                kill_one=args.fleet_kill_one,
                hedge_after_s=args.fleet_hedge_after_s)
    elif args.mode == "disagg":
        if args.disagg_prefill < 1 or args.disagg_decode < 1:
            p.error("--mode disagg needs --disagg-prefill >= 1 and "
                    "--disagg-decode >= 1 (an empty pool cannot serve)")
        if args.disagg_long_every < 2:
            p.error("--disagg-long-every must be >= 2 (the workload "
                    "must mix long and short prompts)")
        if args.requests < 2 * args.disagg_long_every:
            p.error("--mode disagg needs --requests >= "
                    "2 * --disagg-long-every")
        result = run_disagg(
            args.clients, args.requests, args.max_new,
            prefill_replicas=args.disagg_prefill,
            decode_replicas=args.disagg_decode,
            block_size=args.fleet_block_size,
            long_every=args.disagg_long_every,
            hedge_after_s=args.fleet_hedge_after_s)
    elif args.mode == "chaos" and args.closed_loop:
        if args.fleet_replicas < 1:
            p.error("--closed-loop needs --fleet-replicas >= 1")
        if args.requests < 8:
            p.error("--closed-loop needs --requests >= 8")
        result = run_chaos_closed_loop(
            args.clients, args.requests, args.max_new,
            replicas=args.fleet_replicas,
            block_size=args.fleet_block_size)
    elif args.mode == "chaos":
        if args.fleet_replicas < 3:
            # one SIGKILLed + one drained + at least one survivor to
            # absorb the migrated sequences and the wedge probe
            p.error("--mode chaos needs --fleet-replicas >= 3")
        if args.requests < 12:
            p.error("--mode chaos needs --requests >= 12")
        result = run_chaos(
            args.clients, args.requests, args.max_new,
            replicas=args.fleet_replicas,
            block_size=args.fleet_block_size,
            seed=args.chaos_seed,
            drop_rate=args.chaos_drop_rate,
            delay_rate=args.chaos_delay_rate,
            duplicate_rate=args.chaos_duplicate_rate,
            blackhole_beats=args.chaos_blackhole_beats)
    elif args.mode == "rollout":
        if args.fleet_replicas < 2:
            p.error("--mode rollout needs --fleet-replicas >= 2 "
                    "(a canary plus at least one replica to promote)")
        if args.requests < 8:
            p.error("--mode rollout needs --requests >= 8")
        result = run_rollout(
            args.clients, args.requests, args.max_new,
            replicas=args.fleet_replicas,
            block_size=args.fleet_block_size)
    elif args.mode == "train-chaos":
        if args.train_replicas < 2:
            p.error("--train-replicas must be >= 2 (one to kill, one "
                    "to survive)")
        if args.train_steps < 2 * args.train_save_every + 4:
            p.error("--train-steps must leave room for the survivors "
                    "to be mid-run when dead-detection fires "
                    "(>= 2*save_every + 4)")
        result = run_train_chaos(
            replicas=args.train_replicas,
            steps=args.train_steps,
            save_every=args.train_save_every,
            slow_save_s=args.train_slow_save_s,
            slo_short_s=args.train_slo_short_s)
    elif args.mode == "scenario":
        if not args.scenario:
            p.error("--mode scenario requires --scenario "
                    "(a trace file or gen:<shape>)")
        if args.scenario_speed <= 0:
            p.error("--scenario-speed must be > 0")
        if args.scenario_fidelity_pct < 0:
            p.error("--scenario-fidelity-pct must be >= 0")
        if (args.scenario_fidelity_pct > 0
                and args.scenario_target != "single"):
            p.error("--scenario-fidelity-pct needs --scenario-target "
                    "single (timelines live on replicas, not the "
                    "router)")
        if args.scenario_max_batch < 1:
            p.error("--scenario-max-batch must be >= 1")
        result = run_scenario(
            args.scenario, seed=args.seed, speed=args.scenario_speed,
            target=args.scenario_target,
            replicas=args.fleet_replicas,
            block_size=args.fleet_block_size,
            max_batch=args.scenario_max_batch,
            fidelity_pct=args.scenario_fidelity_pct)
    elif args.mode == "tenants":
        if args.tenant_bulk_clients < 1:
            p.error("--tenant-bulk-clients must be >= 1")
        if args.tenant_live_requests < 2:
            p.error("--tenant-live-requests must be >= 2 (quantiles)")
        if args.tenant_bulk_prompt < 1:
            p.error("--tenant-bulk-prompt must be >= 1")
        if args.prefill_chunk_tokens < 0:
            p.error("--prefill-chunk-tokens must be >= 0")
        result = run_tenants(
            bulk_clients=args.tenant_bulk_clients,
            live_requests=args.tenant_live_requests,
            bulk_prompt_len=args.tenant_bulk_prompt,
            prefill_chunk_tokens=args.prefill_chunk_tokens,
            slo_ttft_s=args.slo_ttft_s,
            slo_alert_burn=args.slo_alert_burn)
    else:
        result = run(args.clients, args.requests, args.max_new,
                     args.batch_window_ms, args.mode, args.spread,
                     pipeline_depth=args.pipeline_depth)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
