#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

Drives the data plane's main paths once on a TPU, at the full width of
`llama3-1b` with seeded random weights, through the entry points a user
calls, and checks what comes out:

  kernels  tools/smoke_kernels.py — each Pallas kernel compiled at the
           model's head shapes against the XLA reference
  serve    python -m kubeflow_tpu.serving --model llama3-1b --random
           --continuous --warmup ... — the command a ModelServer pod
           runs; must become ready, answer a repeated prompt
           identically (the second a radix hit), eight concurrent
           requests from 16 to ~1500 prompt tokens, one SSE stream,
           all without a program compiled after it was ready, and
           drain to exit code 0 on SIGTERM
  train    tools/smoke_train.py — the user guide's training script,
           five steps at seq 2048, loss finite and falling, attention
           through the flash kernel; over every chip of the host when
           there are several (pure FSDP), parameters checked to be
           spread over all of them

A chip belongs to one process. This parent never imports JAX; each
phase is a child started with JAX_PLATFORMS=tpu, one after another,
each fully exited before the next starts. There is no CPU mode: without
an accelerator the first child fails and so does this script.

Prints what each child saw (JAX version, platform, device kind and
count), seconds per phase, the compile cache directory with its entry
count before and after, and the attention impl counts. Exits non-zero
if any phase failed or any child's platform is not "tpu". On success
the last line of standard output is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Logs of the children go to chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

from kubeflow_tpu.compile_cache import cache_dir  # noqa: E402  (JAX-free)

LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
MODEL = "llama3-1b"
VOCAB = 128256
MAX_LEN = 2048
SERVE_ARGS = ["--model", MODEL, "--random", "--continuous", "--warmup",
              "--max-len", str(MAX_LEN), "--max-batch", "8"]
# ops/attention.py's rule: on TPU `auto` is the Pallas paged kernel
ATTENTION_IMPL = "pallas"
# The whole run has 1200 s, compilation included; no single wait may
# outlast what is left of it.
DEADLINE = time.monotonic() + 1150
SCRIPT_TIMEOUT_S = 420
SERVE_READY_TIMEOUT_S = 600
REQUEST_TIMEOUT_S = 300
DRAIN_TIMEOUT_S = 60


def within(seconds: float) -> float:
    return max(1.0, min(seconds, DEADLINE - time.monotonic()))


class PhaseFailed(Exception):
    pass


def child_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"
    env["PYTHONUNBUFFERED"] = "1"
    # a forced host-device count belongs to the CPU tests
    env.pop("XLA_FLAGS", None)
    return env


def cache_entries() -> int:
    path = cache_dir()
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def tail(path: str, n: int = 40) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def stop(proc: subprocess.Popen) -> None:
    """Whatever happened, the child does not outlive this script."""
    if proc.poll() is None:
        proc.kill()
    proc.wait()


# ------------------------------------------------------------ script phases


def run_script(phase: str, script: str) -> dict:
    """Run one tools/ script to its end; its last stdout line is its
    JSON result."""
    log = os.path.join(LOG_DIR, f"{phase}.log")
    timeout_s = within(SCRIPT_TIMEOUT_S)
    with open(log, "w") as out:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, script)],
            env=child_env(), cwd=ROOT, stdout=out, stderr=subprocess.STDOUT)
        try:
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(
                f"{script} still running after {timeout_s:.0f}s\n"
                f"{tail(log)}")
        finally:
            stop(proc)
    lines = [ln for ln in open(log, errors="replace").read().splitlines()
             if ln.startswith(f"{phase}:") or ln.startswith("{")]
    for ln in lines:
        if not ln.startswith("{"):
            print("  " + ln)
    if not lines or not lines[-1].startswith("{"):
        raise PhaseFailed(f"{script} exited {rc} without a result\n"
                          f"{tail(log)}")
    result = json.loads(lines[-1])
    if rc != 0 or not result.get("ok"):
        raise PhaseFailed(f"{script} exited {rc}: "
                          f"{result.get('problems')}\n{tail(log)}")
    return result


# ------------------------------------------------------------- serve phase


def http(port: int, path: str, body: dict | None = None,
         timeout: float = REQUEST_TIMEOUT_S):
    url = f"http://127.0.0.1:{port}{path}"
    data = None if body is None else json.dumps(body).encode()
    with urllib.request.urlopen(urllib.request.Request(url, data=data),
                                timeout=within(timeout)) as r:
        return r.read().decode()


def generate(port: int, tokens: list[int], max_new: int) -> list[int]:
    body = json.loads(http(port, f"/v1/models/{MODEL}:generate",
                           {"tokens": [tokens], "max_new": max_new}))
    return body["tokens"][0]


def stream(port: int, tokens: list[int], max_new: int) -> list[int]:
    """One SSE stream: a `data:` event per token, then a done record."""
    url = f"http://127.0.0.1:{port}/v1/models/{MODEL}:generate"
    data = json.dumps({"tokens": [tokens], "max_new": max_new,
                       "stream": True}).encode()
    got: list[int] = []
    done = None
    with urllib.request.urlopen(urllib.request.Request(url, data=data),
                                timeout=within(REQUEST_TIMEOUT_S)) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            event = json.loads(line[len("data: "):])
            if event.get("done"):
                done = event
            elif "tokens" in event:
                got.extend(event["tokens"][0])
    if done is None or done.get("total") != len(got) or "error" in done:
        raise PhaseFailed(f"stream ended without a clean done record: "
                          f"{done}, {len(got)} tokens")
    return got


def check_tokens(what: str, toks: list[int], n: int) -> None:
    if len(toks) != n or not all(
            isinstance(t, int) and 0 <= t < VOCAB for t in toks):
        raise PhaseFailed(f"{what}: expected {n} token ids in "
                          f"[0, {VOCAB}), got {toks}")


def metric(text: str, name: str, **labels) -> float:
    """Sum of a Prometheus family's samples whose labels include
    `labels`."""
    total, seen = 0.0, False
    for line in text.splitlines():
        m = re.match(rf"{name}(?:\{{(.*)\}})? (\S+)$", line)
        if m and all(f'{k}="{v}"' in (m.group(1) or "")
                     for k, v in labels.items()):
            total += float(m.group(2))
            seen = True
    if not seen:
        raise PhaseFailed(f"/metrics has no {name} with {labels}")
    return total


def compiled_so_far(port: int) -> tuple[dict, int]:
    """The signatures past its first that each watched jit has met,
    and the compile cache's entries."""
    profile = json.loads(http(port, "/debug/profile"))
    return profile["models"][MODEL]["recompiles"], cache_entries()


def serve_requests(port: int) -> dict:
    rnd = random.Random(0)
    # ready = compiled (--warmup): no request below may compile
    at_ready = compiled_so_far(port)

    def prompt(n: int) -> list[int]:
        return [rnd.randrange(VOCAB) for _ in range(n)]

    # one ~300-token prompt twice: identical tokens, the second a radix hit
    p300 = prompt(300)
    hits0 = metric(http(port, "/metrics"),
                   "serving_prefix_cache_hits_total", model=MODEL)
    first = generate(port, p300, 32)
    second = generate(port, p300, 32)
    check_tokens("repeated prompt", first, 32)
    if first != second:
        raise PhaseFailed(f"repeated prompt answered differently:\n"
                          f"{first}\n{second}")
    hits = metric(http(port, "/metrics"),
                  "serving_prefix_cache_hits_total", model=MODEL) - hits0
    if hits < 1:
        raise PhaseFailed("the repeated prompt was not a radix hit")

    # eight at once, 16 to ~1500 prompt tokens: fills the slots, crosses
    # chunked prefill and the decode kernel together
    lengths = [16, 64, 150, 300, 520, 800, 1100, 1500]
    answers: dict[int, object] = {}

    def one(n: int) -> None:
        try:
            answers[n] = generate(port, prompt(n), 16)
        except Exception as e:  # noqa: BLE001 — thread boundary: reported below
            answers[n] = e

    threads = [threading.Thread(target=one, args=(n,)) for n in lengths]
    for t in threads:
        t.start()
    for t in threads:
        t.join(within(REQUEST_TIMEOUT_S))
    for n in lengths:
        if not isinstance(answers.get(n), list):
            raise PhaseFailed(f"concurrent request with {n} prompt tokens "
                              f"failed: {answers.get(n)!r}")
        check_tokens(f"concurrent request ({n} prompt tokens)",
                     answers[n], 16)

    streamed = stream(port, prompt(40), 24)
    check_tokens("SSE stream", streamed, 24)

    profile = json.loads(http(port, "/debug/profile"))
    phases = profile["models"][MODEL]["phases"]
    for name in ("prefill_chunk", "decode"):
        if not phases[name]["count"] or not phases[name]["total_s"]:
            raise PhaseFailed(f"/debug/profile shows no {name} phase: "
                              f"{phases[name]}")
    if metric(http(port, "/metrics"), "serving_attention_impl",
              model=MODEL, impl=ATTENTION_IMPL) != 1:
        raise PhaseFailed(
            f"serving_attention_impl is not {ATTENTION_IMPL}")
    at_end = compiled_so_far(port)
    if at_end != at_ready:
        raise PhaseFailed(
            f"a request compiled a program the warm-up had not: "
            f"(recompiles, cache entries) {at_ready} when ready, "
            f"{at_end} after the last request")
    return {
        "radix_hits": hits,
        "prefill_chunk": {k: phases["prefill_chunk"][k]
                          for k in ("count", "tokens", "total_s")},
        "decode": {k: phases["decode"][k]
                   for k in ("count", "total_s", "p50_s")},
        # what the warm-up walked; nothing was added after it
        "recompiles": at_ready[0],
        "attention_impl": ATTENTION_IMPL,
    }


def serve_phase() -> dict:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    log = os.path.join(LOG_DIR, "serve.log")
    ready_timeout_s = within(SERVE_READY_TIMEOUT_S)
    t0 = time.monotonic()
    with open(log, "w") as out:
        proc = subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.serving", *SERVE_ARGS,
             "--host", "127.0.0.1", "--port", str(port)],
            env=child_env(), cwd=ROOT, stdout=out, stderr=subprocess.STDOUT)
        try:
            # ready = compiled (--warmup) and admitting work
            while True:
                if proc.poll() is not None:
                    raise PhaseFailed(f"server exited {proc.returncode} "
                                      f"before ready\n{tail(log)}")
                if time.monotonic() - t0 > ready_timeout_s:
                    raise PhaseFailed(f"server not ready after "
                                      f"{ready_timeout_s:.0f}s\n{tail(log)}")
                try:
                    http(port, "/healthz", timeout=2)
                    break
                except OSError:
                    time.sleep(1)
            ready_s = time.monotonic() - t0
            try:
                result = serve_requests(port)
            except PhaseFailed as e:
                raise PhaseFailed(f"{e}\n{tail(log)}")
            requests_s = time.monotonic() - t0 - ready_s
            proc.send_signal(signal.SIGTERM)
            try:
                rc = proc.wait(timeout=within(DRAIN_TIMEOUT_S))
            except subprocess.TimeoutExpired:
                raise PhaseFailed(f"server still up {DRAIN_TIMEOUT_S}s "
                                  f"after SIGTERM\n{tail(log)}")
            if rc != 0:
                raise PhaseFailed(f"server exited {rc} on SIGTERM\n"
                                  f"{tail(log)}")
        finally:
            stop(proc)
    # the server's own start-up line says what it attached
    m = re.search(r"backend=(\S+) device_kind='([^']*)' devices=(\d+) "
                  r"jax=(\S+)", open(log, errors="replace").read())
    if not m:
        raise PhaseFailed(f"no start-up line in the server log\n{tail(log)}")
    result.update(
        phase="serve", jax=m.group(4), ready_seconds=round(ready_s, 1),
        requests_seconds=round(requests_s, 1),
        device={"platform": m.group(1), "kind": m.group(2),
                "count": int(m.group(3))})
    return result


# -------------------------------------------------------------------- main


def main() -> int:
    os.makedirs(LOG_DIR, exist_ok=True)
    print(f"compile cache: {cache_dir()} "
          f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'in-checkout default'}), "
          f"{cache_entries()} entries before")
    phases = (
        ("kernels", lambda: run_script("kernels", "tools/smoke_kernels.py")),
        ("serve", serve_phase),
        ("train", lambda: run_script("train", "tools/smoke_train.py")),
    )
    results, failed = {}, ""
    for name, run in phases:
        print(f"== {name}", flush=True)
        t0 = time.monotonic()
        before = cache_entries()
        try:
            r = results[name] = run()
            if r["device"]["platform"] != "tpu":
                raise PhaseFailed(
                    f"ran on {r['device']['platform']!r}, not tpu")
        except PhaseFailed as e:
            # the first failure ends the run: later phases would spend
            # the chip on a system already known to be broken
            print(f"{name}: FAILED: {e}", file=sys.stderr, flush=True)
            failed = name
            break
        finally:
            print(f"{name}: {time.monotonic() - t0:.1f}s, cache entries "
                  f"{before} -> {cache_entries()}", flush=True)
        print(f"{name}: jax={r['jax']} device={r['device']}")
    if "kernels" in results:
        print(f"kernels: auto selects {results['kernels']['auto']}")
    if "serve" in results:
        r = results["serve"]
        print(f"serve: ready in {r['ready_seconds']}s, requests "
              f"{r['requests_seconds']}s, attention_impl="
              f"{r['attention_impl']}, radix_hits={r['radix_hits']}, "
              f"prefill_chunk={r['prefill_chunk']}, decode={r['decode']}, "
              f"recompiles={r['recompiles']}")
    if "train" in results:
        r = results["train"]
        print(f"train: depth {r['depth']} of {r['full_depth']}, mesh "
              f"{r['mesh']}, losses {r['losses']}, step seconds "
              f"{r['step_seconds']}, attention impl counts "
              f"{r['impl_counts']}, parameter bytes per device "
              f"{r['param_bytes_per_device']}, peak bytes in use "
              f"{r['peak_bytes_in_use']}")
    print(f"compile cache: {cache_entries()} entries after")
    if failed:
        print(f"chip_smoke: FAILED in phase {failed}", file=sys.stderr)
        return 1
    devices = [r["device"] for r in results.values()]
    if any(d != devices[0] for d in devices):
        print(f"chip_smoke: FAILED: phases saw different devices: "
              f"{devices}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
