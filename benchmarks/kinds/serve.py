"""Kind of cell `serve`: one replica driven in-process. The load
generator and `ContinuousBatcher` share one asyncio loop, as
`bench.py`'s `bench_decode_cont_ttft` does; there is no server child
and no HTTP (PERF.md section 7 says what that leaves out).

Arrivals are data: `{"process": "closed", "clients": n}` — each client
opens a stream, reads it to its end and sends its next request at
once — or `{"process": "poisson", "rate_rps": r}` — requests are due
on a schedule made from the mix's `order_seed`, each timed from when it
was due, the generator's lateness kept.

One run: weights on the device from the seed -> reference check ->
warm-up (every program the window can need) -> `ramp_s` of the cell's
own traffic (the batch fills; counted as set-up) -> the measured
window -> a drain bounded by `drain_s`, until every request that was
due in the window has its first token -> whatever is still decoding is
cut by the harness (not a failure: its tokens inside the window count,
its tail outside does not exist).
"""

from __future__ import annotations

import asyncio
import dataclasses
import statistics
import time

import numpy as np

from benchmarks import harness, trafficgen

# seconds of the window a traced run profiles: enough for some hundred
# program executions, small enough to parse in seconds
TRACE_S = 4.0
POOL_SAMPLE_S = 0.05


@dataclasses.dataclass
class Request:
    due: float                      # when it was to be sent
    sent: float                     # when it was
    max_new: int
    times: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False              # read to its end
    cut: bool = False               # cancelled by the harness at the end
    error: str | None = None


class Load:
    """Sends a `RequestStream` to a batcher and records every token's
    arrival on the host clock."""

    def __init__(self, batcher, stream: trafficgen.RequestStream):
        self.batcher = batcher
        self.stream = stream
        self.requests: list[Request] = []
        self._reading: set[asyncio.Task] = set()
        self.stopped = False

    async def one(self, due: float) -> Request:
        prompt, max_new = self.stream.next()
        req = Request(due=due, sent=time.perf_counter(), max_new=max_new)
        self.requests.append(req)
        try:
            fut, queue = self.batcher.open_stream(prompt, max_new, ())
        except Exception as e:  # noqa: BLE001 — refused: a failed request
            req.error = f"refused: {e!r}"
            return req
        me = asyncio.current_task()
        self._reading.add(me)
        try:
            while True:
                tok = await queue.get()
                if tok is None:
                    break
                req.times.append(time.perf_counter())
                req.tokens.append(tok)
            await fut
            req.done = True
        except asyncio.CancelledError:
            if not self.stopped:
                raise
            req.cut = True          # `cut()` cancelled this read itself
        except Exception as e:  # noqa: BLE001 — raised: a failed request
            req.error = f"raised: {e!r}"
        finally:
            self._reading.discard(me)
            if not fut.done():
                fut.cancel()        # frees the slot, as a disconnect does
        return req

    async def client(self) -> None:
        while not self.stopped:
            await self.one(time.perf_counter())

    async def schedule(self, t_begin: float, offsets: list[float]) -> None:
        tasks = []
        for off in offsets:
            delay = t_begin + off - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            if self.stopped:
                break
            tasks.append(asyncio.ensure_future(self.one(t_begin + off)))
        await asyncio.gather(*tasks)

    def cut(self) -> None:
        """End of the run: stop reading what is still decoding. A
        cancelled request's queue gets no end marker, so it is the
        reader that is cancelled, and the reader cancels the request."""
        self.stopped = True
        for task in list(self._reading):
            task.cancel()


async def _burst(batcher, prompts: list[list[int]], max_new: int, *,
                 cancel_after_first: bool) -> None:
    """`len(prompts)` simultaneous requests. With `cancel_after_first`
    all are cancelled at once when each has its first token, so that
    they retire in one iteration and the `reset_slots` program of that
    many slots (padded to a power of two) compiles now."""
    streams = [batcher.open_stream(p, max_new, ()) for p in prompts]
    if not cancel_after_first:
        await asyncio.gather(*(fut for fut, _ in streams))
        return
    for _, q in streams:
        await q.get()
    for fut, _ in streams:
        fut.cancel()
    await asyncio.gather(*(fut for fut, _ in streams),
                         return_exceptions=True)


async def warm_up(batcher, cell: harness.Cell, seed: int) -> None:
    """Every program the chunked path can need, through the public API
    alone (`ContinuousBatcher.warmup()` is not called: it compiles the
    monolithic prefill's group sizes, which this path never runs).

    - decode exists for 1 to `chunk` steps a dispatch: a lone request
      of 5 + k tokens is dispatched as 4 steps and then k;
    - `reset_slots` pads its slot list to a power of two: bursts of 2,
      4, ... slots retired in one iteration, each reset by the request
      that follows it;
    - `copy_cells` runs when a prompt shares part of a cached block
      (ids drawn over the vocabulary do now and then share a first
      token): a prompt that repeats the head of an earlier one.
    `append_rows` and `adopt_slot` are one shape each."""
    vocab = cell.config["vocab_size"]
    rng = np.random.default_rng(trafficgen.seed_words(seed, 0x7761726D))

    def prompt(n):
        return rng.integers(0, vocab, n).tolist()

    chunk = batcher.chunk
    for k in range(1, chunk + 1):
        await _burst(batcher, [prompt(24)], 1 + chunk + k,
                     cancel_after_first=False)
    # the radix cache keeps whole blocks: share one and a half of them
    block = cell.config["batcher"]["kv_block_size"]
    head = prompt(3 * block)
    await _burst(batcher, [head], 2, cancel_after_first=False)
    await _burst(batcher, [head[:block + block // 2] + prompt(block)], 2,
                 cancel_after_first=False)
    n = 2
    slots = cell.config["batcher"]["max_slots"]
    while n <= slots:
        # long enough that the first is still decoding when the last
        # has its first token: one slice and one dispatch a request
        await _burst(batcher, [prompt(20) for _ in range(n)],
                     32 + 2 * chunk * n, cancel_after_first=True)
        n *= 2
    await _burst(batcher, [prompt(20)], 2, cancel_after_first=False)


async def check_reference(batcher, cell: harness.Cell, model, params,
                          seed: int) -> tuple[list[str], float]:
    """-> (problems, largest difference read). The served path against
    the plain reference, outside the window:
    for each seeded prompt, the engine's log-probability of every token
    it emitted (the first from the chunked prefill, the rest from the
    paged decode) against the reference's log-probability of that token
    at that position. Log-probabilities and not tokens: with random
    weights the largest logit changes on rounding."""
    spec = cell.config["reference_check"]
    vocab = cell.config["vocab_size"]
    tol = model.logprob_tolerance(cell.config)
    rng = np.random.default_rng(trafficgen.seed_words(seed, 0x726566))
    problems, worst = [], 0.0
    for n in spec["prompt_lengths"]:
        prompt = rng.integers(0, vocab, n).tolist()
        out, lps = await batcher.submit(prompt, spec["max_new"], (),
                                        with_logprobs=True)
        if len(out) != spec["max_new"] or len(lps) != spec["max_new"]:
            problems.append(f"reference prompt of {n}: {len(out)} tokens, "
                            f"{len(lps)} log-probabilities, asked "
                            f"{spec['max_new']}")
            continue
        want = np.asarray(model.reference_token_logprobs(
            cell.config, params, prompt + out[:-1],
            prompt[1:] + out))[n - 1:]
        diff = float(np.max(np.abs(np.asarray(lps) - want)))
        worst = max(worst, diff)
        if not diff <= tol:
            problems.append(
                f"reference prompt of {n}: log-probabilities differ by "
                f"{diff:.4g}, tolerance {tol}")
    return problems, worst


def _output_fault(req: Request, vocab: int) -> str | None:
    if req.done and len(req.tokens) != req.max_new:
        return f"{len(req.tokens)} tokens returned, {req.max_new} asked"
    if len(req.tokens) > req.max_new:
        return f"{len(req.tokens)} tokens streamed, {req.max_new} asked"
    if any(not 0 <= t < vocab for t in req.tokens):
        return "a token id outside the vocabulary"
    return None


def reduce_requests(requests: list[Request], t0: float, t1: float,
                    vocab: int) -> dict:
    """The window [t0, t1) of the host clock, from the per-token
    arrival times. Tails are over every request that was due in the
    window; the rate is over every token that arrived in it."""
    ttft, itl, late = [], [], []
    tokens_in = completed_in = attempted = failed = 0
    problems = []
    for r in requests:
        tokens_in += sum(t0 <= t < t1 for t in r.times)
        itl.extend(b - a for a, b in zip(r.times, r.times[1:])
                   if t0 <= b < t1)
        if r.done and t0 <= r.times[-1] < t1:
            completed_in += 1
        if not t0 <= r.due < t1:
            continue
        attempted += 1
        late.append(r.sent - r.due)
        bad = r.error or _output_fault(r, vocab)
        if bad is None and not r.times:
            bad = "no first token by the end of the drain"
        if bad is not None:
            failed += 1
            problems.append(bad)
        else:
            ttft.append(r.times[0] - r.due)
    window = t1 - t0
    # tokens by fifths of the window: a drift shows a ramp that was too short
    fifths = [0] * 5
    for r in requests:
        for t in r.times:
            if t0 <= t < t1:
                fifths[min(4, int(5 * (t - t0) / window))] += 1
    out = {
        "tokens_by_fifth": fifths,
        "serve_tok_s": tokens_in / window,
        "requests_per_s": completed_in / window,
        "attempted": attempted, "failed": failed,
        "n_ttft": len(ttft), "n_itl": len(itl),
        "n_cut": sum(r.cut for r in requests),
        "problems": sorted(set(problems)),
    }
    if ttft:
        out["ttft_p50_ms"] = 1e3 * statistics.median(ttft)
        out["ttft_p90_ms"] = 1e3 * trafficgen.percentile(ttft, 0.90)
    if itl:
        out["itl_p50_ms"] = 1e3 * statistics.median(itl)
        out["itl_p95_ms"] = 1e3 * trafficgen.percentile(itl, 0.95)
    if late:
        out["late_p95_ms"] = 1e3 * trafficgen.percentile(late, 0.95)
    return out


def _counters(batcher) -> dict:
    return {"calls": batcher.calls,
            "tokens_emitted": batcher.tokens_emitted,
            "recompiles": sum(batcher.compile_watch.counts().values())}


async def _sample_pool(batcher, peak: list[int]) -> None:
    while True:
        peak[0] = max(peak[0], batcher.kv_blocks_in_use())
        await asyncio.sleep(POOL_SAMPLE_S)


async def _serve(cell, model, *, seed, seconds, trace_dir, t_start, tamper):
    import jax

    from kubeflow_tpu.serving.continuous import ContinuousBatcher

    config, mix = cell.config, cell.traffic
    vocab = config["vocab_size"]
    engine, params = model.serving_engine(config, seed)
    jax.block_until_ready(params)
    harness.log(t_start, "parameters on the device")
    batcher = ContinuousBatcher(engine, asyncio.Lock(), **config["batcher"])
    loop = asyncio.get_running_loop()
    profile = harness.Profile(trace_dir)
    background: list[asyncio.Future] = []
    load = None
    try:
        problems, worst = await check_reference(
            batcher, cell, model,
            params if tamper is None else tamper(params), seed)
        harness.log(t_start, f"reference check: largest log-probability "
                             f"difference {worst:.4g}, problems {problems}")
        await warm_up(batcher, cell, seed)
        harness.log(t_start, "warm-up done")

        load = Load(batcher, trafficgen.RequestStream(mix, seed, vocab))
        arrivals = mix["arrivals"]
        ramp, drain = float(mix["ramp_s"]), float(mix["drain_s"])
        t_begin = time.perf_counter()
        if arrivals["process"] == "closed":
            background = [asyncio.ensure_future(load.client())
                          for _ in range(arrivals["clients"])]
        elif arrivals["process"] == "poisson":
            offsets = trafficgen.poisson_offsets(
                arrivals["rate_rps"], int(mix.get("order_seed", 0)),
                ramp + seconds, cycle=int(mix.get("cycle", 64)))
            background = [asyncio.ensure_future(
                load.schedule(t_begin, offsets))]
        else:
            raise ValueError(f"unknown arrivals {arrivals['process']!r}")
        await asyncio.sleep(ramp)

        peak = [0]
        background.append(asyncio.ensure_future(_sample_pool(batcher, peak)))
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        c0 = _counters(batcher)
        traced_counters = None
        harness.log(t_start, f"window opens: {seconds} s")
        with harness.CompileCount() as compiles:
            if trace_dir is not None:
                ct0 = _counters(batcher)
                await loop.run_in_executor(None, profile.start)
                await asyncio.sleep(min(TRACE_S, seconds))
                ct1 = _counters(batcher)
                trace = await loop.run_in_executor(None, profile.stop)
                traced_counters = {k: ct1[k] - ct0[k] for k in ct0}
            else:
                trace = None
            await asyncio.sleep(max(0.0, t0 + seconds - time.perf_counter()))
            t1 = time.perf_counter()
            c1 = _counters(batcher)
            compiled = compiles.n
        load.stopped = True            # no new request from here on
        harness.log(t_start, f"window closed, {len(load.requests)} requests "
                             f"sent so far, {compiled} programs compiled")

        def waiting():
            return [r for r in load.requests
                    if t0 <= r.due < t1 and not r.times and not r.error]
        deadline = t1 + drain
        while waiting() and time.perf_counter() < deadline:
            await asyncio.sleep(0.02)
    finally:
        if load is not None:
            load.cut()
        for task in background:
            task.cancel()
        await asyncio.gather(*background, return_exceptions=True)
        await batcher.close()
    harness.log(t_start, "drained and closed")

    red = reduce_requests(load.requests, t0, t1, vocab)
    problems += red.pop("problems")
    window = {k: c1[k] - c0[k] for k in c0}
    if compiled:
        problems.append(f"{compiled} programs compiled inside the window")
    if window["recompiles"]:
        problems.append(f"{window['recompiles']} recompiles inside the "
                        f"window: {batcher.compile_watch.counts()}")
    counters = {
        "slots": config["batcher"]["max_slots"],
        "pool_blocks": batcher.cengine.pool.capacity,
        "pool_peak_in_use": peak[0],
        "decode_steps": window["calls"],
        "decode_tokens": window["tokens_emitted"],
        "recompiles": window["recompiles"],
        "compiles_in_window": compiled,
        "late_p95_ms": red.get("late_p95_ms"),
        # the context a decoded token attends to, for the byte model.
        # From the traffic and not from the pool: the radix cache keeps
        # retired blocks, so blocks in use overcount what is read
        "mean_context_tokens": _mean_context(mix),
    }
    if traced_counters is not None:
        counters["traced_decode_steps"] = traced_counters["calls"]
    end_to_end = {k: red[k] for k in
                  ("serve_tok_s", "ttft_p90_ms", "itl_p95_ms") if k in red}
    end_to_end["setup_s"] = setup_s
    extra = {k: red[k] for k in red
             if k not in ("attempted", "failed")}
    extra.update(window_s=t1 - t0, reference_worst_logprob_diff=worst,
                 pool_peak_in_use=peak[0], decode_steps=window["calls"],
                 decode_tokens=window["tokens_emitted"])
    return harness.Run(
        end_to_end=end_to_end, counters=counters,
        attempted=red["attempted"], failed=red["failed"],
        problems=problems, trace=trace, extra=extra)


def _mean_context(mix: dict) -> float:
    """Expected context of one decoded token: a request of `o` output
    tokens decodes `o` times at a mean context of prompt + o / 2, and
    prompt and output lengths are paired at random."""
    n = int(mix.get("cycle", 64))
    prompts = trafficgen.strata(mix["prompt_tokens"], n)
    outputs = trafficgen.strata(mix["output_tokens"], n)
    return (sum(prompts) / n
            + sum(o * o for o in outputs) / (2.0 * sum(outputs)))


def run(cell: harness.Cell, model, *, seed: int, seconds: float,
        trace_dir: str | None, t_start: float, tamper=None) -> harness.Run:
    return asyncio.run(_serve(
        cell, model, seed=seed, seconds=seconds, trace_dir=trace_dir,
        t_start=t_start, tamper=tamper))
