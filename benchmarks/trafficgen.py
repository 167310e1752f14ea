"""The one traffic generator: every mix is a data file of parameters
under `traffic/`, read here.

Every seed gets the same work in the same order. A length distribution
is cut into `cycle` equal-probability strata and each run of `cycle`
consecutive requests holds each stratum's value exactly once, prompt and
output lengths permuted independently; Poisson gaps are the strata of
the exponential law, permuted the same way. The permutations come from
the mix's own `order_seed`, which is data, and not from `--seed`: on the
chip the same sizes in another order moved the closed loop's
90th-percentile TTFT by a quarter (which long prompts collide is the
tail), while two runs of one order agreed to a thousandth (PERF.md
section 6, PR 24). `--seed` draws the token ids (and, in the kinds, the
weights): it changes every input and none of the work.

Copied from `kubeflow_tpu/scenarios` (sound there: seeded, no wall
clock, open loop timed from the scheduled send): the arrival process
and `percentile`. Not copied: `prompt_ids_for`, whose 480-id band is a
toy; ids are drawn here over the configuration's whole vocabulary.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_SEED_MASK = (1 << 32) - 1


def seed_words(seed: int, *more: int) -> list[int]:
    """A seed of any size as 32-bit words for numpy's SeedSequence (the
    driver's seeds pass 2**31)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return [seed & _SEED_MASK, seed >> 32, *more]


def strata(spec: dict, n: int) -> list[int]:
    """The `n` equal-probability strata of a length distribution, each
    at its mid-quantile, clipped to [min, max] and rounded."""
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    normal = statistics.NormalDist()
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        x = math.exp(mu + spec["sigma"] * normal.inv_cdf((i + 0.5) / n))
        out.append(int(round(min(max(x, spec["min"]), spec["max"]))))
    return out


def exponential_strata(rate: float, n: int) -> list[float]:
    """The `n` equal-probability strata of exponential gaps at `rate`
    a second, each at its mid-quantile, rescaled to the mean 1/rate
    (mid-quantiles alone undershoot the law's tail)."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / (rate * sum(raw))
    return [g * scale for g in raw]


class RequestStream:
    """Requests in order of issue: `next()` -> (prompt ids, max_new).
    Closed-loop clients and the open-loop scheduler draw from the one
    stream, so a cycle's set of sizes is whole however they interleave."""

    def __init__(self, traffic: dict, seed: int, vocab_size: int):
        self._seed = seed
        self._order_seed = int(traffic.get("order_seed", 0))
        self._vocab = vocab_size
        self._n = int(traffic.get("cycle", 64))
        self._prompts = strata(traffic["prompt_tokens"], self._n)
        self._outputs = strata(traffic["output_tokens"], self._n)
        self._i = 0
        self._order: tuple[np.ndarray, np.ndarray] | None = None

    def next(self) -> tuple[list[int], int]:
        cycle, pos = divmod(self._i, self._n)
        if pos == 0 or self._order is None:
            rng = np.random.default_rng([self._order_seed, cycle])
            self._order = (rng.permutation(self._n),
                           rng.permutation(self._n))
        length = self._prompts[self._order[0][pos]]
        max_new = self._outputs[self._order[1][pos]]
        ids = np.random.default_rng(
            seed_words(self._seed, cycle, pos)
        ).integers(0, self._vocab, length)
        self._i += 1
        return ids.tolist(), max_new


def poisson_offsets(rate_rps: float, order_seed: int, duration_s: float, *,
                    cycle: int = 64) -> list[float]:
    """Send times in [0, duration_s) of a Poisson process at `rate_rps`,
    its gaps stratified and ordered as the module docstring says."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    gaps = exponential_strata(rate_rps, cycle)
    out: list[float] = []
    t, c = 0.0, 0
    while True:
        order = np.random.default_rng(
            [order_seed, 0x6172, c]).permutation(cycle)
        for j in order:
            t += gaps[j]
            if t >= duration_s:
                return out
            out.append(t)
        c += 1


def percentile(xs: list[float], q: float) -> float | None:
    """Nearest-rank percentile, as `scenarios/replay.py` takes it."""
    if not xs:
        return None
    s = sorted(xs)
    return s[min(len(s) - 1, int(q * len(s)))]
