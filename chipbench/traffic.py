"""The one traffic generator: turns a traffic file's parameters and a seed
into requests.

Sizes and arrival gaps are stratified quantiles of the stated
distributions, put in one fixed order (``SCHEDULE_SEED``): every run seed
gets the same schedule of arrivals and output lengths, and its own prompt
token ids.  (Shuffling the order by the run seed made the latency median
of one cell spread by 10-12% between seeds against 1-5% between two runs
of one seed, see PERF.md.)

Open loop (``"arrivals": "poisson"``): ``round(rate_rps * seconds)`` requests
whose gaps are exponential quantiles of mean ``1 / rate_rps``, scaled so that
the last is due inside the window.  The gaps follow ``poisson_arrivals`` of
``repro.harnesses.serve`` (exponential gaps), with a fixed set of gaps in
place of independent draws.

Every request decodes greedily with no end token: the check compares the
served tokens with the reference's best, which holds for greedy tokens only.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

SCHEDULE_SEED = 0


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any integer) and a stream index."""
    return np.random.default_rng([seed % 2**63, *stream])


@dataclasses.dataclass
class Request:
    uid: int
    arrival_s: float          # due time, seconds after the window opens
    prompt: np.ndarray        # (prompt_len,) int32
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def output_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` output lengths, sorted: fixed, or lognormal quantiles clipped."""
    if "len" in spec:
        return np.full(n, int(spec["len"]), np.int64)
    if spec.get("dist") != "lognormal":
        raise ValueError(f"unknown output distribution {spec!r}")
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(n)])
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def open_loop(traffic: dict, *, seed: int, seconds: float, vocab: int) -> List[Request]:
    """The requests due in a window of ``seconds``, in order of arrival."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"open_loop needs poisson arrivals, got {traffic['arrivals']!r}")
    rate = float(traffic["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    order = rng_for(SCHEDULE_SEED, 0)
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps *= seconds * (n - 0.5) / n / gaps.sum()    # the last is due inside the window
    arrivals = np.cumsum(order.permutation(gaps))
    outs = order.permutation(output_lengths(traffic["output"], n))
    plen = int(traffic["prompt_len"])
    prompts = rng_for(seed, 0).integers(1, vocab, (n, plen), dtype=np.int32)
    return [Request(i, float(arrivals[i]), prompts[i], int(outs[i])) for i in range(n)]


def check_sample(n_items: int, size: int, *, seed: int, longest: int) -> List[int]:
    """Indices of ``size`` items to compare, drawn from ``seed``, always
    holding ``longest``."""
    rng = rng_for(seed, 2)
    rest = [int(i) for i in rng.permutation(n_items) if i != longest]
    return sorted([longest] + rest[: max(0, size - 1)])
