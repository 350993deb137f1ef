"""Arithmetic that the metric readers in ``chipbench/metrics/`` share."""

from __future__ import annotations

import math
from typing import Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear between ranks); a failed item counts
    as infinitely late, and a percentile that lands on one is ``None``."""
    if not len(values):
        return None
    v = float(np.percentile(np.asarray(values, np.float64), q))
    return v if math.isfinite(v) else None


def mean(values) -> Optional[float]:
    """The mean; ``None`` where a failed item (infinitely late) is among them."""
    if not len(values):
        return None
    v = float(np.mean(np.asarray(values, np.float64)))
    return v if math.isfinite(v) else None


def latencies(run):
    """Seconds from due to served, per request of the window."""
    return [r["end"] - r["due"] if r["ok"] else math.inf for r in run.record["requests"]]


def queue_waits(run):
    """Seconds from due to the start of the call that served the request."""
    return [r["start"] - r["due"] if r["ok"] else math.inf for r in run.record["requests"]]


def span_s(run) -> float:
    """From the window's start to the end of the last call."""
    return max(c["end"] for c in run.record["calls"])


def work_flops(run) -> float:
    """Operations of the real work served: each served request's prefill and
    decode steps, as the cell's family counts them."""
    return float(sum(run.family.sequence_flops(run.model, r["n_prompt"], r["n_out"])
                     for r in run.record["requests"] if r["ok"]))


def mfu_percent(run) -> Optional[float]:
    if run.peak is None:
        return None
    return 100.0 * work_flops(run) / (span_s(run) * run.chips * run.peak["flops_bf16"])


def idle_percent(run) -> Optional[float]:
    t = run.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
