"""Kernel block-size autotuning: sweep, classify, promote, cache.

``autotune@v1`` sweeps a pallas kernel's block-knob grid through
``KernelHarness`` cells (each point is a feature-injection override),
classifies every point with the roofline vocabulary, then promotes the
fastest config twice over:

* into the **autotune cache** — a JSON file keyed by
  ``(kernel, shape key, dtype, hardware-fingerprint key)`` that the
  kernels' ``ops.py`` entry points consult for their *default* blocks
  (opt-in via the ``EXACB_AUTOTUNE_CACHE`` environment variable, so a
  bare ``flash_attention(q, k, v)`` call stays dependency-free), and
* into the **regression gate** — confirmation runs of the winner are
  pinned as the ``kernel_latency_s`` baseline, so later sweeps defend
  the tuned latency instead of chasing a drifting rolling window.

The fingerprint component of the cache key is what makes the cache safe
to ship around: an entry tuned on one machine (or under one governor /
library stack) is invisible on another — lookups compare the *full*
canonical fingerprint key, not a truncated hash.

A re-run with an unchanged key is an incremental no-op (the exaCB
watermark idiom applied to tuning): the sweep is skipped and the cached
winner reported, unless ``force: true``.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
from pathlib import Path
from typing import Any, Dict, Iterable, List, Mapping, Optional, Tuple

from repro.core import fingerprint
from repro.core.component import (
    PARALLELISM,
    ComponentContext,
    ComponentInputs,
    ComponentSchema,
    InputSpec,
    PipelineError,
)

CACHE_BASENAME = "autotune_cache.json"
CACHE_ENV = "EXACB_AUTOTUNE_CACHE"


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------

def _entry_key(kernel: str, shape: str, dtype: str, fp_key: str) -> str:
    import hashlib

    fp16 = hashlib.sha256(fp_key.encode()).hexdigest()[:16] if fp_key else "nofp"
    return f"{kernel}|{shape}|{dtype}|{fp16}"


class AutotuneCache:
    """One JSON file of promoted block configs; atomic writes, full-key
    fingerprint verification on lookup (hash collisions cannot alias)."""

    def __init__(self, path: str | Path):
        self.path = Path(path)

    def load(self) -> Dict[str, Any]:
        try:
            data = json.loads(self.path.read_text())
        except (OSError, ValueError):
            return {"version": 1, "entries": {}}
        if not isinstance(data, dict) or not isinstance(data.get("entries"), dict):
            return {"version": 1, "entries": {}}
        return data

    def lookup(self, kernel: str, shape: str, dtype: str, fp_key: str) -> Optional[Dict[str, Any]]:
        entry = self.load()["entries"].get(_entry_key(kernel, shape, dtype, fp_key))
        if entry is None:
            return None
        if entry.get("fingerprint_key", "") != fp_key:
            return None  # hash-bucket collision or hand-edited file: distrust
        return dict(entry)

    def put(self, kernel: str, shape: str, dtype: str, fp_key: str,
            config: Dict[str, int], **extra: Any) -> Dict[str, Any]:
        from repro.core.store import _atomic_write

        data = self.load()
        key = _entry_key(kernel, shape, dtype, fp_key)
        prev = data["entries"].get(key, {})
        entry = {
            "kernel": kernel,
            "shape": shape,
            "dtype": dtype,
            "fingerprint_key": fp_key,
            "config": {k: int(v) for k, v in config.items()},
            "updates": int(prev.get("updates", 0)) + 1,
            **extra,
        }
        data["entries"][key] = entry
        self.path.parent.mkdir(parents=True, exist_ok=True)
        _atomic_write(self.path, json.dumps(data, indent=1, sort_keys=True))
        return entry


# -- ops.py-facing lookup ----------------------------------------------------
# Kernel entry points call `cached_blocks(...)` on every invocation with
# unresolved (None) block arguments, so the lookup has to be cheap: the
# fingerprint key is computed once per process, and cache files are
# re-parsed only when their mtime changes.

_FP_KEY: Optional[str] = None
_FILE_CACHE: Dict[str, Tuple[int, Dict[str, Any]]] = {}


def _current_fp_key() -> str:
    global _FP_KEY
    if _FP_KEY is None:
        _FP_KEY = fingerprint.key(fingerprint.capture())
    return _FP_KEY


def cached_blocks(kernel: str, shape: str, dtype: str,
                  path: Optional[str | Path] = None) -> Optional[Dict[str, int]]:
    """Promoted block config for (kernel, shape, dtype) on *this* hardware,
    or None.  ``path`` defaults to ``$EXACB_AUTOTUNE_CACHE``; unset means
    autotuned defaults are off."""
    p = str(path) if path else os.environ.get(CACHE_ENV, "")
    if not p:
        return None
    try:
        mtime = os.stat(p).st_mtime_ns
    except OSError:
        return None
    cached = _FILE_CACHE.get(p)
    if cached is None or cached[0] != mtime:
        data = AutotuneCache(p).load()
        _FILE_CACHE[p] = (mtime, data)
    else:
        data = cached[1]
    entry = data["entries"].get(_entry_key(kernel, shape, dtype, _current_fp_key()))
    if entry is None or entry.get("fingerprint_key", "") != _current_fp_key():
        return None
    cfg = entry.get("config")
    return {k: int(v) for k, v in cfg.items()} if isinstance(cfg, dict) else None


def reset_runtime_caches() -> None:
    """Drop the per-process fingerprint + file memos (tests, forked envs)."""
    global _FP_KEY
    _FP_KEY = None
    _FILE_CACHE.clear()


# ---------------------------------------------------------------------------
# autotune@v1 component
# ---------------------------------------------------------------------------

_SWEEP_KNOBS = ("block_q", "block_k", "chunk", "block_w")

AUTOTUNE_SCHEMA = ComponentSchema(
    "autotune", 1,
    (
        InputSpec("kernel", str, required=True,
                  choices=("flash_attention", "rglru", "ssd")),
        InputSpec("prefix", str, default="autotune"),
        InputSpec("system", str, default="local", aliases=("machine",)),
        InputSpec("arch", str, default="kernel"),
        InputSpec("shape", str, default="",
                  help="cell shape label; defaults to the kernel shape key"),
        InputSpec("seed", int, default=0),
        InputSpec("record", bool, default=True),
        InputSpec("dtype", str, default="float32"),
        InputSpec("batch", int, default=1),
        InputSpec("heads", int, default=2),
        InputSpec("seq", int, default=128),
        InputSpec("head_dim", int, default=16),
        InputSpec("width", int, default=64),
        InputSpec("state", int, default=16),
        InputSpec("calls", int, default=3),
        InputSpec("warmup", int, default=1),
        InputSpec("interpret", bool, default=False,
                  help="run the pallas kernel in interpret mode (no TPU)"),
        InputSpec("block_q", list, default=(), element=int, wrap_scalar=True),
        InputSpec("block_k", list, default=(), element=int, wrap_scalar=True),
        InputSpec("chunk", list, default=(), element=int, wrap_scalar=True),
        InputSpec("block_w", list, default=(), element=int, wrap_scalar=True),
        InputSpec("confirm", int, default=3,
                  help="confirmation runs of the winner; their latencies are "
                       "pinned as the kernel_latency_s baseline"),
        InputSpec("baseline", bool, default=True,
                  help="pin the winner as the gate baseline"),
        InputSpec("cache", str, default="",
                  help=f"cache file path (default <store>/{CACHE_BASENAME})"),
        InputSpec("force", bool, default=False,
                  help="re-sweep even when the cache already holds this key"),
        PARALLELISM,
    ),
    description="sweep a pallas kernel's block grid, classify each point "
                "with roofline terms, promote the winner into the autotune "
                "cache and as a pinned latency baseline",
)


def _grid(inputs: Mapping[str, Any], knobs: Iterable[str]) -> List[Dict[str, int]]:
    axes = [(k, [int(v) for v in inputs.get(k) or ()]) for k in knobs]
    axes = [(k, vals) for k, vals in axes if vals]
    if not axes:
        raise PipelineError(
            f"autotune: no block values to sweep; give at least one of "
            f"{list(knobs)} a list of candidates")
    names = [k for k, _ in axes]
    return [dict(zip(names, combo))
            for combo in itertools.product(*(vals for _, vals in axes))]


def run_autotune(inputs: ComponentInputs, ctx: ComponentContext) -> Dict[str, Any]:
    # Local imports: autotune is registered at orchestrator import time, and
    # the heavy deps (jax via the harness, the orchestrator itself) must not
    # load just to validate a document.
    from repro.core.harness import BenchmarkSpec, Injections
    from repro.core.orchestrator import ExecutionOrchestrator
    from repro.core.regression import BaselineManager
    from repro.core.roofline import kernel_terms
    from repro.harnesses.kernel import KERNEL_KNOBS, KernelHarness
    from repro.hardware import CHIPS_BY_KIND

    kernel = inputs["kernel"]
    prefix = inputs.get("prefix") or "autotune"
    record = bool(inputs.get("record", True))
    dims = {k: int(inputs[k]) for k in
            ("batch", "heads", "seq", "head_dim", "width", "state")}
    harness = KernelHarness(
        kernel=kernel, dtype=inputs["dtype"], calls=int(inputs["calls"]),
        warmup=int(inputs["warmup"]), interpret=bool(inputs["interpret"]),
        use_cache=False, **dims)
    skey = harness.shape_key()
    dtype = inputs["dtype"]
    fp_key = fingerprint.key(fingerprint.capture())
    cache_path = Path(inputs.get("cache") or Path(ctx.store.root) / CACHE_BASENAME)
    cache = AutotuneCache(cache_path)

    base = {
        "component": "autotune",
        "kernel": kernel,
        "shape": skey,
        "dtype": dtype,
        "cache": {"path": str(cache_path)},
    }

    existing = cache.lookup(kernel, skey, dtype, fp_key)
    if existing is not None and not bool(inputs.get("force", False)):
        return {
            **base,
            "skipped": "cache-hit",
            "points": [],
            "winner": {"config": existing["config"],
                       "latency_s": existing.get("latency_s")},
            "cache": {**base["cache"], "hit": True, "updated": False},
        }

    grid = _grid(inputs, KERNEL_KNOBS[kernel])
    spec = BenchmarkSpec(
        arch=inputs.get("arch") or "kernel",
        shape=inputs.get("shape") or skey,
        system=inputs.get("system") or "local",
        seed=int(inputs.get("seed", 0)),
    )
    ex = ExecutionOrchestrator(
        inputs={"prefix": prefix, "record": record},
        harness=harness, store=ctx.store)

    points: List[Dict[str, Any]] = []
    errors: List[str] = []
    for cfg in grid:
        label = ".".join(f"{k}{v}" for k, v in sorted(cfg.items()))
        pt_spec = dataclasses.replace(spec, variant=f"{kernel}.{label}")
        res = ex.run_cell(pt_spec, injections=Injections(overrides=dict(cfg)))
        if res.error or res.report is None:
            errors.append(f"{label}: {res.error or 'no report'}")
            continue
        m = res.report.data[-1].metrics
        # Peaks of the device the point ran on; none known, no terms.
        chip = CHIPS_BY_KIND.get(res.report.parameter.get("device_kind", ""))
        points.append({
            "config": cfg,
            "latency_s": float(m["kernel_latency_s"]),
            "achieved_flops": float(m.get("achieved_flops", 0.0)),
            "achieved_bytes_per_s": float(m.get("achieved_bytes_per_s", 0.0)),
            **(kernel_terms(float(m.get("hlo_flops", 0.0)),
                            float(m.get("hlo_bytes", 0.0)), chip) if chip else {}),
        })

    if not points:
        return {**base, "points": [], "winner": None,
                "error": "all sweep points failed: " + "; ".join(errors)}

    best = min(points, key=lambda p: p["latency_s"])

    # Confirmation runs at the winning config: a spread for the pinned
    # baseline that reflects run-to-run noise, not the one lucky sample.
    confirm_n = max(0, int(inputs.get("confirm", 3)))
    confirm: List[float] = [best["latency_s"]]
    for i in range(confirm_n):
        c_spec = dataclasses.replace(
            spec, variant=f"{kernel}.winner", seed=spec.seed + 1 + i)
        res = ex.run_cell(c_spec, injections=Injections(overrides=dict(best["config"])))
        if not res.error and res.report is not None:
            confirm.append(float(res.report.data[-1].metrics["kernel_latency_s"]))

    entry = cache.put(
        kernel, skey, dtype, fp_key,
        best["config"],
        latency_s=best["latency_s"],
        dominant=best.get("dominant"),
        source=prefix,
    )

    baseline_info: Optional[Dict[str, Any]] = None
    if record and bool(inputs.get("baseline", True)):
        mgr = BaselineManager(ctx.store)
        mgr.pin(prefix, "kernel_latency_s", values=confirm,
                commit=f"autotune:{kernel}:{skey}")
        baseline_info = {
            "pinned": True,
            "source_prefix": prefix,
            "metric": "kernel_latency_s",
            "n_values": len(confirm),
        }

    out = {
        **base,
        "points": points,
        "winner": {"config": best["config"], "latency_s": best["latency_s"],
                   "dominant": best.get("dominant"), "confirm": confirm},
        "cache": {**base["cache"], "hit": False, "updated": True,
                  "updates": entry["updates"], "fingerprint_key": fp_key},
        "baseline": baseline_info,
    }
    if errors:
        out["point_errors"] = errors
    return out
