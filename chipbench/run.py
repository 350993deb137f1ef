#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python3 chipbench/run.py --workload <cell> ... --rehearse

A run makes the cell's weights and traffic from ``--seed``, warms every
shape the window uses (set-up), measures for ``--seconds``, serves what is
still due, then frees the program's state and compares what the timed
path produced with the plain reference (the ``reference_logits`` of the
cell's family).  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics, read from a profiler
trace of a stretch of the window), ``device`` and, last, ``checks``: each
number compared with its limit.  The checks are also the last lines of
standard error.  Lines before the last report set-up phases, how late the
load generator ran, compiles inside the window and device memory; they are
not metrics.

It runs on the TPU it is started on and fails, printing no result, where
JAX finds no TPU, fewer chips than the cell asks for, or a device kind
missing from ``chipbench/peaks.json``.  ``--rehearse`` runs the same path on
the CPU at the configuration's rehearsal sizes, with Pallas kernels in
interpret mode; it is for the tests and never reports a TPU metric.

The traced stretch starts at 30% of the window and ends at the first call
boundary 8 seconds later (or at half the window, if that is shorter).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import loader  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_START_FRAC, TRACE_SECONDS = 0.3, 8.0


def info(tag: str, **kv) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v!r}" for k, v in kv.items()), flush=True)


@dataclasses.dataclass
class Run:
    """What a metric reader reads: the driver's record of the window, the
    reduced trace (``None`` untraced), the sizes and the family that counts
    their operations, the chip's peaks (``None`` when rehearsing), and the
    set-up time."""
    record: dict
    trace: Optional[dict]
    model: dict
    family: ModuleType
    peak: Optional[dict]
    chips: int
    setup_s: float


class CompileCounter:
    def __init__(self):
        self.on, self.count = False, 0

    def __call__(self, event, *_a, **_kw):
        if self.on and event == BACKEND_COMPILE:
            self.count += 1


class NoDevice(Exception):
    """The devices the cell asks for are not there."""


def open_devices(cell, rehearse: bool):
    """The cell's devices and their published peaks (``None`` rehearsing).

    Raises ``NoDevice`` where JAX finds no TPU (no CPU when rehearsing),
    fewer devices than the cell's chips, or a kind without peaks.  Off
    rehearsal, turns on the persistent compile cache in the checkout."""
    import jax

    devices = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devices[0].platform != want or len(devices) < cell.chips:
        raise NoDevice(f"{cell.name} needs {cell.chips} {want} device(s); JAX found "
                       f"{len(devices)} {devices[0].platform!r}")
    peak = None
    if not rehearse:
        try:
            peak = loader.peaks_for(devices[0].device_kind)
        except KeyError as e:
            raise NoDevice(e.args[0]) from None
        from repro.launch.cache import enable_compile_cache

        enable_compile_cache()
        # Every program, however quick to compile, is kept: only the first
        # run of a cell in a checkout compiles.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    info("device", platform=devices[0].platform, kind=devices[0].device_kind,
         count=len(devices), jax=jax.__version__, since_start_s=time.perf_counter() - T_START)
    return devices[: cell.chips], peak


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return max(peaks) if all(p is not None for p in peaks) else None


def check_limits(workload: dict, rehearse: bool) -> dict:
    """The cell's limits, or at rehearsal sizes the rehearsal's own."""
    over = workload.get("rehearse", {}).get("check", {}) if rehearse else {}
    return {**workload["check"]["limits"], **over.get("limits", {})}


def check(cell, model: dict, seed: int, samples, max_out: int, rehearse: bool) -> dict:
    """Each number compared, with its limit: the reference's full forward
    pass over every drawn sequence, against the tokens served."""
    from chipbench import program as PG
    from chipbench import reference as R

    limits = check_limits(cell.workload, rehearse)
    if any(s.served is None for s in samples):
        return {k: {"value": None, "limit": v} for k, v in limits.items()}
    tokens, rows = PG.reference_inputs(samples, max_out)
    ref = cell.family.reference_logits(model, seed, tokens, rows)
    got = R.compare([s.served for s in samples], ref)
    return {k: {"value": got.get(k), "limit": v} for k, v in limits.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal sizes, interpret-mode kernels (tests only)")
    args = ap.parse_args(argv)

    cell = loader.load_cell(args.workload, trace=bool(args.trace))
    try:
        devices, peak = open_devices(cell, args.rehearse)
    except NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax

    kind = devices[0].device_kind
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    from chipbench.trace import Tracer

    model = cell.family.sizes(cell.config, args.rehearse)
    driver = cell.driver(cell, seed=args.seed, seconds=args.seconds, rehearse=args.rehearse)
    driver.setup()
    setup_s = time.perf_counter() - T_START
    info("setup", setup_s=setup_s)

    tracer = Tracer(enabled=bool(args.trace), start_s=TRACE_START_FRAC * args.seconds,
                    seconds=min(TRACE_SECONDS, args.seconds / 2))
    counter.on = True
    record = driver.run(tracer)
    counter.on = False
    info("window", compiles_in_window=counter.count,
         generator_lag_s=record.get("generator_lag_s"))
    trace = tracer.summary(kernel=cell.family.KERNEL)
    mem = _peak_bytes(devices)
    info("memory", peak_bytes_in_use=mem)

    samples = driver.release(record)
    t_check = time.perf_counter()
    checks = check(cell, model, args.seed, samples, driver.max_out, args.rehearse)
    info("check", reference_s=time.perf_counter() - t_check,
         run_s=time.perf_counter() - T_START)
    attempted = len(record["requests"])
    failed = sum(not r["ok"] for r in record["requests"])
    correct = (failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in checks.values()))

    run = Run(record=record, trace=trace, model=model, family=cell.family, peak=peak,
              chips=cell.chips, setup_s=setup_s)
    metrics = {}
    for m in cell.metrics:
        v = m.read(run)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(jax.devices()),
              "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        out["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    out["checks"] = checks
    for name, c in checks.items():
        print(f"[check] {name} value={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:  # noqa: BLE001 — any failure ends the run without a result
        traceback.print_exc()
        code = 1
    sys.exit(code)
