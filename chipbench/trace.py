"""The profiler trace of one stretch of the window, and its reduction to
device busy time, per-op time, kernel time and the longest idle gaps named
by what the host was doing.

The drivers mark their host work with ``jax.profiler.TraceAnnotation``
named ``chipbench.*``; the tracer marks the traced stretch itself with
``chipbench.traced_window``.  Device time comes from each device plane's
``XLA Ops`` line, clipped to that stretch; per-op and kernel time count
only ops that hold no other (a loop's event spans its body's),
named by the HLO instruction (``%fusion.12``).  An idle gap is named by
the innermost host span around its middle on the thread that traced:
a ``chipbench.*`` mark or one the runtime records (``np.asarray`` of a
device array is the host waiting for a result).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import List, Optional, Sequence, Tuple

WINDOW_MARK = "chipbench.traced_window"
HOST_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)")


@dataclasses.dataclass
class Line:
    name: str
    events: List[Tuple[str, float, float]]   # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def load_planes(path: str) -> List[Plane]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [Plane(p.name, [Line(l.name, [(e.name, e.start_ns, e.duration_ns) for e in l.events])
                           for l in p.lines])
            for p in data.planes]


class Tracer:
    """Traces from the first boundary at or after ``start_s`` into the window
    to the first boundary at or after ``start_s + seconds``.  Drivers call
    ``boundary`` between the calls they make; ``close`` stops a trace that is
    still running."""

    def __init__(self, *, enabled: bool, start_s: float, seconds: float):
        self.enabled = enabled
        self.start_s, self.seconds = start_s, seconds
        self.dir: Optional[str] = None
        self._mark = None

    def boundary(self, t_rel: float) -> None:
        if not self.enabled:
            return
        if self.dir is None and t_rel >= self.start_s:
            import jax

            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
            self._mark.__enter__()
        elif self._mark is not None and t_rel >= self.start_s + self.seconds:
            self.close()

    def close(self) -> None:
        if self._mark is None:
            return
        import jax

        self._mark.__exit__(None, None, None)
        self._mark = None
        jax.profiler.stop_trace()

    def summary(self, **kw) -> Optional[dict]:
        """Reduce the trace, then delete it; None if nothing was traced."""
        self.close()
        if self.dir is None:
            return None
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
            if not files:
                return None
            return summarize(load_planes(files[0]), **kw)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float):
    """Idle stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _host_name(host: List[Tuple[str, float, float]], t: float) -> str:
    """The innermost host span covering ``t`` on the thread that traced."""
    best = None
    for name, s, d in host:
        if s <= t <= s + d and name != WINDOW_MARK and (best is None or d < best[1]):
            best = (name, d)
    if best is None:
        return "no_host_span"
    return best[0][len(HOST_PREFIX):] if best[0].startswith(HOST_PREFIX) else best[0]


def _short(op: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return op.split(" = ", 1)[0]


def _leaves(ops):
    """The ops that hold no other op: a loop's own event spans its body's."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(ops) if i + 1 == len(ops) or ops[i + 1][1] >= e[2]]


def summarize(planes: List[Plane], *, kernel: Optional[str] = None, top: int = 10) -> Optional[dict]:
    """Busy and kernel time per device over the traced window,
    the ops that took most device time, and the longest idle gaps of the
    first device named by the host span in them.  Times in seconds."""
    host = next((l.events for p in planes if p.name.startswith("/host:") for l in p.lines
                 if any(e[0] == WINDOW_MARK for e in l.events)), None)
    if host is None:
        return None
    _, lo, dur = next(e for e in host if e[0] == WINDOW_MARK)
    hi = lo + dur
    devices = sorted((int(m.group(2)), p) for p in planes if (m := _DEVICE.match(p.name)))
    per_device, op_time = [], {}
    for _, p in devices:
        ops = [ev for l in p.lines if l.name == OPS_LINE for ev in _clip(l.events, lo, hi)]
        if not ops:
            continue
        leaves = _leaves(ops)
        for name, a, b in leaves:
            op_time[_short(name)] = op_time.get(_short(name), 0.0) + (b - a)
        per_device.append({
            "busy": union_length([(a, b) for _, a, b in ops]),
            "kernel": sum(b - a for n, a, b in leaves if kernel and kernel in _short(n)),
            "kernel_events": sum(1 for n, _, _ in leaves if kernel and kernel in _short(n)),
            "intervals": [(a, b) for _, a, b in ops],
        })
    if not per_device:
        return None
    n = len(per_device)
    idle = sorted(gaps(per_device[0]["intervals"], lo, hi), key=lambda g: g[0] - g[1])[:top]
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "devices": n,
        "busy_s": sum(d["busy"] for d in per_device) / n * ns,
        "kernel_s": sum(d["kernel"] for d in per_device) / n * ns,
        "kernel_events": sum(d["kernel_events"] for d in per_device) / n,
        "device_ops": [[k, v / n * ns] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_name(host, (s + e) / 2), (e - s) * ns] for s, e in idle],
    }
