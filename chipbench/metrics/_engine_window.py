"""What the engine's recorder (``repro.serve.telemetry``) holds of a run's
window, for the readers of its spans and token times.  Not a metric: no
entry of ``BENCHMARK.json`` names this file.

The window's requests are uids ``0 .. len(record["requests"]) - 1``, as
``traffic.open_loop`` numbers them (the warm-up's are negative); a uid's
latest record counts.  Left out are those served by the calls that ran
under the profiler (``profiled``), whose steps the profiler slows, but not
a failed request.  The window's waves are those that hold its requests (a
request's ``wave`` is the ``seq`` of its ``engine.wave`` span), and its
spans are those waves, the spans that name them in their ``wave`` id, and
their children.  Times in seconds.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

from chipbench.run import TRACE_SECONDS, TRACE_START_FRAC


@dataclasses.dataclass
class Window:
    uids: List[int]               # the window's requests, failed ones too
    requests: Dict[int, dict]     # uid -> {"uid", "wave", "start_ns", "token_ns"}
    spans: list                   # repro.serve.telemetry.Span

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def children(self, name: str) -> dict:
        """The spans ``name`` by the ``seq`` of their parent."""
        return {s.parent: s for s in self.named(name)}


def seconds(span) -> float:
    return (span.end_ns - span.start_ns) * 1e-9


def profiled(record) -> Tuple[float, float]:
    """The starts of the calls that ran under the profiler, ``[lo, hi)``
    after the window opened, as ``run.py``'s tracer picks them: from the
    first call at or after its stretch's start, up to the first call after
    that one at or after its stretch's end."""
    start = TRACE_START_FRAC * record["seconds"]
    end = start + min(TRACE_SECONDS, record["seconds"] / 2)
    starts = sorted(c["start"] for c in record["calls"])
    lo = next((s for s in starts if s >= start), math.inf)
    hi = next((s for s in starts if s > lo and s >= end), math.inf)
    return lo, hi


def window(run) -> Optional[Window]:
    """``None`` where the program has no recorder, where no request is
    left, or where a record of the window was dropped: a served request
    without its record, or a full ring that let go of a span that ended
    after the window's first call began."""
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    snap = telemetry.snapshot()
    lo, hi = profiled(run.record)
    served = run.record["requests"]
    uids = [uid for uid, r in enumerate(served) if not r["ok"] or not lo <= r["start"] < hi]
    keep = set(uids)
    requests = {r["uid"]: r for r in snap["requests"] if r["uid"] in keep}
    if not requests or any(served[uid]["ok"] and uid not in requests for uid in uids):
        return None
    first_ns = min(r["start_ns"] for r in requests.values())
    if snap["dropped"] and snap["spans"] and snap["spans"][0].end_ns > first_ns:
        return None
    waves = {r["wave"] for r in requests.values()}
    own = waves | {s.seq for s in snap["spans"] if s.ids.get("wave") in waves}
    spans: List = [s for s in snap["spans"] if s.seq in own or s.parent in own]
    return Window(uids, requests, spans)
