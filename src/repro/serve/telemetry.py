"""Spans and per-request token times of the serving engine, kept in memory
for the whole process.

``span(name, **ids)`` marks one piece of the engine's work twice: as a
``jax.profiler.TraceAnnotation`` of that name with ``ids`` as its stats, so
that a profiler trace holds it beside the device's ops on the trace's
clock, and as a ``Span`` in a bounded ring here, timed on
``time.perf_counter_ns``.  It yields the open span (``end_ns`` still
``None``), whose ``seq`` numbers it in the process and whose ``start_ns``
is its start.  A span's ``parent`` is the ``seq`` of the span that encloses
it on the same thread.  The trace and the ring are joined by name and ids:
trace times are offsets from the start of the profiling session.

``request(uid, wave, start_ns)`` opens a request's record; the engine
appends to its ``token_ns`` the time each token is in host hands.
``snapshot()`` copies it all, with ``dropped``, the number of records the
full rings let go (the oldest go first); ``reset()`` empties it.

The recorder is always on and costs a few microseconds a decode step.  It
belongs to the process, not to an engine, so what it holds outlives the
engine that wrote it.  ``python -m repro.launch.serve`` prints its
tokens/s, decode slot use and token-time percentiles from ``snapshot()``.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Dict, NamedTuple, Optional

import jax

CAPACITY = 1 << 16


class Span(NamedTuple):
    seq: int
    name: str
    start_ns: int
    end_ns: Optional[int]
    parent: Optional[int]
    ids: Dict[str, object]


class Recorder:
    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._local = threading.local()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._spans: collections.deque = collections.deque(maxlen=self.capacity)
            self._requests: collections.deque = collections.deque(maxlen=self.capacity)
            self._dropped = 0
            self._seq = itertools.count()

    def _append(self, ring: collections.deque, record) -> None:
        with self._lock:
            if len(ring) == ring.maxlen:
                self._dropped += 1
            ring.append(record)

    @contextlib.contextmanager
    def span(self, name: str, **ids):
        stack = self._local.__dict__.setdefault("stack", [])
        parent = stack[-1] if stack else None
        opened = Span(next(self._seq), name, time.perf_counter_ns(), None, parent, ids)
        stack.append(opened.seq)
        try:
            with jax.profiler.TraceAnnotation(name, **ids):
                yield opened
        finally:
            end = time.perf_counter_ns()
            stack.pop()
            self._append(self._spans, opened._replace(end_ns=end))

    def request(self, uid: int, wave: int, start_ns: int) -> dict:
        """A request's record; its ``token_ns`` is the caller's to fill."""
        rec = {"uid": uid, "wave": wave, "start_ns": start_ns, "token_ns": []}
        self._append(self._requests, rec)
        return rec

    def snapshot(self) -> dict:
        with self._lock:
            return {"spans": list(self._spans),
                    "requests": [dict(r, token_ns=list(r["token_ns"])) for r in self._requests],
                    "dropped": self._dropped}


RECORDER = Recorder()
span = RECORDER.span
request = RECORDER.request
snapshot = RECORDER.snapshot
reset = RECORDER.reset
