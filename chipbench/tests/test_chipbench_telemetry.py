"""The readers of the engine's recorder on a hand-built snapshot and record:
each gives its hand-worked value, leaves out the calls that ran under the
profiler, and reads nothing from a truncated window or a program without
the recorder.  A traced rehearsal reports them all."""

import json
import math
import os
import subprocess
import sys

import pytest

from chipbench import loader
from chipbench.metrics import _engine_window as E
from chipbench.run import Run
from repro.serve import telemetry as tel

CELL = "qwen3-4b-complete-poisson"
MS = 1_000_000   # ns
MODEL = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1, "head_dim": 4,
         "d_ff": 16, "vocab_size": 32}
NEW = ["ttft_p90_s.lat", "itl_p90_s.lat", "decode_slot_use.lat", "decode_host_share.lat",
       "decode_read_share.lat"]


def S(seq, name, start_ms, end_ms, parent=None, **ids):
    return tel.Span(seq, name, start_ms * MS, end_ms * MS, parent, ids)


def _snapshot():
    """In the ring's order (a span ends after its children): wave 0 is the
    warm-up (uid -1); the window's uids 0 and 1 are served in wave 10, uid 2
    in wave 20."""
    spans = [
        S(2, "engine.wait", 0.5, 0.9, 1),
        S(3, "engine.read", 0.9, 1, 1),
        S(1, "engine.step", 0, 1, 0, wave=0, step=1, pos=8, live=4),
        S(0, "engine.wave", 0, 1),
        S(12, "engine.wait", 102, 109, 11),
        S(13, "engine.read", 109, 110, 11),
        S(11, "engine.prefill", 100, 110, 10, wave=10, step=0),
        S(15, "engine.wait", 111, 118, 14),
        S(16, "engine.read", 118, 119, 14),
        S(14, "engine.step", 110, 120, 10, wave=10, step=1, pos=8, live=2),
        S(18, "engine.wait", 121, 131, 17),
        S(19, "engine.read", 131, 134, 17),
        S(17, "engine.step", 120, 135, 10, wave=10, step=2, pos=9, live=1),
        S(10, "engine.wave", 100, 135),
        S(21, "engine.prefill", 200, 205, 20, wave=20, step=0),
        S(23, "engine.wait", 206, 214, 22),
        S(24, "engine.read", 214, 215, 22),
        S(22, "engine.step", 205, 215, 20, wave=20, step=1, pos=8, live=1),
        S(20, "engine.wave", 200, 215),
    ]
    requests = [
        {"uid": -1, "wave": 0, "start_ns": 0, "token_ns": [1 * MS, 2 * MS]},
        {"uid": 0, "wave": 10, "start_ns": 100 * MS, "token_ns": [110 * MS, 120 * MS, 135 * MS]},
        {"uid": 1, "wave": 10, "start_ns": 100 * MS, "token_ns": [110 * MS, 120 * MS]},
        {"uid": 2, "wave": 20, "start_ns": 200 * MS, "token_ns": [205 * MS, 215 * MS]},
    ]
    return {"spans": spans, "requests": requests, "dropped": 0}


def _run(ok=(True, True, True), seconds=20.0):
    """A 20-s window: the profiler's stretch, from 6 s to 14 s, holds no call."""
    reqs = [{"due": d, "start": s, "ok": k}
            for (d, s), k in zip([(0.0, 0.5), (0.2, 0.5), (1.0, 3.0)], ok)]
    calls = [{"start": 0.5}, {"start": 3.0}]
    return Run(record={"seconds": seconds, "batch": 4, "prompt_len": 8, "requests": reqs,
                       "calls": calls},
               trace=None, model=MODEL, family=None, peak=None, chips=1, setup_s=0.0)


def _read(monkeypatch, snap, run):
    monkeypatch.setattr(tel, "snapshot", lambda: snap)
    readers = {m.name: m.read for m in loader.load_cell(CELL, trace=True).metrics}
    return {name: readers[name](run) for name in NEW}


# Worked by hand from _snapshot and _run:
# ttft: 0.5 + 0.010, 0.3 + 0.010, 2.0 + 0.005; p90 between the 2nd and 3rd.
# gaps: 0.010, 0.015 | 0.010 | 0.010; p90 between the 3rd and 4th.
# steps: live 2 + 1 + 1 of 3 x 4 slots; outside their waits 3 + 5 + 2 of
#   10 + 15 + 10 ms; their reads 1 + 3 + 1 ms.
WANT = {
    "ttft_p90_s.lat": 0.51 + 0.8 * (2.005 - 0.51),
    "itl_p90_s.lat": 0.010 + 0.7 * 0.005,
    "decode_slot_use.lat": 100 * 4 / 12,
    "decode_host_share.lat": 100 * 10 / 35,
    "decode_read_share.lat": 100 * 5 / 35,
}


def test_readers_by_hand(monkeypatch):
    got = _read(monkeypatch, _snapshot(), _run())
    assert got == pytest.approx(WANT, rel=1e-9)


def test_calls_under_the_profiler_are_left_out(monkeypatch):
    """A slow wave served at 7 s, inside the profiler's stretch, moves nothing."""
    snap, run = _snapshot(), _run()
    snap["spans"] += [S(32, "engine.wait", 7000, 7001, 31), S(33, "engine.read", 7001, 7500, 31),
                      S(31, "engine.step", 7000, 7500, 30, wave=30, step=1, pos=8, live=4),
                      S(30, "engine.wave", 7000, 7500)]
    snap["requests"].append({"uid": 3, "wave": 30, "start_ns": 7000 * MS,
                             "token_ns": [7000 * MS, 7500 * MS]})
    run.record["requests"].append({"due": 6.9, "start": 7.0, "ok": True})
    run.record["calls"].append({"start": 7.0})
    assert _read(monkeypatch, snap, run) == pytest.approx(WANT, rel=1e-9)


@pytest.mark.parametrize("seconds,starts,want", [
    (20.0, [0.5, 3.0, 7.0, 9.0, 14.0, 15.0], (7.0, 14.0)),   # 6 s to 14 s
    (1.0, [0.0, 3.0, 6.0], (3.0, 6.0)),    # the first call at or after 0.3 s runs past 0.8 s
    (20.0, [0.5, 3.0], (math.inf, math.inf)),                # no call after 6 s
])
def test_profiled_calls_follow_the_tracer(seconds, starts, want):
    assert E.profiled({"seconds": seconds, "calls": [{"start": t} for t in starts]}) == want


def test_a_failed_request_is_infinitely_late(monkeypatch):
    snap = _snapshot()
    snap["requests"] = [r for r in snap["requests"] if r["uid"] != 2]
    got = _read(monkeypatch, snap, _run(ok=(True, True, False)))
    assert got["ttft_p90_s.lat"] is None and got["itl_p90_s.lat"] is None


def test_drops_before_the_window_are_harmless(monkeypatch):
    snap = _snapshot()
    snap["dropped"] = 5
    assert _read(monkeypatch, snap, _run()) == pytest.approx(WANT, rel=1e-9)


@pytest.mark.parametrize("cut", ["request", "span"])
def test_truncated_window_reads_nothing(monkeypatch, cut):
    snap = _snapshot()
    if cut == "request":        # a served request's record fell out of the ring
        snap["requests"] = [r for r in snap["requests"] if r["uid"] != 1]
    else:                       # the ring let go of spans of the window
        snap["spans"] = snap["spans"][5:]
        snap["dropped"] = 5
    assert set(_read(monkeypatch, snap, _run()).values()) == {None}


def test_program_without_the_recorder_reads_nothing(monkeypatch):
    import repro.serve

    monkeypatch.delattr(repro.serve, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.serve.telemetry", None)
    assert set(_read(monkeypatch, _snapshot(), _run()).values()) == {None}


def test_traced_rehearsal_reports_the_engine_metrics():
    proc = subprocess.run(
        [sys.executable, str(loader.ROOT / "chipbench" / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 777), "--seconds", "1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    reported = set(out["metrics"])
    assert set(NEW) <= reported
    assert 0 < out["metrics"]["decode_slot_use.lat"]["value"] <= 100
    host = out["metrics"]["decode_host_share.lat"]["value"]
    assert 0 <= out["metrics"]["decode_read_share.lat"]["value"] <= host <= 100
