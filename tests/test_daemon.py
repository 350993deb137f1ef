"""Tests for the continuous campaign daemon: lag-driven refresh (only the
stale slice re-executes, proven from the store manifest), downstream and
watermark triggers, crash-restart resume (state file and signature
recovery), graceful SIGTERM drain, and the status view."""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from repro.core.component import PipelineError
from repro.core.daemon import (
    CampaignDaemon,
    SchedulePolicy,
    daemon_status,
    payload_signature,
    render_status,
    report_signature,
    _last_seq,
)
from repro.core.harness import BenchmarkSpec
from repro.core.orchestrator import ExecutionOrchestrator
from repro.core.store import ResultStore
from repro.core.synthetic import SpinHarness

REPO = Path(__file__).resolve().parent.parent


def _write_doc(path, body):
    path.write_text(body)
    return str(path)


def _two_prefix_doc(tmp_path, *, target_lag=30, triggers="[lag]", extra=""):
    """schedule + one execution cell in each of two prefixes — staleness can
    be proven per cell from each prefix's manifest independently."""
    return _write_doc(tmp_path / "doc.yml", f"""\
include:
  - component: schedule@v1
    inputs:
      target_lag: {target_lag}
      triggers: {triggers}
{extra}  - component: execution@v4
    inputs:
      prefix: "contA"
      arch: "archA"
      shape: "train_4k"
      system: "sysA"
  - component: execution@v4
    inputs:
      prefix: "contB"
      arch: "archB"
      shape: "train_4k"
      system: "sysA"
""")


def _daemon(store, doc, **kw):
    kw.setdefault("harness", SpinHarness(iters=50))
    kw.setdefault("workers", 1)
    return CampaignDaemon(store, [doc], **kw)


def _key_for(daemon, prefix):
    doc = daemon.documents[0]
    keys = [k for k, p in doc.cells.items() if p["prefix"] == prefix]
    assert len(keys) == 1
    return keys[0]


# ---------------------------------------------------------------------------
# lag trigger: exactly the stale slice, manifest-proven, across ticks
# ---------------------------------------------------------------------------

def test_lag_refreshes_exactly_the_stale_cells_across_ticks(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _two_prefix_doc(tmp_path, target_lag=30)
    d = _daemon(store, doc)
    key_a, key_b = _key_for(d, "contA"), _key_for(d, "contB")

    # Tick 1: nothing has ever run — both cells refresh.
    s1 = d.tick(now=1000.0)["documents"][doc]
    assert s1["stale"] == {key_a: "never-run", key_b: "never-run"}
    assert sorted(s1["refreshed"]) == sorted([key_a, key_b])
    assert _last_seq(store, "contA") == 0 and _last_seq(store, "contB") == 0

    # Tick 2, inside the lag budget: nothing is stale, nothing re-executes —
    # the manifest is the proof (no new sequence in either prefix).
    s2 = d.tick(now=1010.0)["documents"][doc]
    assert s2["stale"] == {} and s2["refreshed"] == []
    assert sorted(s2["fresh"]) == sorted([key_a, key_b])
    assert _last_seq(store, "contA") == 0 and _last_seq(store, "contB") == 0

    # Age only cell A past target_lag (B was refreshed more recently).
    d.state["documents"][doc]["cells"][key_b]["last_refresh"] = 1020.0
    s3 = d.tick(now=1035.0)["documents"][doc]
    assert s3["stale"] == {key_a: "lag"}
    assert s3["refreshed"] == [key_a] and s3["fresh"] == [key_b]
    # Manifest + watermark proof: exactly one new entry, in A's prefix only.
    assert _last_seq(store, "contA") == 1
    assert _last_seq(store, "contB") == 0
    assert store.columnar.watermark("contA") == 1
    assert store.columnar.watermark("contB") == 0


def test_max_cells_per_tick_bounds_one_ticks_work(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _write_doc(tmp_path / "doc.yml", """\
include:
  - component: schedule@v1
    inputs:
      target_lag: 30
      triggers: [lag]
      max_cells_per_tick: 1
  - component: execution@v4
    inputs:
      prefix: "cap"
      arch: "a1"
      shape: "train_4k"
      system: "sysA"
  - component: execution@v4
    inputs:
      prefix: "cap"
      arch: "a2"
      shape: "train_4k"
      system: "sysA"
  - component: execution@v4
    inputs:
      prefix: "cap"
      arch: "a3"
      shape: "train_4k"
      system: "sysA"
""")
    d = _daemon(store, doc)
    counts = []
    for i in range(4):
        s = d.tick(now=1000.0 + i)["documents"][doc]
        counts.append((len(s["stale"]), len(s["refreshed"])))
    # The backlog drains one cell per tick; un-refreshed cells stay stale.
    assert counts == [(3, 1), (2, 1), (1, 1), (0, 0)]
    assert _last_seq(store, "cap") == 2  # three cells, one entry each


# ---------------------------------------------------------------------------
# restart resume: state file, then signature recovery from the store
# ---------------------------------------------------------------------------

def test_restart_with_state_never_reruns_fresh_cells(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _two_prefix_doc(tmp_path, target_lag=30)
    d1 = _daemon(store, doc)
    d1.tick(now=1000.0)
    assert _last_seq(store, "contA") == 0 and _last_seq(store, "contB") == 0

    # A new daemon instance (restart) resumes from daemon_state.json.
    d2 = _daemon(store, doc)
    assert d2.ticks == 1  # tick counter survived
    s = d2.tick(now=1010.0)["documents"][doc]
    assert s["stale"] == {} and s["refreshed"] == []
    assert _last_seq(store, "contA") == 0 and _last_seq(store, "contB") == 0
    # Once the budget expires, the restarted daemon picks up where it left.
    s = d2.tick(now=1100.0)["documents"][doc]
    assert set(s["stale"].values()) == {"lag"}
    assert _last_seq(store, "contA") == 1 and _last_seq(store, "contB") == 1


def test_restart_without_state_recovers_from_report_signatures(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _two_prefix_doc(tmp_path, target_lag=60)
    d1 = _daemon(store, doc)
    d1.tick(now=1000.0)

    # Crash restart with the state file gone: the daemon matches stored
    # reports against each cell's signature instead of re-running them.
    # (SpinHarness pins experiment timestamps to 1000.0, so recovered
    # last-refresh times are deterministic here.)
    os.unlink(d1.state_path)
    d2 = _daemon(store, doc)
    s = d2.tick(now=1010.0)["documents"][doc]
    assert s["stale"] == {} and s["refreshed"] == []
    assert _last_seq(store, "contA") == 0 and _last_seq(store, "contB") == 0
    # The recovery was persisted: per-cell times are back in the state file.
    saved = json.loads(Path(d2.state_path).read_text())
    cells = saved["documents"][doc]["cells"]
    assert {c["last_refresh"] for c in cells.values()} == {1000.0}

    # And the recovered times still age out normally.
    s = d2.tick(now=1100.0)["documents"][doc]
    assert set(s["stale"].values()) == {"lag"}
    assert _last_seq(store, "contA") == 1


def test_payload_and_report_signatures_agree(tmp_path):
    """The recovery path's core invariant: the signature computed from a
    queue payload equals the one recomputed from the report that executing
    the payload persists."""
    from repro.core.workers import cell_payload

    store = ResultStore(tmp_path / "s")
    spec = BenchmarkSpec(arch="archX", shape="train_4k", system="sysA")
    ex = ExecutionOrchestrator(inputs={"prefix": "sig"},
                               harness=SpinHarness(iters=50), store=store)
    ex.run_collection([spec])
    payload = cell_payload(spec, {"prefix": "sig"})
    report = store.query("sig")[0]
    assert payload_signature(payload) == report_signature("sig", report)


# ---------------------------------------------------------------------------
# downstream + watermark triggers
# ---------------------------------------------------------------------------

def test_downstream_consumer_runs_only_when_inputs_advance(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _write_doc(tmp_path / "doc.yml", """\
include:
  - component: schedule@v1
    inputs:
      target_lag: 30
      triggers: [lag, downstream]
  - component: execution@v4
    inputs:
      prefix: "cont"
      arch: "archA"
      shape: "train_4k"
      system: "sysA"
  - component: campaign-report@v1
    inputs:
      metric: "spin_result"
      prefixes: ["cont"]
""")
    d = _daemon(store, doc)
    consumer_key = d.documents[0].consumers[0][0]

    s1 = d.tick(now=1000.0)["documents"][doc]
    assert len(s1["refreshed"]) == 1
    assert s1["consumers_run"] == [consumer_key]  # inputs advanced from empty

    # Nothing stale, inputs unchanged: the analysis is NOT recomputed.
    s2 = d.tick(now=1010.0)["documents"][doc]
    assert s2["refreshed"] == [] and s2["consumers_run"] == []

    # Producer refresh advances the consumed prefix -> consumer re-runs.
    s3 = d.tick(now=1040.0)["documents"][doc]
    assert len(s3["refreshed"]) == 1
    assert s3["consumers_run"] == [consumer_key]
    cst = d.state["documents"][doc]["consumers"][consumer_key]
    assert cst["run_count"] == 2 and cst["cursors"] == {"cont": 1}


def test_watermark_trigger_fires_on_external_store_writes(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _write_doc(tmp_path / "doc.yml", """\
include:
  - component: schedule@v1
    inputs:
      target_lag: 100000
      triggers: [watermark]
      watch: ["ext"]
  - component: execution@v4
    inputs:
      prefix: "cont"
      arch: "archA"
      shape: "train_4k"
      system: "sysA"
""")
    d = _daemon(store, doc)
    key = _key_for(d, "cont")
    s1 = d.tick(now=1000.0)["documents"][doc]
    assert s1["stale"] == {key: "never-run"}
    s2 = d.tick(now=1001.0)["documents"][doc]
    assert s2["stale"] == {}

    # Another writer (a CI job sharing the store) lands a report upstream.
    ex = ExecutionOrchestrator(inputs={"prefix": "ext"},
                               harness=SpinHarness(iters=50), store=store)
    ex.run_collection([BenchmarkSpec(arch="up", shape="train_4k",
                                     system="sysA")])
    s3 = d.tick(now=1002.0)["documents"][doc]
    assert s3["stale"] == {key: "watermark:ext"}
    assert s3["refreshed"] == [key]
    # Acted-on marks advance: the same upstream write never fires twice.
    s4 = d.tick(now=1003.0)["documents"][doc]
    assert s4["stale"] == {}


# ---------------------------------------------------------------------------
# schedule@v1 schema + one-shot no-op
# ---------------------------------------------------------------------------

def test_unknown_trigger_is_a_parse_time_error(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _two_prefix_doc(tmp_path, triggers="[lag, hourly]")
    with pytest.raises(PipelineError, match="hourly"):
        _daemon(store, doc)


def test_daemon_override_beats_document_target_lag(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _two_prefix_doc(tmp_path, target_lag=30)
    d = _daemon(store, doc, target_lag=1000.0)
    d.tick(now=1000.0)
    # 30s budget would mark both stale; the 1000s override keeps them fresh.
    s = d.tick(now=1100.0)["documents"][doc]
    assert s["stale"] == {}
    assert SchedulePolicy.from_calls(d.documents[0].calls).target_lag == 30.0


def test_schedule_component_is_a_noop_under_one_shot_run(tmp_path):
    from repro.core.api import Campaign

    doc = _two_prefix_doc(tmp_path, target_lag=30)
    c = Campaign(tmp_path / "s", harness=SpinHarness(iters=50))
    summaries = c.run(doc)
    sched = [s for s in summaries if s.get("component") == "schedule"]
    assert len(sched) == 1
    assert sched[0]["target_lag"] == 30.0 and "daemon" in sched[0]["note"]
    # The producers still executed normally around it.
    assert _last_seq(ResultStore(tmp_path / "s"), "contA") == 0


# ---------------------------------------------------------------------------
# status view
# ---------------------------------------------------------------------------

def test_daemon_status_reports_lag_and_due(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _two_prefix_doc(tmp_path, target_lag=30)
    d = _daemon(store, doc)
    d.tick(now=1000.0)
    fresh = daemon_status(store, [doc], now=1010.0)
    cells = fresh["documents"][doc]["cells"]
    assert [c["due"] for c in cells] == [False, False]
    assert all(c["lag_s"] == pytest.approx(10.0) for c in cells)
    assert all(c["refresh_count"] == 1 for c in cells)
    stale = daemon_status(store, [doc], now=1100.0)
    assert all(c["due"] for c in stale["documents"][doc]["cells"])
    text = render_status(stale)
    assert "contA/archA" in text and "DUE" in text


# ---------------------------------------------------------------------------
# service loop: SIGTERM graceful drain (real process), CLI status
# ---------------------------------------------------------------------------

def _cli_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    # The CLI keeps a compile cache in the checkout; tests leave it off.
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return env


def test_sigterm_drains_and_persists_resumable_state(tmp_path):
    doc = _write_doc(tmp_path / "doc.yml", """\
include:
  - component: schedule@v1
    inputs:
      target_lag: 3600
      triggers: [lag]
  - component: execution@v4
    inputs:
      prefix: "svc"
      arch: "starcoder2-3b"
      shape: "train_4k"
      system: "cpu-smoke"
""")
    store_root = tmp_path / "store"
    state = store_root / "daemon_state.json"
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro", "daemon", doc,
         "--store", str(store_root), "--interval", "0.3"],
        env=_cli_env(), cwd=str(REPO),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if json.loads(state.read_text()).get("ticks", 0) >= 1:
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.1)
        else:
            raise AssertionError("daemon never completed a tick")
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=30) == 0  # graceful drain, not a crash
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)

    saved = json.loads(state.read_text())  # valid JSON, resumable
    assert saved["version"] == 1 and saved["ticks"] >= 1
    cells = saved["documents"][doc]["cells"]
    assert len(cells) == 1
    (cell,) = cells.values()
    assert cell["refresh_count"] >= 1 and cell["last_error"] is None
    # The work actually landed in the store exactly once per refresh.
    store = ResultStore(store_root)
    assert len(store.query("svc")) == cell["refresh_count"]

    # daemon-status reads the persisted state without a running daemon.
    out = subprocess.run(
        [sys.executable, "-m", "repro", "daemon-status", doc,
         "--store", str(store_root), "--json"],
        env=_cli_env(), cwd=str(REPO), capture_output=True, text=True,
        timeout=60)
    assert out.returncode == 0
    status = json.loads(out.stdout)
    assert status["ticks"] == saved["ticks"]
    assert status["queue_depth"] == 0
    (cell_status,) = status["documents"][doc]["cells"]
    assert cell_status["refresh_count"] == cell["refresh_count"]


# ---------------------------------------------------------------------------
# quarantine circuit-breaker
# ---------------------------------------------------------------------------

class _PoisonHarness(SpinHarness):
    """Raises on every cell — models a persistently failing benchmark."""

    def run(self, spec, injections=None):
        raise RuntimeError(f"poisoned cell {spec.cell}")


def _poison_doc(tmp_path, *, quarantine_after=2):
    return _write_doc(tmp_path / "doc.yml", f"""\
include:
  - component: schedule@v1
    inputs:
      target_lag: 30
      triggers: [lag]
      quarantine_after: {quarantine_after}
  - component: execution@v4
    inputs:
      prefix: "poison"
      arch: "archA"
      shape: "train_4k"
      system: "sysA"
""")


def test_consecutive_failures_quarantine_the_cell(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _poison_doc(tmp_path, quarantine_after=2)
    d = _daemon(store, doc, harness=_PoisonHarness())
    key = _key_for(d, "poison")

    s1 = d.tick(now=1000.0)["documents"][doc]
    assert s1["refreshed"] == [key]
    cell_st = d.state["documents"][doc]["cells"][key]
    assert cell_st["fail_streak"] == 1 and "quarantined" not in cell_st

    s2 = d.tick(now=1040.0)["documents"][doc]  # aged past target_lag
    assert s2["refreshed"] == [key]
    cell_st = d.state["documents"][doc]["cells"][key]
    assert cell_st["fail_streak"] == 2
    assert "poisoned cell" in cell_st["quarantined"]["reason"]
    assert len(cell_st["history"]) == 2

    # Parked: the cell is never stale again, however far it ages.
    s3 = d.tick(now=9000.0)["documents"][doc]
    assert s3["stale"] == {} and s3["refreshed"] == []
    assert s3["quarantined"] == [key]
    assert key not in s3["fresh"]

    # Operator clears it -> eligible again on the very next tick.
    assert d.clear_quarantine() == [key]
    s4 = d.tick(now=9100.0)["documents"][doc]
    assert s4["refreshed"] == [key]
    # Still failing, streak restarts from the cleared baseline.
    assert d.state["documents"][doc]["cells"][key]["fail_streak"] == 1


def test_quarantine_zero_disables_the_breaker(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _poison_doc(tmp_path, quarantine_after=0)
    d = _daemon(store, doc, harness=_PoisonHarness())
    key = _key_for(d, "poison")
    for i in range(5):
        d.tick(now=1000.0 + 40.0 * i)
    cell_st = d.state["documents"][doc]["cells"][key]
    assert cell_st["fail_streak"] == 5 and "quarantined" not in cell_st
    # History stays bounded even without quarantine.
    assert len(cell_st["history"]) <= 5


def test_success_resets_streak_and_lifts_nothing(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _poison_doc(tmp_path, quarantine_after=3)
    d = _daemon(store, doc, harness=_PoisonHarness())
    key = _key_for(d, "poison")
    d.tick(now=1000.0)
    assert d.state["documents"][doc]["cells"][key]["fail_streak"] == 1
    # The cell recovers (harness fixed in place): streak resets to 0.
    d.harness = SpinHarness(iters=50)
    d.tick(now=1040.0)
    cell_st = d.state["documents"][doc]["cells"][key]
    assert cell_st["fail_streak"] == 0
    assert "quarantined" not in cell_st and "history" not in cell_st


def test_daemon_status_surfaces_quarantine_workers_and_retries(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _poison_doc(tmp_path, quarantine_after=2)
    d = _daemon(store, doc, harness=_PoisonHarness())
    key = _key_for(d, "poison")
    d.tick(now=1000.0)
    d.tick(now=1040.0)

    status = daemon_status(store, [doc], now=2000.0)
    (cell,) = status["documents"][doc]["cells"]
    assert cell["quarantined"] and cell["due"] is False
    assert cell["fail_streak"] == 2 and len(cell["history"]) == 2
    assert status["documents"][doc]["quarantined"] == [key]
    # New top-level robustness sections are always present.
    assert "hosts" in status["workers"]
    assert isinstance(status["retry_counters"], dict)

    text = render_status(status)
    assert "QUARANTINED" in text and "poisoned cell" in text


def test_max_ticks_exits_cleanly_without_signals(tmp_path):
    store = ResultStore(tmp_path / "s")
    doc = _two_prefix_doc(tmp_path, target_lag=3600)
    d = _daemon(store, doc, interval=0.01, max_ticks=3)
    assert d.run() == 0
    assert d.ticks == 3
    saved = json.loads(Path(d.state_path).read_text())
    assert saved["ticks"] == 3
    # Tick 1 refreshed both never-run cells; ticks 2-3 re-ran nothing.
    assert _last_seq(store, "contA") == 0 and _last_seq(store, "contB") == 0
