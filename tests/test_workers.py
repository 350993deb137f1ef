"""Tests for the distributed execution plane: the lease-reclaimed work
queue protocol, the CampaignBroker + spawned worker pool, crash recovery
(SIGKILL mid-cell → reclaim → retry, exactly once), resource accounting,
and env-injection survival across the spawn boundary."""

import json
import os
import signal
import threading
import time
from pathlib import Path

import multiprocessing as mp

import pytest

from repro.core import accounting
from repro.core.component import PipelineError
from repro.core.harness import BenchmarkSpec, Harness, Injections
from repro.core.orchestrator import ExecutionOrchestrator
from repro.core.readiness import Readiness
from repro.core.store import ResultStore
from repro.core.synthetic import SPIN_ENV_KNOB, BlockingHarness, SpinHarness
from repro.core.workers import (
    CampaignBroker,
    WorkerConfig,
    cell_payload,
    resolve_harness,
    run_collection_process,
    spawn_spec_for,
    worker_main,
)
from repro.core.workqueue import WorkQueue

SPAWN = mp.get_context("spawn")


def _specs(n):
    return [BenchmarkSpec(arch=f"arch{i}", shape="train_4k", system="sysA")
            for i in range(n)]


def _payloads(n, prefix="q"):
    return [cell_payload(s, {"prefix": prefix}, cell_index=i)
            for i, s in enumerate(_specs(n))]


def _canon(store, prefix):
    return sorted(json.dumps(accounting.strip_volatile(r.to_dict()),
                             sort_keys=True)
                  for r in store.query(prefix))


# ---------------------------------------------------------------------------
# work queue protocol
# ---------------------------------------------------------------------------

def test_queue_claim_complete_cycle(tmp_path):
    q = WorkQueue(tmp_path / "q").create(_payloads(2), campaign="c")
    assert q.n_tasks == 2
    idx, payload, attempt = q.claim_next("w1")
    assert (idx, attempt) == (0, 1)
    assert payload["task_uid"] == "c:0"
    # Lowest unleased cell next — the claimed one is skipped.
    idx2, _, _ = q.claim_next("w2")
    assert idx2 == 1
    assert q.claim_next("w3") is None  # everything leased
    assert q.heartbeat(0)
    assert q.complete(0, {"readiness": 3})
    assert not q.finished()
    assert q.complete(1, {"readiness": 3})
    assert q.finished()
    assert q.results()[0] == {"readiness": 3}


def test_queue_done_marker_first_writer_wins(tmp_path):
    q = WorkQueue(tmp_path / "q").create(_payloads(1))
    q.claim_next("w1")
    assert q.complete(0, {"winner": "w1"})
    # A slow-but-alive worker whose cell was reclaimed loses the race and
    # its result is discarded.
    assert not q.complete(0, {"winner": "w2"})
    assert q.results()[0] == {"winner": "w1"}


def test_queue_claim_race_single_winner(tmp_path):
    q = WorkQueue(tmp_path / "q").create(_payloads(1))
    wins, barrier = [], threading.Barrier(8)

    def racer(i):
        barrier.wait(timeout=5)
        got = q.claim_next(f"w{i}")
        if got is not None:
            wins.append(i)

    threads = [threading.Thread(target=racer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(wins) == 1  # O_EXCL lease create has exactly one winner


def test_queue_reclaim_expired_lease(tmp_path):
    q = WorkQueue(tmp_path / "q", lease_timeout=0.05).create(_payloads(1))
    q.claim_next("dead-worker")
    time.sleep(0.15)
    assert q.reclaim_expired() == [0]
    journal = q.reclaim_journal()
    assert len(journal) == 1 and journal[0]["worker"] == "dead-worker"
    # The reclaimed cell is claimable again, with the attempt counter bumped.
    idx, _, attempt = q.claim_next("w2")
    assert (idx, attempt) == (0, 2)


def test_queue_heartbeat_keeps_lease_alive(tmp_path):
    q = WorkQueue(tmp_path / "q", lease_timeout=0.2).create(_payloads(1))
    q.claim_next("slow-but-alive")
    deadline = time.monotonic() + 0.5
    while time.monotonic() < deadline:
        q.heartbeat(0)
        time.sleep(0.03)
    assert q.reclaim_expired() == []  # never mistaken for dead


def test_queue_bounded_attempts_terminal_failure(tmp_path):
    q = WorkQueue(tmp_path / "q", lease_timeout=0.05).create(_payloads(1))
    for attempt in (1, 2):
        idx, _, got = q.claim_next("crashy")
        assert (idx, got) == (0, attempt)
        time.sleep(0.15)
        q.reclaim_expired(max_attempts=2)
    # Second reclaim exhausted the budget: terminal failure marker, and the
    # queue is finished — a poisoned cell cannot wedge the campaign.
    assert q.finished()
    result = q.results()[0]
    assert result["readiness"] == 0 and result["reclaimed"]
    assert "2 failed attempts" in result["error"]
    assert q.claim_next("w9") is None


def test_queue_stop_flag(tmp_path):
    q = WorkQueue(tmp_path / "q").create(_payloads(1))
    assert not q.stop_requested()
    q.request_stop()
    assert q.stop_requested()


# ---------------------------------------------------------------------------
# spawn-safety plumbing
# ---------------------------------------------------------------------------

def test_spawn_spec_round_trip():
    ref, kwargs = spawn_spec_for(SpinHarness(iters=77))
    rebuilt = resolve_harness(ref, kwargs)
    assert isinstance(rebuilt, SpinHarness) and rebuilt.iters == 77


def test_unspawnable_harness_is_hard_error():
    class ClosureHarness(Harness):
        name = "closure"

        def run(self, spec, injections=None):  # pragma: no cover
            raise AssertionError

    with pytest.raises(PipelineError, match="spawn_spec"):
        spawn_spec_for(ClosureHarness())
    with pytest.raises(PipelineError, match="harness ref"):
        resolve_harness("not-a-module-path", {})


def test_launcher_injection_rejected_in_payload():
    with pytest.raises(PipelineError, match="launcher"):
        cell_payload(_specs(1)[0], {"prefix": "p"},
                     injections=Injections(launcher=lambda cmd: cmd))


def test_worker_config_round_trip():
    cfg = WorkerConfig(store_root="/s", harness_ref="m:f",
                       harness_kwargs={"iters": 3}, env={"K": "1"},
                       lease_timeout=2.0)
    back = WorkerConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert back == cfg
    assert back.heartbeat_s() == pytest.approx(0.5)  # lease / 4


# ---------------------------------------------------------------------------
# process collection end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["dir", "jsonl"])
def test_process_collection_matches_thread_collection(tmp_path, backend):
    specs = _specs(4)
    t_store = ResultStore(tmp_path / "thread", backend=backend)
    p_store = ResultStore(tmp_path / "proc", backend=backend)
    ex_t = ExecutionOrchestrator(inputs={"prefix": "c", "parallelism": 2},
                                 harness=SpinHarness(iters=2000), store=t_store)
    rt = ex_t.run_collection(specs)
    ex_p = ExecutionOrchestrator(
        inputs={"prefix": "c", "workers": 2, "worker_mode": "process"},
        harness=SpinHarness(iters=2000), store=p_store)
    rp = ex_p.run_collection(specs)
    assert [r.readiness for r in rt] == [Readiness.REPRODUCIBLE] * 4
    assert [r.readiness for r in rp] == [Readiness.REPRODUCIBLE] * 4
    assert [r.spec.cell for r in rt] == [r.spec.cell for r in rp]
    # Byte-identical stores modulo timestamps / execution-plane provenance.
    assert _canon(t_store, "c") == _canon(p_store, "c")
    # Resource accounting: envelope + columnar metrics, process scope.
    for report in p_store.query("c"):
        res = report.parameter["resources"]
        assert res["worker_mode"] == "process" and res["scope"] == "process"
        assert report.parameter["task_uid"].startswith("collection-c:")
        metrics = report.data[0].metrics
        for key in accounting.RESOURCE_METRICS:
            assert key in metrics
        assert metrics["res_wall_s"] > 0
    # The queue working directory never leaks into prefix scans.
    assert all(not p.startswith("_") for p in p_store.prefixes())


def test_process_collection_requires_store():
    ex = ExecutionOrchestrator(inputs={"prefix": "c", "worker_mode": "process"},
                               harness=SpinHarness(iters=10))
    with pytest.raises(PipelineError, match="store"):
        ex.run_collection(_specs(2), workers=2)


def test_worker_reapplies_injected_env_after_spawn(tmp_path):
    """Regression: ``injected_env`` frames are per-interpreter state — a
    spawned worker inherits neither the locks nor the parent's active
    frames, so the worker bootstrap must re-enter the campaign env itself."""
    store = ResultStore(tmp_path / "s")
    results = run_collection_process(
        inputs={"prefix": "env"}, harness=SpinHarness(iters=500), store=store,
        specs=_specs(2), workers=2, env={SPIN_ENV_KNOB: "7"})
    assert [r.readiness for r in results] == [Readiness.REPRODUCIBLE] * 2
    for r in results:
        assert r.report.data[0].metrics["spin_env_echo"] == 7.0
    # The frame was scoped to the worker's drain loop, not leaked here.
    assert SPIN_ENV_KNOB not in os.environ


def test_broker_synthesizes_failures_for_lost_cells(tmp_path):
    """A pool that dies without completing its cells still yields one
    terminal answer per payload (synthesized failure records)."""
    store = ResultStore(tmp_path / "s")
    broker = CampaignBroker(store, workers=1, name="doomed",
                            lease_timeout=0.5, max_attempts=1,
                            deadline_s=0.2, poll_s=0.05)

    class Unspawnable(SpinHarness):
        def spawn_spec(self):
            return "repro.core.synthetic:does_not_exist", {}

    results = broker.run(_payloads(2), harness=Unspawnable())
    assert set(results) == {0, 1}
    assert all(r["readiness"] == 0 and r["error"] for r in results.values())


@pytest.mark.parametrize("declared", [False, True])
def test_broker_refuses_device_harness_on_tpu(tmp_path, monkeypatch, declared):
    """On a TPU host a device-bound harness (the campaign default, or one a
    payload declares) is refused before any worker or queue exists."""
    from repro.core import workers
    from repro.core.harness import ExecHarness

    monkeypatch.setattr(workers, "_worker_platform", lambda env: "tpu")
    store = ResultStore(tmp_path / "s")
    broker = CampaignBroker(store, workers=1, name="chip")
    payloads = _payloads(1)
    harness = SpinHarness(iters=10)
    if declared:
        payloads[0]["inputs"].update({"harness": "kernel"})
    else:
        harness = ExecHarness()
    with pytest.raises(PipelineError, match="worker_mode: thread"):
        broker.run(payloads, harness=harness)
    assert not broker.queue_root.exists() and broker.processes == []
    # A host-only harness may still use process workers there.
    broker._refuse_device_harnesses(_payloads(1), SpinHarness(iters=10))


# ---------------------------------------------------------------------------
# crash recovery: SIGKILL mid-cell → lease expiry → reclaim → retry
# ---------------------------------------------------------------------------

def _wait_for(predicate, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        got = predicate()
        if got:
            return got
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.mark.parametrize("backend", ["dir", "jsonl"])
def test_sigkill_mid_cell_reclaimed_exactly_once(tmp_path, backend):
    store = ResultStore(tmp_path / "store", backend=backend)
    sentinels = tmp_path / "sentinels"
    queue_root = tmp_path / "queue"
    spec = _specs(1)[0]
    cfg = WorkerConfig(
        store_root=str(store.root), store_backend=backend,
        harness_ref="repro.core.synthetic:BlockingHarness",
        harness_kwargs={"sentinel_dir": str(sentinels), "timeout_s": 60.0},
        lease_timeout=0.6, poll_s=0.05, idle_timeout=60.0,
    ).to_dict()
    queue = WorkQueue(queue_root, lease_timeout=0.6)
    queue.create([cell_payload(spec, {"prefix": "crash"})], campaign="crash")

    w1 = SPAWN.Process(target=worker_main, args=("w1", str(queue_root), cfg),
                       daemon=True)
    w1.start()
    try:
        # The harness blocks inside run(); the sentinel name carries the
        # executing pid — kill exactly that process, mid-cell.
        sentinel = _wait_for(
            lambda: next(iter(sentinels.glob(f"started.{spec.cell}.*")), None),
            30.0, "worker to start the cell")
        victim = int(sentinel.name.rsplit(".", 1)[1])
        os.kill(victim, signal.SIGKILL)
        w1.join(timeout=10)
        assert not w1.is_alive()

        # Heartbeats stopped with the process: the lease goes stale and the
        # cell is reclaimed exactly once.
        _wait_for(lambda: queue.reclaim_expired() == [0], 10.0, "reclaim")
        assert len(queue.reclaim_journal()) == 1
        assert queue.done_count() == 0  # reclaimed for retry, not failed

        # A fresh worker claims the retry (attempt 2) and completes once
        # the release file appears.
        (sentinels / "release").write_text("go")
        w2 = SPAWN.Process(target=worker_main, args=("w2", str(queue_root), cfg),
                           daemon=True)
        w2.start()
        w2.join(timeout=30)
        assert queue.finished()
    finally:
        for p in (w1,):
            if p.is_alive():
                p.terminate()

    result = queue.results()[0]
    assert result["readiness"] == int(Readiness.REPRODUCIBLE)
    # worker_main expands a bare label to the full host:pid:label identity.
    assert result["worker"].endswith(":w2") and result["attempts"] == 2
    assert len(queue.reclaim_journal()) == 1  # reclaimed exactly once
    # Exactly one persisted report for the cell — the killed attempt never
    # reached its store append, and the retry appended exactly once.
    reports = store.query("crash")
    assert len(reports) == 1
    assert reports[0].parameter["task_uid"] == "crash:0"
    assert reports[0].parameter["attempt"] == 2


@pytest.mark.parametrize("backend", ["dir", "jsonl"])
def test_sigstop_paused_worker_is_fenced_exactly_one_store_entry(tmp_path, backend):
    """A worker paused mid-cell (SIGSTOP — alive, not dead) loses its lease
    to the reclaimed retry.  When it resumes it is already PAST its adoption
    check with a report in hand; pre-fix that report appended unconditionally
    and the store held two entries for one (task_uid, slot).  The lease
    fence (ownership re-check before append and before complete) is what
    makes the effect exactly-once, on both store backends."""
    from repro.core.workers import LeaseLostError, _FencedStore, _find_adopted
    from repro.core.protocol import Report

    store = ResultStore(tmp_path / "store", backend=backend)
    sentinels = tmp_path / "sentinels"
    queue_root = tmp_path / "queue"
    spec = _specs(1)[0]
    cfg = WorkerConfig(
        store_root=str(store.root), store_backend=backend,
        harness_ref="repro.core.synthetic:BlockingHarness",
        harness_kwargs={"sentinel_dir": str(sentinels), "timeout_s": 60.0},
        lease_timeout=0.6, poll_s=0.05, idle_timeout=60.0,
    ).to_dict()
    queue = WorkQueue(queue_root, lease_timeout=0.6)
    queue.create([cell_payload(spec, {"prefix": "pause"})], campaign="pause")

    w1 = SPAWN.Process(target=worker_main, args=("w1", str(queue_root), cfg),
                       daemon=True)
    w1.start()
    victim = None
    try:
        sentinel = _wait_for(
            lambda: next(iter(sentinels.glob(f"started.{spec.cell}.*")), None),
            30.0, "w1 to start the cell")
        victim = int(sentinel.name.rsplit(".", 1)[1])
        os.kill(victim, signal.SIGSTOP)  # paused mid-run: heartbeats freeze

        _wait_for(lambda: queue.reclaim_expired() == [0], 10.0, "reclaim")
        # The retry completes the cell while w1 is still frozen.
        (sentinels / "release").write_text("go")
        w2 = SPAWN.Process(target=worker_main, args=("w2", str(queue_root), cfg),
                           daemon=True)
        w2.start()
        w2.join(timeout=30)
        assert queue.finished()
        assert len(store.query("pause")) == 1

        # Resume the paused worker: it finishes its blocked harness call and
        # reaches its store append — the fence must drop it.
        os.kill(victim, signal.SIGCONT)
        w1.join(timeout=30)
        assert not w1.is_alive()
    finally:
        if victim is not None and w1.is_alive():
            try:
                os.kill(victim, signal.SIGCONT)
            except ProcessLookupError:
                pass
        for p in (w1,):
            if p.is_alive():
                p.terminate()

    # Exactly one done marker (the retry's) and exactly one store entry.
    result = queue.results()[0]
    assert result["worker"].endswith(":w2") and result["attempts"] == 2
    reports = store.query("pause")
    assert len(reports) == 1
    assert reports[0].parameter["worker"].endswith(":w2")
    assert reports[0].parameter["task_uid"] == "pause:0"

    # Pre-fix repro: the resumed worker's append was an unconditional
    # store.append — replay that exact write and the duplicate lands.
    ghost = Report.from_dict(reports[0].to_dict())
    ghost.parameter["worker"] = "w1-ghost"
    with pytest.raises(LeaseLostError):
        # The fix: the fenced proxy re-checks lease ownership first.
        _FencedStore(store, lambda: queue.owns(0, "w1", 1)).append("pause", ghost)
    assert len(store.query("pause")) == 1  # fenced write never landed
    store.append("pause", ghost)  # the pre-fix behavior
    assert len(store.query("pause")) == 2  # ...duplicated the cell

    # Defense-in-depth for historical stores that already carry such a
    # duplicate: every reader keeps the lowest-seq record.
    adopted = _find_adopted(store, "pause", "pause:0")
    assert adopted is not None and adopted.parameter["worker"].endswith(":w2")


def test_corrupt_task_payload_fails_terminally_without_leaking_lease(tmp_path):
    """``claim_next`` winning the lease race and then failing to parse the
    task payload must not leave the lease behind (the cell would wedge until
    lease_timeout and the journal would charge a phantom attempt): the cell
    is terminally failed with a structured marker and the claim moves on."""
    q = WorkQueue(tmp_path / "q").create(_payloads(2))
    (tmp_path / "q" / "tasks" / "00000.json").write_text("{corrupt")

    claim = q.claim_next("w1")
    assert claim is not None
    idx, payload, attempt = claim
    assert idx == 1 and attempt == 1  # the healthy cell, claimed normally
    assert payload["task_uid"] == "campaign:1"

    # The corrupt cell got a terminal marker, not a stuck lease.
    r0 = q.results()[0]
    assert r0["corrupt"] and r0["readiness"] == 0
    assert "corrupt task payload" in r0["error"]
    assert q.lease_info(0) is None  # complete() released the held lease
    assert q.reclaim_journal() == []  # no phantom attempt charged
    assert q.claim_next("w2") is None  # nothing else claimable

    q.complete(1, {"readiness": 3})
    assert q.finished()  # the campaign terminates normally


def test_idle_worker_outlives_slow_peer_while_campaign_progresses(tmp_path):
    """Campaign progress = liveness: a worker with nothing claimable must
    not abandon an unfinished campaign while ANOTHER worker is still
    completing cells — pre-fix it idle-timed-out and the last cell, later
    reclaimed, had nobody left to run it."""
    store = ResultStore(tmp_path / "s")
    queue_root = tmp_path / "q"
    q = WorkQueue(queue_root, lease_timeout=60.0)
    q.create(_payloads(3, prefix="idle"), campaign="idle")
    # A slow peer owns every cell before our worker starts.
    for want in range(3):
        idx, _, _ = q.claim_next("peer")
        assert idx == want

    cfg = WorkerConfig(
        store_root=str(store.root),
        harness_ref="repro.core.synthetic:SpinHarness",
        harness_kwargs={"iters": 100},
        lease_timeout=60.0, poll_s=0.05, idle_timeout=1.0,
    ).to_dict()
    t = threading.Thread(target=worker_main,
                         args=("w-idle", str(queue_root), cfg), daemon=True)
    t.start()

    # The peer finishes a cell every 0.6s — each completion advances
    # done_count and must reset the worker's idle clock.  Total idle time
    # far exceeds idle_timeout (1.0s), but no single gap does.
    time.sleep(0.6)
    q.complete(0, {"readiness": 3, "worker": "peer"})
    time.sleep(0.6)
    q.complete(1, {"readiness": 3, "worker": "peer"})
    time.sleep(0.6)
    assert t.is_alive()  # ~1.8s idle total: alive only if progress resets

    # The peer dies on its last cell; once the lease frees up, the
    # still-alive worker claims and finishes the campaign.
    (queue_root / "leases" / "00002.lease").unlink()
    _wait_for(q.finished, 15.0, "idle worker to pick up the freed cell")
    t.join(timeout=10)
    assert not t.is_alive()
    assert q.results()[2]["worker"].endswith(":w-idle")
    assert len(store.query("idle")) == 1  # only the cell w-idle executed


def test_retry_adopts_orphaned_store_result(tmp_path):
    """A worker killed AFTER persisting but BEFORE its done marker must not
    make the retry re-append: the retry finds the ``task_uid``-tagged report
    in the store and adopts it."""
    from repro.core.workers import _execute_payload

    store = ResultStore(tmp_path / "s")
    payload = cell_payload(_specs(1)[0], {"prefix": "adopt"})
    payload["task_uid"] = "adopt:0"
    harness = SpinHarness(iters=500)
    # Attempt 1 persists its report; pretend the worker died before
    # queue.complete() by simply discarding the result dict.
    first = _execute_payload(payload, store=store, harness=harness,
                             worker_id="w1", attempt=1)
    assert first["readiness"] == int(Readiness.REPRODUCIBLE)
    assert len(store.query("adopt")) == 1
    # The reclaimed retry adopts instead of re-executing.
    second = _execute_payload(payload, store=store, harness=harness,
                              worker_id="w2", attempt=2)
    assert second["adopted"] and second["readiness"] == int(Readiness.REPRODUCIBLE)
    reports = store.query("adopt")
    assert len(reports) == 1  # no duplicate append
    assert reports[0].parameter["worker"] == "w1"  # the original, adopted


# ---------------------------------------------------------------------------
# resource accounting primitives
# ---------------------------------------------------------------------------

def test_resource_probe_fills_accounting_on_success_and_failure():
    acct = {}
    with accounting.resource_probe(acct, "thread"):
        sum(range(10_000))
    assert acct["res_wall_s"] > 0 and acct["scope"] == "thread"
    failed = {}
    with pytest.raises(RuntimeError):
        with accounting.resource_probe(failed, "process"):
            raise RuntimeError("cell exploded")
    assert "res_wall_s" in failed  # a failed cell still cost time
    with pytest.raises(ValueError):
        with accounting.resource_probe({}, "cluster"):
            pass


def test_strip_volatile_removes_exactly_the_plane_fields(tmp_path):
    store = ResultStore(tmp_path / "s")
    ex = ExecutionOrchestrator(inputs={"prefix": "v"},
                               harness=SpinHarness(iters=200), store=store)
    ex.run_collection(_specs(1))
    doc = store.query("v")[0].to_dict()
    canon = accounting.strip_volatile(doc)
    assert "resources" not in canon["parameter"]
    assert canon["reporter"]["timestamp"] == 0.0
    for key in accounting.RESOURCE_METRICS:
        assert key not in canon["data"][0]["metrics"]
    # Payload metrics survive canonicalization.
    assert "spin_result" in canon["data"][0]["metrics"]
    # The original document is untouched (deep copy).
    assert "resources" in doc["parameter"]
