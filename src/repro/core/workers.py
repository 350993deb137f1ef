"""Distributed execution plane: CampaignBroker + process worker pool.

The thread scheduler (``repro.core.scheduler``) keeps every cell in one
interpreter — CPU-bound harness work serializes on the GIL, and one crashed
interpreter loses the whole campaign.  This module is the alternative
dispatch path the paper's JUREAP deployment model needs:

* :class:`CampaignBroker` materializes a campaign's cells into a
  lease-reclaimed :class:`~repro.core.workqueue.WorkQueue` persisted under
  the store root, spawns N worker *processes*, and monitors them —
  reclaiming expired leases and respawning dead workers (bounded).
* :func:`worker_main` is the spawn entrypoint.  A worker is configured by
  plain data only (store root + backend name, harness ``module:factory``
  recipe, lease timings): no closure, harness object, or lock crosses the
  process boundary.  It re-applies the campaign's ambient env-injection
  frame inside its own interpreter (``injected_env`` state is per-process —
  see the spawn caveat in ``repro.core.harness``), then drains the queue:
  claim → execute via a fresh ``ExecutionOrchestrator`` (process-scope
  resource accounting) → persist → write the done marker.
* **Exactly-once effect**: a worker SIGKILLed between its store append and
  its done marker would make the reclaimed retry re-execute the cell.
  Every persisted report is tagged with the cell's ``task_uid``, and a
  retry first checks the store for that tag — it adopts the orphaned
  result instead of appending a duplicate.

Because the queue and the results both live in the store's filesystem, the
same protocol extends to N *hosts* draining one campaign over shared
storage — nothing here assumes the workers share a parent process.  The
multi-host entry point is ``python -m repro.core.workers <queue-root>``: a
remote host sharing the filesystem reads the broker-published
``worker_config.json`` and joins the drain with a ``host:pid:label``
identity that flows into lease files, done markers, and report provenance
(see ``docs/failure_model.md`` for the liveness assumptions).
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import multiprocessing as mp
import os
import socket
import threading
import time
import traceback
import uuid
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.core import chaos
from repro.core import duet as duet_mod
from repro.core import fingerprint as fingerprint_mod
from repro.core.component import PipelineError
from repro.core.harness import BenchmarkSpec, Harness, HarnessCapabilities, Injections, injected_env
from repro.core.protocol import Report
from repro.core.readiness import Readiness
from repro.core.retry import RetryPolicy, call_with_retry
from repro.core.store import ResultStore
from repro.core.workqueue import (
    DEFAULT_LEASE_TIMEOUT, DEFAULT_MAX_ATTEMPTS, WorkQueue, _atomic_json)

QUEUE_DIRNAME = "_queue"   # under the store root; skipped by prefix scans
WORKER_CONFIG = "worker_config.json"  # broker-published, read by remote hosts

#: Host identity override for workers — lets tests (and containerized
#: deployments whose hostname is meaningless) simulate distinct hosts.
HOST_ENV = "EXACB_HOST"


def host_identity() -> str:
    """This process's host identity: ``$EXACB_HOST`` or the hostname."""
    return os.environ.get(HOST_ENV, "").strip() or socket.gethostname()


def worker_identity(label: str = "") -> str:
    """Compose the full ``host:pid:label`` worker id for this process."""
    return f"{host_identity()}:{os.getpid()}:{label or uuid.uuid4().hex[:8]}"


def host_of(worker_id: str) -> str:
    """The host component of a ``host:pid:label`` worker id ('' if none)."""
    return worker_id.split(":", 1)[0] if ":" in worker_id else ""


# ---------------------------------------------------------------------------
# Spawn-safe configuration
# ---------------------------------------------------------------------------

def spawn_spec_for(harness: Harness) -> Tuple[str, Dict[str, Any]]:
    """The harness's ``("module:factory", kwargs)`` recipe, as a hard error
    (not a mystery pickle failure) when the adapter doesn't provide one."""
    try:
        ref, kwargs = harness.spawn_spec()
    except NotImplementedError as e:
        raise PipelineError(str(e)) from e
    return str(ref), dict(kwargs)


def resolve_harness(ref: str, kwargs: Dict[str, Any]) -> Harness:
    """Rebuild a harness from its spawn recipe inside a worker."""
    module, sep, attr = ref.partition(":")
    if not sep or not attr:
        raise PipelineError(f"bad harness ref {ref!r} (want 'module:factory')")
    factory = getattr(importlib.import_module(module), attr)
    return factory(**kwargs)


@dataclasses.dataclass
class WorkerConfig:
    """Everything a spawned worker needs, as plain data."""

    store_root: str
    store_backend: str = "dir"
    harness_ref: str = ""
    harness_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: Ambient env-injection frame re-applied inside the worker interpreter
    #: (spawn does not inherit the parent's active ``injected_env`` frames).
    env: Dict[str, str] = dataclasses.field(default_factory=dict)
    lease_timeout: float = DEFAULT_LEASE_TIMEOUT
    heartbeat_interval: float = 0.0  # 0 = lease_timeout / 4
    max_attempts: int = DEFAULT_MAX_ATTEMPTS
    poll_s: float = 0.1
    #: Give up after this long with no claimable work and an unfinished
    #: queue (an orphaned worker must not outlive its campaign forever).
    idle_timeout: float = 120.0
    #: The broker's environment fingerprint at campaign start.  Workers
    #: measure against this shared reference so a drifted worker host
    #: (governor flip, different library set) marks its reports untrusted
    #: instead of silently mixing environments into one campaign.
    reference_fingerprint: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(doc: Dict[str, Any]) -> "WorkerConfig":
        return WorkerConfig(**doc)

    def heartbeat_s(self) -> float:
        return self.heartbeat_interval or max(0.05, self.lease_timeout / 4.0)


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------

#: Heartbeat I/O retry: total backoff must stay well under the lease
#: timeout, or the retries themselves would let the lease expire.
_HEARTBEAT_POLICY = RetryPolicy(tries=4, base_s=0.02, factor=2.0, max_s=0.25)


class _Heartbeat(threading.Thread):
    """Refreshes one cell's lease while the harness runs, so a *live* worker
    on a slow cell is never mistaken for a dead one.

    A heartbeat that *errors* used to kill this thread silently: the lease
    then aged out mid-run and a peer reclaimed the cell while this worker
    kept executing — the exact slow-but-alive race fencing exists for, now
    entered through an I/O blip instead of a pause.  Transient failures are
    retried with backoff; persistent failure (or a vanished lease) sets
    ``lost``, which the worker's fence checks before every store append —
    the cell is fenced promptly instead of racing the reclaimer.
    """

    def __init__(self, queue: WorkQueue, idx: int, interval: float):
        super().__init__(daemon=True, name=f"heartbeat-{idx:05d}")
        self.queue = queue
        self.idx = idx
        self.interval = interval
        # NB: not `_stop` — that would shadow threading.Thread's internal
        # `_stop()` method and break `join()`.
        self._halt = threading.Event()
        #: Set when the lease is gone or unheartbeatable — ownership can no
        #: longer be asserted, so the owner must consider itself fenced.
        self.lost = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(self.interval):
            try:
                alive = call_with_retry(
                    lambda: self.queue.heartbeat(self.idx),
                    label="queue.heartbeat", policy=_HEARTBEAT_POLICY)
            except Exception:  # noqa: BLE001 — persistent failure fences
                self.lost.set()
                return
            if not alive:
                self.lost.set()
                return

    def stop(self) -> None:
        self._halt.set()


class _TaggingHarness(Harness):
    """Wraps the real harness to stamp execution-plane provenance
    (``task_uid``, worker id, attempt) into each report *before* the
    orchestrator persists it — the dedup key for crash recovery."""

    def __init__(self, inner: Harness, tags: Dict[str, Any]):
        self.inner = inner
        self.name = inner.name
        self.tags = tags

    def capabilities(self) -> HarnessCapabilities:
        return self.inner.capabilities()

    def run(self, spec, injections=None):
        report = self.inner.run(spec, injections)
        report.parameter.update(self.tags)
        return report


def _injections_from_payload(doc: Optional[Dict[str, Any]]) -> Optional[Injections]:
    if not doc:
        return None
    return Injections(env=dict(doc.get("env", {})),
                      overrides=dict(doc.get("overrides", {})))


class LeaseLostError(RuntimeError):
    """The worker's lease was reclaimed while it was still executing."""


class _FencedStore:
    """Store proxy that re-verifies lease ownership immediately before every
    append — the fencing-token check that closes the slow-but-alive window.
    A worker paused mid-cell (SIGSTOP, NFS stall, GC-like hiccup) and resumed
    *after* the reclaimed retry's adoption check would otherwise append a
    second report for the same ``task_uid``; with the fence it fails here and
    the report is dropped instead."""

    def __init__(self, inner: ResultStore, fence):
        self._inner = inner
        self._fence = fence
        #: Set when an append failed *as I/O* even after the store's own
        #: bounded retries — the signal for the worker to fence itself
        #: (release the lease, skip the done marker) rather than terminally
        #: fail the cell on a sick storage path.
        self.append_failed = False

    def append(self, prefix, report, **kwargs):
        if not self._fence():
            raise LeaseLostError(
                f"lease lost before store append to {prefix!r}; dropping "
                "report — the reclaimed retry owns this cell now")
        try:
            return self._inner.append(prefix, report, **kwargs)
        except OSError:
            self.append_failed = True
            raise

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _find_adopted(store: ResultStore, prefix: str, task_uid: str) -> Optional[Report]:
    """A report persisted by a previous (killed) attempt of this cell.

    ``store.query`` yields in seq order, so the first match is the
    lowest-seq report — the canonical one if a fencing gap ever let a
    duplicate ``task_uid`` entry slip in (defense-in-depth: every reader
    converges on the same record)."""
    for report in store.query(prefix):
        if report.parameter.get("task_uid") == task_uid:
            return report
    return None


def _duet_adopted(
    store: ResultStore, prefix: str, task_uid: str,
) -> Tuple[Optional[str], Dict[Tuple[int, str], Report]]:
    """Per-slot adoption for duet cells.  A worker killed mid-duet may have
    persisted only some ``(round, role)`` invocations; the retry must resume
    the *same* duet (reusing its ``duet_id``) and execute only the missing
    slots — never re-measuring a persisted one, or the pair extraction would
    see duplicate slots and exactly-once would be lost."""
    duet_id: Optional[str] = None
    slots: Dict[Tuple[int, str], Report] = {}
    for report in store.query(prefix):
        if report.parameter.get("task_uid") != task_uid:
            continue
        ctx = duet_mod.context_of(report)
        if ctx is None:
            continue
        if duet_id is None:
            duet_id = str(ctx["duet_id"])
        # Lowest store seq wins per (round, role) slot: query is seq-ordered,
        # so keep the first report seen — duplicates from a fencing gap are
        # ignored, matching duet.pairs_from_reports / columnar.duet_pairs.
        slots.setdefault(
            (int(ctx.get("round", -1)), str(ctx.get("role", ""))), report)
    return duet_id, slots


def _execute_payload(
    payload: Dict[str, Any],
    *,
    store: ResultStore,
    harness: Harness,
    worker_id: str,
    attempt: int,
    reference_fingerprint: Optional[Dict[str, Any]] = None,
    fence=None,
    resource_scope: str = "process",
) -> Dict[str, Any]:
    """Run one queue cell to a terminal result dict (the done-marker body).
    Never raises: execution errors are results, like everywhere else.

    ``fence`` is a zero-arg callable returning whether the caller still owns
    the cell's lease.  When provided, every store append is fenced (see
    :class:`_FencedStore`) and the returned dict carries ``fenced: True``
    whenever ownership was lost — the caller must then *not* write the done
    marker: the reclaimed retry owns the cell, and our (possibly stale or
    FAILED) marker could win the first-writer race against its good result.
    """
    from repro.core.orchestrator import (  # lazy: cycle
        CellResult, ExecutionOrchestrator, reduce_duet)

    fenced_store: Optional[_FencedStore] = None
    if fence is not None:
        fenced_store = _FencedStore(store, fence)
        store = fenced_store
    task_uid = str(payload.get("task_uid", ""))
    base = {
        "task_uid": task_uid,
        "component_ref": payload.get("component_ref", "execution@v4"),
        "call_index": payload.get("call_index", 0),
        "cell_index": payload.get("cell_index", 0),
        "worker": worker_id,
        "host": host_of(worker_id),
        "attempts": attempt,
    }
    def _run() -> Dict[str, Any]:
        spec = BenchmarkSpec(**payload["spec"])
        prefix = payload.get("prefix", "default")
        record = bool(payload.get("record", True))
        raw_inputs = dict(payload.get("inputs", {}))
        duet = bool(raw_inputs.get("duet"))
        if attempt > 1 and record and not duet:
            adopted = _find_adopted(store, prefix, task_uid)
            if adopted is not None:
                # A prior attempt died AFTER persisting: adopt its report
                # instead of re-executing — no duplicate store append.
                return base | {
                    "cell": spec.cell,
                    "readiness": int(adopted.parameter.get("readiness", 0)),
                    "error": None,
                    "report": adopted.to_dict(),
                    "adopted": True,
                }
        # A payload may declare its own harness (`harness:` + `harness.*`
        # inputs travel with it) — the document's choice beats the worker's
        # campaign-level default, same precedence as thread mode.
        from repro import harnesses as harness_families

        declared = harness_families.from_inputs(raw_inputs)
        cell_harness = declared if declared is not None else harness
        tagged = _TaggingHarness(cell_harness, {
            "task_uid": task_uid, "worker": worker_id,
            "host": host_of(worker_id), "attempt": attempt})
        # Payloads may originate from a component with a wider schema
        # (feature-injection sweep points); the worker always executes
        # through the execution orchestrator, so keep only its inputs —
        # plus dotted keys in its open namespaces (harness.* kwargs).
        schema = ExecutionOrchestrator.schema
        allowed = {s.name for s in schema.inputs}
        inputs = {k: v for k, v in raw_inputs.items()
                  if k in allowed
                  or ("." in k and k.split(".", 1)[0] in schema.open_namespaces)}
        ex = ExecutionOrchestrator(
            inputs=inputs,
            harness=tagged,
            store=store,
            resource_scope=resource_scope,
            worker_id=worker_id,
            reference_fingerprint=reference_fingerprint,
        )
        inj = _injections_from_payload(payload.get("injections"))
        if duet:
            # The whole duet is ONE queue task, so every interleaved
            # invocation of the pair runs on this worker — the pinning the
            # paired gate's noise-cancellation argument depends on.
            adopted_id: Optional[str] = None
            slots: Dict[Tuple[int, str], Report] = {}
            if attempt > 1 and record:
                adopted_id, slots = _duet_adopted(store, prefix, task_uid)
            invocations = ex.run_duet(
                spec, inj, duet_id=adopted_id, skip=set(slots))
            results = [
                CellResult(spec, rep,
                           Readiness(int(rep.parameter.get("readiness", 0))))
                for rep in slots.values()
            ] + invocations
            res = reduce_duet(spec, results)
            return base | {
                "cell": spec.cell,
                "readiness": int(res.readiness),
                "error": res.error,
                "report": res.report.to_dict() if res.report is not None else None,
                "duet": {
                    "rounds": int(raw_inputs.get("duet_rounds", 4)),
                    "invocations": len(results),
                    "adopted": len(slots),
                },
            }
        res = ex.run_cell(spec, inj)
        return base | {
            "cell": spec.cell,
            "readiness": int(res.readiness),
            "error": res.error,
            "report": res.report.to_dict() if res.report is not None else None,
        }

    try:
        out = _run()
    except LeaseLostError as e:
        # A fenced append outside the orchestrator's own retry loop: the
        # report was dropped, nothing reached the store from this attempt.
        out = base | {
            "cell": payload.get("spec", {}).get("arch", "?"),
            "readiness": 0,
            "error": str(e),
            "report": None,
        }
    except Exception as e:  # noqa: BLE001 — a worker must never die on one cell
        out = base | {
            "cell": payload.get("spec", {}).get("arch", "?"),
            "readiness": 0,
            "error": f"{type(e).__name__}: {e}\n{traceback.format_exc(limit=3)}",
            "report": None,
        }
    if fence is not None and not fence():
        # Post-execution ownership check.  A LeaseLostError raised inside
        # run_cell is swallowed by its per-cell retry (it surfaces as a
        # FAILED result) — without this check the worker would go on to
        # write that FAILED marker and could beat the retry's good one.
        out = dict(out)
        out["fenced"] = True
    if fenced_store is not None and fenced_store.append_failed:
        # The store path is sick (append failed even after bounded retries):
        # this is the worker's problem, not the cell's — the caller must
        # self-fence (release the lease for a retry elsewhere) instead of
        # recording a terminal FAILED marker.
        out = dict(out)
        out["store_failed"] = True
    return out


def _release_quietly(queue: WorkQueue, idx: int, worker_id: str, attempt: int,
                     max_attempts: int) -> None:
    """Best-effort charged release: when even the release path errors the
    lease simply ages out and the reclaimer charges the attempt instead."""
    try:
        queue.release(idx, worker_id, attempt, charge=True,
                      max_attempts=max_attempts)
    except OSError:
        pass


def worker_main(worker_id: str, queue_root: str, config: Dict[str, Any]) -> None:
    """Spawn entrypoint: drain the queue until the campaign finishes.

    Runs in a fresh interpreter — everything it needs arrives as plain data
    in ``config`` (see :class:`WorkerConfig`).  A bare ``worker_id`` (no
    ``:``) is treated as a *label* and expanded to the full
    ``host:pid:label`` identity, so every lease, done marker, and report
    carries the provenance needed to attribute work across hosts.
    """
    if ":" not in worker_id:
        worker_id = worker_identity(worker_id)
    host = host_of(worker_id)
    cfg = WorkerConfig.from_dict(config)
    queue = WorkQueue(queue_root, lease_timeout=cfg.lease_timeout)
    store = ResultStore(cfg.store_root, backend=cfg.store_backend)
    harness = resolve_harness(cfg.harness_ref, cfg.harness_kwargs)
    queue.register_worker(worker_id, host=host, pid=os.getpid())
    idle_since = time.monotonic()
    last_done = queue.done_count()
    # Ambient injection frames do NOT survive spawn — re-enter them here so
    # every cell this worker runs sees the campaign's environment.
    with injected_env(cfg.env):
        while True:
            queue.touch_worker(worker_id)
            try:
                claim = call_with_retry(
                    lambda: queue.claim_next(worker_id, host=host),
                    label="queue.claim")
            except OSError:
                # Queue root unreadable even after bounded retries: this
                # worker's filesystem view is sick — exit instead of
                # spinning (the broker's respawn budget covers a fresh
                # process; other hosts keep draining).
                return
            if claim is None:
                if queue.finished() or queue.stop_requested():
                    return
                try:
                    queue.reclaim_expired(max_attempts=cfg.max_attempts)
                except OSError:
                    pass  # reclaim is cooperative; another pass will win
                # Campaign progress = liveness: while *other* workers are
                # finishing cells, this one must keep polling even with
                # nothing claimable — the remaining long-running cells may
                # yet be reclaimed onto it.  Only bail when both claims AND
                # progress have stalled for idle_timeout.
                done = queue.done_count()
                if done != last_done:
                    last_done = done
                    idle_since = time.monotonic()
                if time.monotonic() - idle_since > cfg.idle_timeout:
                    return
                time.sleep(cfg.poll_s)
                continue
            idle_since = time.monotonic()
            idx, payload, attempt = claim
            chaos.trip("worker.claimed")
            beat = _Heartbeat(queue, idx, cfg.heartbeat_s())
            beat.start()
            try:
                result = _execute_payload(
                    payload, store=store, harness=harness,
                    worker_id=worker_id, attempt=attempt,
                    reference_fingerprint=cfg.reference_fingerprint or None,
                    # The fence folds in heartbeat health: a lease this
                    # worker can no longer refresh (or that vanished) must
                    # fence appends promptly, not only after a reclaimer
                    # happens to race us.
                    fence=lambda i=idx, a=attempt: (
                        not beat.lost.is_set()
                        and queue.owns(i, worker_id, a)))
            finally:
                beat.stop()
            if result.get("store_failed"):
                # Self-fence: the report could not be persisted even with
                # retries.  Hand the cell back charged (bounded attempts)
                # and exit — this worker's store path cannot be trusted.
                _release_quietly(queue, idx, worker_id, attempt,
                                 cfg.max_attempts)
                return
            if beat.lost.is_set():
                # Heartbeat died while executing: release promptly (charged)
                # instead of leaving the lease to age out under a reclaimer.
                _release_quietly(queue, idx, worker_id, attempt,
                                 cfg.max_attempts)
                continue
            if result.get("fenced") or not queue.owns(idx, worker_id, attempt):
                # Lease reclaimed while executing: the retry owns this cell.
                # Our marker (possibly stale or FAILED) must not contest the
                # first-writer race against the retry's result.
                continue
            chaos.trip("worker.pre_complete")
            try:
                queue.complete(idx, result)
            except OSError:
                # The report (if any) is already persisted under its
                # task_uid; releasing charged lets the retry adopt it.
                _release_quietly(queue, idx, worker_id, attempt,
                                 cfg.max_attempts)


# ---------------------------------------------------------------------------
# Broker side
# ---------------------------------------------------------------------------

def _worker_platform(env: Dict[str, str]) -> str:
    """The platform JAX picks in a worker spawned with ``env`` on top of
    this process's environment."""
    platforms = env.get("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", ""))
    if platforms:
        return platforms.split(",")[0]
    import jax

    return jax.default_backend()


class CampaignBroker:
    """Materializes cells into a work queue and supervises the worker pool.

    The broker never executes cells itself: its monitor loop only watches
    completion, reclaims expired leases, and respawns dead workers (bounded
    by ``workers * max_attempts`` — a systematically crashing campaign must
    terminate, not flap forever).
    """

    def __init__(
        self,
        store: ResultStore,
        *,
        workers: int = 4,
        name: str = "campaign",
        lease_timeout: float = DEFAULT_LEASE_TIMEOUT,
        heartbeat_interval: float = 0.0,
        max_attempts: int = DEFAULT_MAX_ATTEMPTS,
        poll_s: float = 0.1,
        queue_root: Optional[Path] = None,
        env: Optional[Dict[str, str]] = None,
        deadline_s: Optional[float] = None,
        keep_queue: bool = False,
    ):
        self.store = store
        self.workers = max(1, int(workers))
        self.name = name
        self.lease_timeout = float(lease_timeout)
        self.heartbeat_interval = float(heartbeat_interval)
        self.max_attempts = max(1, int(max_attempts))
        self.poll_s = float(poll_s)
        self.queue_root = Path(queue_root) if queue_root else (
            Path(store.root) / QUEUE_DIRNAME / f"{name}-{uuid.uuid4().hex[:8]}")
        self.env = dict(env or {})
        self.deadline_s = deadline_s
        self.keep_queue = keep_queue
        self.queue: Optional[WorkQueue] = None
        self.processes: List[Optional[mp.process.BaseProcess]] = []

    def _config(self, harness: Harness) -> WorkerConfig:
        ref, kwargs = spawn_spec_for(harness)
        backend = getattr(self.store.backend, "name", "dir")
        if backend not in ("dir", "jsonl"):
            raise PipelineError(
                f"store backend {backend!r} is not shareable across worker "
                "processes (need a filesystem-backed backend)")
        return WorkerConfig(
            store_root=str(self.store.root),
            store_backend=backend,
            harness_ref=ref,
            harness_kwargs=kwargs,
            env=self.env,
            lease_timeout=self.lease_timeout,
            heartbeat_interval=self.heartbeat_interval,
            max_attempts=self.max_attempts,
            # One reference for the whole pool: every worker compares its
            # own capture against the broker's, not against itself.
            reference_fingerprint=fingerprint_mod.capture(),
        )

    def materialize(self, payloads: Sequence[Dict[str, Any]]) -> WorkQueue:
        queue = WorkQueue(self.queue_root, lease_timeout=self.lease_timeout)
        queue.create(list(payloads), campaign=self.name)
        self.queue = queue
        return queue

    def publish(self, payloads: Sequence[Dict[str, Any]], *,
                harness: Harness) -> WorkQueue:
        """Materialize the queue AND publish ``worker_config.json`` into it,
        so workers launched out-of-band — ``python -m repro.core.workers``
        on any host sharing the filesystem — can join the drain with the
        same store/harness/lease configuration as the local pool."""
        cfg = self._config(harness).to_dict()   # validate before mutating
        queue = self.materialize(payloads)
        _atomic_json(self.queue_root / WORKER_CONFIG, cfg)
        return queue

    def _synthesized(self, payloads: Sequence[Dict[str, Any]],
                     error: str) -> Dict[int, Dict[str, Any]]:
        return {
            idx: {
                "task_uid": payloads[idx].get("task_uid", ""),
                "readiness": 0,
                "error": error,
                "attempts": 0,
                "report": None,
            }
            for idx in range(len(payloads))
        }

    def _refuse_device_harnesses(self, payloads: Sequence[Dict[str, Any]],
                                 harness: Harness) -> None:
        """A TPU belongs to one process: workers that each import JAX cannot
        share it.  Refuse device-bound harnesses (the campaign default and
        any a payload declares) before anything is spawned; thread mode is
        the chip path."""
        from repro import harnesses as harness_families

        declared = [harness_families.from_inputs(p.get("inputs", {})) for p in payloads]
        bound = sorted({h.name for h in [harness, *declared] if h is not None and h.uses_device})
        if bound and _worker_platform(self.env) == "tpu":
            raise PipelineError(
                f"harness {', '.join(bound)} runs JAX on the TPU, which one process "
                "holds; process workers cannot share it — use worker_mode: thread")

    def run(self, payloads: Sequence[Dict[str, Any]], *, harness: Harness) -> Dict[int, Dict[str, Any]]:
        """Drain ``payloads`` through the worker pool; returns the terminal
        result dict for every cell index (synthesized failure records for
        cells that never completed — the caller always gets len(payloads)
        answers).

        Degraded mode: an unusable queue root (unreadable, out of space)
        yields synthesized failure records for every cell instead of an
        exception — a broker embedded in the daemon must report a sick
        filesystem, not crash the service.
        """
        payloads = list(payloads)
        cfg = self._config(harness).to_dict()
        self._refuse_device_harnesses(payloads, harness)
        try:
            queue = self.materialize(payloads)
            _atomic_json(self.queue_root / WORKER_CONFIG, cfg)
        except OSError as e:
            return self._synthesized(
                payloads, f"queue root unusable at {self.queue_root}: {e}")
        ctx = mp.get_context("spawn")  # spawn-safe by construction
        spawned = 0

        def _spawn() -> mp.process.BaseProcess:
            nonlocal spawned
            spawned += 1
            p = ctx.Process(
                target=worker_main,
                args=(f"{self.name}-w{spawned}", str(self.queue_root), cfg),
                daemon=True,
                name=f"exacb-worker-{spawned}",
            )
            p.start()
            return p

        self.processes = [_spawn() for _ in range(min(self.workers, len(payloads)))]
        respawn_budget = self.workers * self.max_attempts
        t0 = time.monotonic()
        try:
            while not queue.finished():
                try:
                    queue.reclaim_expired(max_attempts=self.max_attempts)
                except OSError:
                    pass  # cooperative: workers also reclaim; retry next tick
                if queue.finished():
                    break
                for i, proc in enumerate(self.processes):
                    if proc is not None and proc.exitcode is not None:
                        proc.join()
                        if spawned < respawn_budget:
                            self.processes[i] = _spawn()
                        else:
                            self.processes[i] = None
                if all(p is None for p in self.processes):
                    break  # respawn budget exhausted with work outstanding
                if self.deadline_s is not None and time.monotonic() - t0 > self.deadline_s:
                    break
                time.sleep(self.poll_s)
        finally:
            try:
                queue.request_stop()
            except OSError:
                pass  # workers still exit via idle timeout
            for proc in self.processes:
                if proc is None:
                    continue
                proc.join(timeout=2 * self.lease_timeout)
                if proc.is_alive():
                    proc.terminate()
                    proc.join(timeout=5)
        results = queue.results()
        for idx in range(len(payloads)):
            results.setdefault(idx, {
                "task_uid": payloads[idx].get("task_uid", ""),
                "readiness": 0,
                "error": "cell never completed (worker pool exhausted or deadline hit)",
                "attempts": 0,
                "report": None,
            })
        if not self.keep_queue:
            import shutil
            shutil.rmtree(self.queue_root, ignore_errors=True)
        return results


# ---------------------------------------------------------------------------
# Payload construction + collection entrypoint
# ---------------------------------------------------------------------------

def cell_payload(
    spec: BenchmarkSpec,
    inputs: Dict[str, Any],
    *,
    component_ref: str = "execution@v4",
    call_index: int = 0,
    cell_index: int = 0,
    injections: Optional[Injections] = None,
) -> Dict[str, Any]:
    """One queue task: pure data, dispatchable by any interpreter."""
    if injections is not None and injections.launcher is not None:
        raise PipelineError(
            "launcher injection (a callable) cannot cross the process "
            "boundary; run launcher-injected cells in thread mode")
    return {
        "component_ref": component_ref,
        "call_index": int(call_index),
        "cell_index": int(cell_index),
        "prefix": inputs.get("prefix", "default"),
        "record": bool(inputs.get("record", True)),
        "inputs": dict(inputs),
        "spec": dataclasses.asdict(spec),
        "injections": (
            {"env": dict(injections.env), "overrides": dict(injections.overrides)}
            if injections is not None else None),
    }


def run_collection_process(
    *,
    inputs: Dict[str, Any],
    harness: Harness,
    store: ResultStore,
    specs: Sequence[BenchmarkSpec],
    injections: Optional[Injections] = None,
    workers: int = 4,
    **broker_kwargs: Any,
):
    """Process-mode twin of ``ExecutionOrchestrator.run_collection``: same
    specs in, same ordered ``CellResult`` list out, but drained by spawned
    workers through the broker."""
    from repro.core.orchestrator import CellResult  # lazy: cycle

    specs = list(specs)
    payloads = [
        cell_payload(spec, dict(inputs), cell_index=i, injections=injections)
        for i, spec in enumerate(specs)
    ]
    name = f"collection-{inputs.get('prefix', 'default')}"
    broker = CampaignBroker(store, workers=workers, name=name, **broker_kwargs)
    results = broker.run(payloads, harness=harness)
    out: List[CellResult] = []
    for i, spec in enumerate(specs):
        out.append(result_to_cell(spec, results.get(i)))
    return out


def result_to_cell(spec: BenchmarkSpec, result: Optional[Dict[str, Any]]):
    """Done-marker dict → CellResult (shared by collection and pipeline
    process paths)."""
    from repro.core.orchestrator import CellResult  # lazy: cycle

    if result is None:
        return CellResult(spec, None, Readiness.FAILED,
                          error="no result recorded for cell")
    report = None
    if result.get("report"):
        try:
            report = Report.from_dict(result["report"])
        except Exception as e:  # noqa: BLE001 — a torn marker is a failure
            return CellResult(spec, None, Readiness.FAILED,
                              error=f"unreadable result marker: {e}")
    return CellResult(
        spec,
        report,
        Readiness(int(result.get("readiness", 0))),
        error=result.get("error"),
        attempts=int(result.get("attempts", 1)),
    )


def pipeline_payloads(calls: Sequence[Any]) -> Tuple[List[Dict[str, Any]], Dict[int, List[int]]]:
    """Materialize every *producer* call of a pipeline into queue payloads.

    Returns ``(payloads, owners)`` where ``owners[call_index]`` lists the
    payload indices belonging to that call — a feature-injection sweep
    contributes one payload per sweep point, so its points drain across the
    whole worker pool instead of serializing inside one call."""
    from repro.core.orchestrator import (  # lazy: cycle
        _injections_from_inputs, spec_from_inputs)

    payloads: List[Dict[str, Any]] = []
    owners: Dict[int, List[int]] = {}
    for ci, call in enumerate(calls):
        if call.name not in ("execution", "feature-injection"):
            continue
        inputs = call.inputs
        spec = spec_from_inputs(inputs)
        points: List[Optional[Injections]]
        if call.name == "execution":
            points = [None]
        else:
            base = _injections_from_inputs(inputs)
            values = inputs.get("values")
            if values:
                if not (inputs.get("env_knob") or inputs.get("override_knob")):
                    raise PipelineError(
                        f"{inputs.component}: 'values' needs an 'env_knob' "
                        "or 'override_knob' to sweep")
                points = []
                for v in values:
                    inj = Injections(env=dict(base.env), overrides=dict(base.overrides))
                    if inputs.get("env_knob"):
                        inj.env[inputs["env_knob"]] = str(v)
                    if inputs.get("override_knob"):
                        inj.overrides[inputs["override_knob"]] = v
                    points.append(inj)
            else:
                points = [base]
        owners[ci] = []
        for k, inj in enumerate(points):
            owners[ci].append(len(payloads))
            payloads.append(cell_payload(
                spec, dict(inputs), component_ref=inputs.component or call.ref,
                call_index=ci, cell_index=k, injections=inj))
    return payloads, owners


# ---------------------------------------------------------------------------
# Multi-host entry point: `python -m repro.core.workers <queue-root>`
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    """Join a campaign drain from any host sharing the store filesystem.

    The broker publishes ``worker_config.json`` into the queue root when it
    materializes a campaign (see :meth:`CampaignBroker.publish`); this entry
    point reads it, composes a ``host:pid:label`` identity (host from
    ``$EXACB_HOST`` or the hostname), and drains until the campaign
    finishes.  Exit code 0 = drained to completion, 2 = queue/config
    unusable.
    """
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.core.workers",
        description="join a campaign work queue as a remote worker",
    )
    ap.add_argument("queue_root", help="the campaign's queue directory "
                                       "(<store>/_queue/<name>-<id>)")
    ap.add_argument("--harness", default="",
                    help="module:factory harness override (default: the "
                         "recipe published in worker_config.json)")
    ap.add_argument("--label", default="",
                    help="worker label; the full id is host:pid:label "
                         "(default: a random 8-hex label)")
    ap.add_argument("--host", default="",
                    help="host identity override (default: $EXACB_HOST or "
                         "the hostname)")
    args = ap.parse_args(argv)

    queue_root = Path(args.queue_root)
    try:
        config = json.loads((queue_root / WORKER_CONFIG).read_text())
    except (OSError, ValueError) as e:
        print(f"error: no usable {WORKER_CONFIG} under {queue_root}: {e}\n"
              "(the broker publishes it when the campaign is materialized)",
              flush=True)
        return 2
    if args.harness:
        config["harness_ref"] = args.harness
        config["harness_kwargs"] = {}
    if args.host:
        os.environ[HOST_ENV] = args.host
    worker_id = worker_identity(args.label)
    print(f"worker {worker_id} joining queue {queue_root}", flush=True)
    worker_main(worker_id, str(queue_root), config)
    return 0


if __name__ == "__main__":  # pragma: no cover — exercised via subprocess
    raise SystemExit(main())
