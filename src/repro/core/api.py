"""The ``Campaign`` facade — exaCB's single documented entry point.

Everything a continuous-benchmarking campaign needs sits behind one object:
the component registry (typed, versioned schemas), the campaign scheduler,
the result store, and the regression gates.  The ``python -m repro`` CLI
(``run`` / ``validate`` / ``components``) is a thin wrapper over this class,
and so is any library use::

    from repro.core.api import Campaign

    c = Campaign("exacb_data")
    c.validate("examples/pipelines/smoke.yml")   # schema-check, no execution
    results = c.run("examples/pipelines/smoke.yml", parallelism=2)
    print(c.report()["markdown"])                # cross-prefix summary
    verdict = c.gate("ci.smoke", metrics=["step_time_s"])

See ``docs/component_api.md`` for the full contract (schemas, registry,
migration shims, harness capability negotiation).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

from repro.core import cicd
from repro.core.component import (
    REGISTRY,
    ComponentContext,
    ComponentRegistry,
    PipelineError,
)
from repro.core.harness import Harness
from repro.core.store import ResultStore


def _pipeline_text(pipeline: Union[str, Path]) -> str:
    """A path (existing file) or a literal document (anything with a
    newline / JSON braces) — the CLI and tests use both freely."""
    s = str(pipeline)
    if "\n" not in s and not s.lstrip().startswith("{"):
        p = Path(s)
        if not p.exists():
            raise PipelineError(f"pipeline file not found: {s}")
        return p.read_text()
    return s


class Campaign:
    """Registry → scheduler → store → gates behind one object."""

    def __init__(
        self,
        store: Union[str, Path, ResultStore] = "exacb_data",
        *,
        backend: str = "dir",
        harness: Optional[Harness] = None,
        harness_factory: Optional[Callable[[Dict[str, Any]], Harness]] = None,
        parallelism: Optional[int] = None,
        registry: Optional[ComponentRegistry] = None,
    ):
        self._store_spec = store
        self._backend = backend
        self._store: Optional[ResultStore] = \
            store if isinstance(store, ResultStore) else None
        self.harness = harness
        self.harness_factory = harness_factory
        self.parallelism = parallelism
        self.registry = registry or REGISTRY

    @property
    def store(self) -> ResultStore:
        """Created lazily so read-only entry points (``validate``,
        ``components``) never touch the filesystem."""
        if self._store is None:
            self._store = ResultStore(self._store_spec, backend=self._backend)
        return self._store

    # ------------------------------------------------------------ pipelines
    def validate(self, pipeline: Union[str, Path]) -> List[Dict[str, Any]]:
        """Schema-check a pipeline document without executing anything.
        Returns one summary per component (resolved version, coerced inputs,
        DAG edges); raises ``PipelineError`` naming the offending component
        and field."""
        return cicd.validate_pipeline(_pipeline_text(pipeline),
                                      registry=self.registry)

    def run(self, pipeline: Union[str, Path], *,
            parallelism: Optional[int] = None,
            workers: Optional[int] = None,
            worker_mode: Optional[str] = None) -> List[Dict[str, Any]]:
        """Parse, validate, and dispatch a pipeline document through the
        component DAG and the campaign scheduler.  ``worker_mode="process"``
        (or any component declaring it) drains producer cells through the
        broker + spawned worker pool instead of the in-process threads."""
        calls = cicd.parse_pipeline_text(_pipeline_text(pipeline),
                                         registry=self.registry)
        return cicd.run_pipeline(
            calls,
            store=self.store,
            harness=self.harness,
            harness_factory=self.harness_factory,
            parallelism=parallelism if parallelism is not None else self.parallelism,
            registry=self.registry,
            workers=workers,
            worker_mode=worker_mode,
        )

    # ----------------------------------------------------------- components
    def components(self) -> List[Dict[str, Any]]:
        """Registry listing: every accepted component reference with its
        declared inputs (types, defaults, choices, deprecated aliases) —
        migration shims included."""
        return self.registry.describe()

    def component(self, name: str, version: int, inputs: Dict[str, Any],
                  **extra_inputs: Any) -> Any:
        """Run one component invocation directly (no document needed)."""
        resolved = self.registry.resolve(name, version)
        # Same harness default as cicd.run_pipeline, so a facade without an
        # explicit harness behaves identically to `python -m repro run`.
        harness = self.harness
        if harness is None and self.harness_factory is None:
            from repro.core.harness import ExecHarness

            harness = ExecHarness(steps=2, batch=2, seq=16)
        ctx = ComponentContext(store=self.store, harness=harness,
                               harness_factory=self.harness_factory)
        return resolved.run({**dict(inputs), **extra_inputs}, ctx)

    # ---------------------------------------------------------- collections
    def run_collection(
        self,
        system: Union[str, Sequence[str]],
        *,
        archs: Optional[List[str]] = None,
        shapes: Optional[List[str]] = None,
        prefix: str = "collection",
        require_readiness=None,
        parallelism: Optional[int] = None,
        workers: Optional[int] = None,
        worker_mode: Optional[str] = None,
        record: bool = True,
    ):
        """Expand the benchmark collection for ``system`` and run every cell
        through the execution orchestrator (failure-isolated, streamed into
        the store).  Requires a ``harness`` on the facade.
        ``worker_mode="process"`` drains the cells through the broker +
        spawned worker pool (the harness must declare a ``spawn_spec``)."""
        from repro.core import registry as collection_registry
        from repro.core.orchestrator import ExecutionOrchestrator

        if self.harness is None:
            raise PipelineError("Campaign.run_collection needs a harness")
        specs = collection_registry.collection(
            system, archs=archs, shapes=shapes,
            require_readiness=require_readiness)
        inputs: Dict[str, Any] = {
            "prefix": prefix, "record": record,
            "parallelism": parallelism or self.parallelism or 1,
        }
        if workers is not None:
            inputs["workers"] = workers
        if worker_mode is not None:
            inputs["worker_mode"] = worker_mode
        ex = ExecutionOrchestrator(
            inputs=inputs,
            harness=self.harness,
            store=self.store,
        )
        return ex.run_collection(specs)

    # ---------------------------------------------------------------- gates
    def gate(self, source_prefix: str, **inputs: Any) -> Dict[str, Any]:
        """Run a regression gate over one prefix's stored history; inputs
        follow the ``gate@v1`` schema."""
        return self.component("gate", 1,
                              {"source_prefix": source_prefix, **inputs})

    def report(self, metric: str = "step_time_s",
               prefixes: Optional[List[str]] = None) -> Dict[str, Any]:
        """Cross-prefix campaign summary (the ``campaign-report@v1``
        component) in one columnar scan."""
        inputs: Dict[str, Any] = {"metric": metric}
        if prefixes:
            inputs["prefixes"] = list(prefixes)
        return self.component("campaign-report", 1, inputs)


def main(argv=None) -> int:
    """``python -m repro`` — run / validate / components."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="repro",
        description="exaCB campaign entry point (typed component API)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="run a pipeline document")
    run.add_argument("pipeline")
    run.add_argument("--store", default="exacb_data")
    run.add_argument("--store-backend", default="dir", choices=("dir", "jsonl"))
    run.add_argument("--parallelism", type=int, default=None)
    run.add_argument("--workers", type=int, default=None,
                     help="execution-plane worker count")
    run.add_argument("--worker-mode", default=None,
                     choices=("thread", "process"),
                     help="process = broker + spawned worker pool with "
                          "lease-reclaimed crash recovery")
    run.add_argument("--gate", action="store_true",
                     help="enforce regression gates (exit 3 on regression)")
    run.add_argument("--gate-report", default="gate_report.json")

    val = sub.add_parser("validate",
                         help="schema-check a pipeline document, no execution")
    val.add_argument("pipeline")

    sub.add_parser("components",
                   help="list every registered component with its schema")

    aut = sub.add_parser(
        "autotune",
        help="sweep a pallas kernel's block grid, promote the winner into "
             "the autotune cache and as a pinned latency baseline")
    aut.add_argument("--kernel", required=True,
                     choices=("flash_attention", "rglru", "ssd"))
    aut.add_argument("--store", default="exacb_data")
    aut.add_argument("--store-backend", default="dir", choices=("dir", "jsonl"))
    aut.add_argument("--prefix", default=None,
                     help="store prefix (default: autotune.<kernel>)")
    for knob in ("block-q", "block-k", "chunk", "block-w"):
        aut.add_argument(f"--{knob}", default=None,
                         help=f"comma-separated {knob.replace('-', '_')} "
                              "candidates")
    for dim, dv in (("batch", 1), ("heads", 2), ("seq", 128),
                    ("head-dim", 16), ("width", 64), ("state", 16)):
        aut.add_argument(f"--{dim}", type=int, default=dv)
    aut.add_argument("--dtype", default="float32")
    aut.add_argument("--calls", type=int, default=3)
    aut.add_argument("--warmup", type=int, default=1)
    aut.add_argument("--confirm", type=int, default=3)
    aut.add_argument("--cache", default="",
                     help="cache file (default: <store>/autotune_cache.json)")
    aut.add_argument("--interpret", action="store_true",
                     help="run the pallas kernel in interpret mode (no TPU)")
    aut.add_argument("--no-baseline", action="store_true",
                     help="skip pinning the winner as the gate baseline")
    aut.add_argument("--force", action="store_true",
                     help="re-sweep even on a cache hit")

    def _daemon_common(p):
        p.add_argument("documents", nargs="+",
                       help="pipeline documents to watch (schedule@v1 "
                            "declares each document's refresh policy)")
        p.add_argument("--store", default="exacb_data")
        p.add_argument("--store-backend", default="dir",
                       choices=("dir", "jsonl"))
        p.add_argument("--state", default=None,
                       help="daemon state file (default: "
                            "<store>/daemon_state.json)")
        p.add_argument("--target-lag", type=float, default=None,
                       help="override every document's target_lag (seconds)")

    dmn = sub.add_parser(
        "daemon",
        help="continuous service: re-execute cells on declarative triggers "
             "(lag / downstream / watermark), resuming from the store")
    _daemon_common(dmn)
    dmn.add_argument("--interval", type=float, default=None,
                     help="override the tick interval (seconds)")
    dmn.add_argument("--workers", type=int, default=2)
    dmn.add_argument("--worker-mode", default="thread",
                     choices=("thread", "process"),
                     help="refresh dispatch: in-process scheduler, or "
                          "broker + spawned worker pool")
    dmn.add_argument("--max-ticks", type=int, default=None,
                     help="exit cleanly after N ticks (CI / smoke mode)")

    dst = sub.add_parser(
        "daemon-status",
        help="per-document lag / last-refresh / next-due / queue-depth "
             "from the state file and store (no running daemon needed)")
    _daemon_common(dst)
    dst.add_argument("--json", action="store_true", dest="as_json",
                     help="machine-readable output")
    dst.add_argument("--clear-quarantine", nargs="?", const="", default=None,
                     dest="clear_quarantine", metavar="CELL",
                     help="lift quarantine before reporting: pass a cell key "
                          "to clear one cell, or no value to clear every "
                          "quarantined cell")
    dst.add_argument("--suspend", default=None, metavar="DOC",
                     help="park one document's schedule (path or basename): "
                          "persisted in the state file and skipped by every "
                          "staleness scan until resumed")
    dst.add_argument("--resume", default=None, metavar="DOC",
                     help="lift a suspension set with --suspend")

    args = ap.parse_args(argv)
    if args.cmd == "run":
        # Delegate to the cicd CLI so gate-report/exit-code semantics stay
        # in exactly one place.
        cicd_args = [args.pipeline, "--store", args.store,
                     "--store-backend", args.store_backend]
        if args.parallelism is not None:
            cicd_args += ["--parallelism", str(args.parallelism)]
        if args.workers is not None:
            cicd_args += ["--workers", str(args.workers)]
        if args.worker_mode is not None:
            cicd_args += ["--worker-mode", args.worker_mode]
        if args.gate:
            cicd_args += ["--gate", "--gate-report", args.gate_report]
        return cicd.main(cicd_args)
    if args.cmd == "validate":
        # Same delegation as `run`: one implementation of the INVALID/OK
        # reporting and exit codes, in cicd.main.
        return cicd.main([args.pipeline, "--validate"])
    if args.cmd == "daemon":
        from repro.core.daemon import CampaignDaemon

        try:
            daemon = CampaignDaemon(
                args.store, args.documents,
                backend=args.store_backend,
                state_path=args.state,
                workers=args.workers,
                worker_mode=args.worker_mode,
                target_lag=args.target_lag,
                interval=args.interval,
                max_ticks=args.max_ticks,
            )
        except (OSError, PipelineError) as e:
            import sys
            print(f"daemon: {e}", file=sys.stderr)
            return 1
        return daemon.run()
    if args.cmd == "autotune":
        import sys

        def _ints(s):
            return [int(v) for v in s.split(",") if v.strip()] if s else []

        inputs = {
            "kernel": args.kernel,
            "prefix": args.prefix or f"autotune.{args.kernel}",
            "block_q": _ints(args.block_q), "block_k": _ints(args.block_k),
            "chunk": _ints(args.chunk), "block_w": _ints(args.block_w),
            "batch": args.batch, "heads": args.heads, "seq": args.seq,
            "head_dim": args.head_dim, "width": args.width,
            "state": args.state, "dtype": args.dtype,
            "calls": args.calls, "warmup": args.warmup,
            "confirm": args.confirm, "cache": args.cache,
            "baseline": not args.no_baseline, "force": args.force,
        }
        if args.interpret:
            inputs["interpret"] = True
        try:
            out = Campaign(args.store, backend=args.store_backend).component(
                "autotune", 1, inputs)
        except PipelineError as e:
            print(f"autotune: {e}", file=sys.stderr)
            return 1
        print(json.dumps(out, indent=2, default=str))
        return 1 if out.get("error") else 0
    if args.cmd == "daemon-status":
        from repro.core.daemon import CampaignDaemon, daemon_status, render_status

        try:
            wants_daemon = (args.clear_quarantine is not None
                            or args.suspend or args.resume)
            if wants_daemon:
                daemon = CampaignDaemon(
                    args.store, args.documents,
                    backend=args.store_backend,
                    state_path=args.state,
                    target_lag=args.target_lag,
                )
                if args.clear_quarantine is not None:
                    for key in daemon.clear_quarantine(
                            args.clear_quarantine or None):
                        print(f"cleared quarantine: {key}")
                if args.suspend:
                    for path in daemon.suspend(args.suspend):
                        print(f"suspended: {path}")
                if args.resume:
                    for path in daemon.resume(args.resume):
                        print(f"resumed: {path}")
            status = daemon_status(
                args.store, args.documents,
                backend=args.store_backend,
                state_path=args.state,
                target_lag=args.target_lag,
            )
        except (OSError, PipelineError) as e:
            import sys
            print(f"daemon-status: {e}", file=sys.stderr)
            return 1
        if args.as_json:
            print(json.dumps(status, indent=2, default=str))
        else:
            print(render_status(status))
        return 0
    print(json.dumps(Campaign().components(), indent=2, default=str))
    return 0
