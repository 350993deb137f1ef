"""DryRunHarness — the production-mesh harness adapter.

Runs ``repro.launch.dryrun`` in a SUBPROCESS (exactly how a CI job would
launch it: the dry-run needs 512 placeholder devices, which must be set
before jax initializes) and converts the JSON record into a protocol Report.
Feature injections map onto the dry-run CLI knobs — the benchmark definition
itself is never edited.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import Optional

import jax

from repro.core import protocol
from repro.core.harness import BenchmarkSpec, Harness, HarnessCapabilities, Injections
from repro.core.readiness import Readiness


class DryRunHarness(Harness):
    name = "dryrun"

    def capabilities(self) -> HarnessCapabilities:
        # The dry-run subprocess takes env vars and config-knob overrides
        # via CLI flags, but a launcher CALLABLE cannot cross the process
        # boundary — declaring that honestly lets negotiation reject e.g.
        # an energy-launcher injection before the subprocess is spawned.
        return HarnessCapabilities(
            max_readiness=Readiness.REPRODUCIBLE,
            launcher_injection=False,
        )

    def __init__(
        self,
        *,
        repo_root: Optional[Path] = None,
        timeout_s: int = 3600,
        raw_dir: Optional[Path] = None,
    ):
        self.repo_root = Path(repo_root or Path(__file__).resolve().parents[3])
        self.timeout_s = timeout_s
        self.raw_dir = Path(raw_dir) if raw_dir else None
        if self.raw_dir:
            self.raw_dir.mkdir(parents=True, exist_ok=True)

    def spawn_spec(self):
        # All construction state is path/scalar data, so dry-run cells run
        # under spawned process workers: the worker rebuilds the harness and
        # the cell's real work happens in the dry-run SUBPROCESS it launches
        # (process-scope accounting picks the child up via os.times).
        return "repro.core.dryrun_harness:DryRunHarness", {
            "repo_root": str(self.repo_root),
            "timeout_s": self.timeout_s,
            "raw_dir": str(self.raw_dir) if self.raw_dir else None,
        }

    def run(self, spec: BenchmarkSpec, injections: Optional[Injections] = None) -> protocol.Report:
        inj = injections or Injections()
        multi_pod = "2pods" in spec.system
        with tempfile.TemporaryDirectory() as td:
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", spec.arch, "--shape", spec.shape, "--out", td,
            ]
            if multi_pod:
                cmd.append("--multi-pod")
            for knob, flag in (
                ("strategy", "--strategy"), ("remat", "--remat"),
                ("microbatches", "--microbatches"), ("opt_state_dtype", "--opt-state"),
                ("global_batch", "--global-batch"),
            ):
                if knob in inj.overrides:
                    cmd += [flag, str(inj.overrides[knob])]
            env = dict(os.environ)
            env["PYTHONPATH"] = str(self.repo_root / "src")
            env.update(inj.env)
            # The child compiles on host devices only; left to pick the
            # TPU it would contend with this process for the chip.
            env["JAX_PLATFORMS"] = "cpu"
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=self.timeout_s, env=env,
                cwd=self.repo_root,
            )
            tag = "2pod" if multi_pod else "1pod"
            rec_path = Path(td) / f"{spec.arch}.{spec.shape}.{tag}.json"
            if not rec_path.exists():
                raise RuntimeError(
                    f"dry-run produced no record (rc={proc.returncode}):\n"
                    f"{proc.stderr[-2000:]}"
                )
            rec = json.loads(rec_path.read_text())
            if self.raw_dir:
                suffix = ""
                if inj.overrides:
                    suffix = "." + "_".join(
                        f"{k}-{v}" for k, v in sorted(inj.overrides.items())
                    )
                (self.raw_dir / f"{spec.arch}.{spec.shape}.{tag}{suffix}.json").write_text(
                    json.dumps(rec, indent=2)
                )
        if rec.get("status") == "error":
            raise RuntimeError(f"dry-run cell failed: {rec.get('error')}")

        report = protocol.new_report(
            system=spec.system,
            variant=spec.effective_variant(),
            usecase=spec.shape,
            software_version=jax.__version__,
            parameter={
                "arch": spec.arch,
                "scale": "production-dryrun",
                "strategy": rec.get("strategy"),
                "knobs": rec.get("knobs", {}),
                "injections": inj.describe(),
            },
        )
        if rec.get("status") == "skipped":
            report.parameter["skipped"] = rec.get("reason", "")
            return report
        if rec.get("status") != "ok":
            entry = protocol.DataEntry(
                success=False, runtime=0.0,
                metrics={"error": rec.get("error", "unknown")},
            )
            report.data.append(entry)
            return report

        rl = rec["roofline"]
        digest = hashlib.sha256(
            json.dumps(rec["roofline"], sort_keys=True).encode()
        ).hexdigest()[:16]
        entry = protocol.DataEntry(
            success=True,
            runtime=rec["compile_s"],
            nodes=512 if multi_pod else 256,
            tasks_per_node=1,
            queue="dryrun",
            job_id=f"dryrun-{spec.cell}",
            metrics={
                "hlo_flops": rl["hlo_flops"],
                "hlo_bytes": rl["hlo_bytes"],
                "collective_bytes": rl["collective_bytes"],
                "t_compute": rl["t_compute"],
                "t_memory": rl["t_memory"],
                "t_collective": rl["t_collective"],
                "dominant": rl["dominant"],
                "useful_ratio": rl["useful_ratio"],
                "model_flops": rl["model_flops"],
                "roofline_fraction": rl["roofline_fraction"],
                "step_time_bound_s": rl["step_time_bound_s"],
                "hbm_required": rl["hbm_required"],
                "fits": rl["fits"],
                "artifact_digest": digest,
                "seed": spec.seed,
            },
        )
        report.data.append(entry)
        return report
