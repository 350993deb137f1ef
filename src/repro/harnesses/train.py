"""TrainHarness: short real trainer runs reporting step time and loss.

Unlike ``ExecHarness`` (which times a single hand-built fwd+bwd step),
this drives ``repro.train.trainer.train`` itself — optimizer update,
data pipeline, remat/microbatch plumbing included — so a cell measures
what a training job actually pays per step.  Remat and microbatch
feature-injections map onto the corresponding ``TrainConfig`` fields.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from repro.core import protocol
from repro.core.harness import (
    BenchmarkSpec,
    Harness,
    HarnessCapabilities,
    Injections,
    injected_env,
)
from repro.core.readiness import Readiness


class TrainHarness(Harness):
    """Runs a short smoke-scale training loop per model config."""

    name = "train"
    uses_device = True

    def __init__(self, *, steps: int = 3, seq_len: int = 32, global_batch: int = 2):
        self.steps = int(steps)
        self.seq_len = int(seq_len)
        self.global_batch = int(global_batch)

    def capabilities(self) -> HarnessCapabilities:
        # Trainer steps only; prefill/decode cells fail negotiation.  The
        # launcher contract wraps a bare step callable, which the trainer
        # does not expose — wrapping train() would time the whole run.
        return HarnessCapabilities(
            max_readiness=Readiness.REPRODUCIBLE,
            step_kinds=frozenset({"train"}),
            launcher_injection=False,
        )

    def spawn_spec(self):
        return "repro.harnesses.train:TrainHarness", {
            "steps": self.steps, "seq_len": self.seq_len,
            "global_batch": self.global_batch,
        }

    def run(self, spec: BenchmarkSpec, injections: Optional[Injections] = None) -> protocol.Report:
        import jax

        from repro import configs
        from repro.data.pipeline import DataConfig
        from repro.train.trainer import TrainConfig, train

        inj = injections or Injections()
        ov = inj.overrides
        steps = int(ov.get("steps", self.steps))

        report = protocol.new_report(
            system=spec.system,
            variant=spec.effective_variant(),
            usecase=spec.shape,
            software_version=jax.__version__,
            parameter={
                "arch": spec.arch,
                "device_kind": jax.devices()[0].device_kind,
                "injections": inj.describe(),
                "scale": "train",
                "steps": steps,
            },
        )

        cfg = configs.get_smoke(spec.arch)
        tc = TrainConfig(
            steps=steps,
            log_every=10 ** 9,
            ckpt_every=10 ** 9,
            seed=spec.seed,
            remat=str(ov.get("remat", "none")),
            microbatches=int(ov.get("microbatches", 1)),
            data=DataConfig(
                seq_len=int(ov.get("seq_len", self.seq_len)),
                global_batch=int(ov.get("global_batch", self.global_batch)),
                seed=spec.seed,
            ),
        )

        with injected_env(inj.env):
            t0 = time.perf_counter()
            result = train(cfg, tc)
            runtime = time.perf_counter() - t0

        # Step 0 pays compilation; median over the remaining steps is the
        # steady-state figure (falls back to all steps for 1-step runs).
        steady = result.step_times[1:] or result.step_times
        entry = protocol.DataEntry(
            success=bool(np.isfinite(result.final_loss)),
            runtime=runtime,
            nodes=1,
            tasks_per_node=jax.device_count(),
            job_id=f"local-{os.getpid()}",
            queue=jax.devices()[0].platform,
            metrics={
                "step_time_s": float(np.median(steady)),
                "step_time_min_s": float(np.min(steady)),
                "final_loss": float(result.final_loss),
                "steps": float(result.final_step),
                "seed": spec.seed,
            },
        )
        report.data.append(entry)
        return report
