"""ServeHarness: drive the batched serve engine under Poisson load.

Heavy traffic, measured instead of imagined: requests arrive on a seeded
Poisson process at ``rate_rps``, the engine admits them greedily in waves
of ``batch`` (the engine's own scheduling policy), and each wave's
*measured* wall time advances a virtual clock.  Per-request latency is
wave-completion minus arrival, so queueing delay is part of the number —
a saturated engine shows it in P95/P99, not just in throughput.

The arrival process is pure numpy (`poisson_arrivals`) and deterministic
under a fixed seed; only the service times are measured.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from repro.core import protocol
from repro.core.harness import (
    BenchmarkSpec,
    Harness,
    HarnessCapabilities,
    Injections,
    artifact_digest,
    injected_env,
)
from repro.core.readiness import Readiness


def poisson_arrivals(n: int, rate_rps: float, seed: int) -> np.ndarray:
    """Arrival times (seconds from t=0) of ``n`` requests at ``rate_rps``."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be positive, got {rate_rps}")
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_rps, size=max(0, int(n)))
    return np.cumsum(gaps)


class ServeHarness(Harness):
    """Poisson load generator over ``serve.engine.Engine``."""

    name = "serve"
    uses_device = True

    def __init__(
        self,
        *,
        batch: int = 2,
        max_len: int = 48,
        requests: int = 6,
        prompt_len: int = 4,
        max_new_tokens: int = 4,
        rate_rps: float = 50.0,
        temperature: float = 0.0,
    ):
        self.batch = int(batch)
        self.max_len = int(max_len)
        self.requests = int(requests)
        self.prompt_len = int(prompt_len)
        self.max_new_tokens = int(max_new_tokens)
        self.rate_rps = float(rate_rps)
        self.temperature = float(temperature)

    def capabilities(self) -> HarnessCapabilities:
        # Serving is decode-bound; train/prefill cells fail negotiation.
        # No launcher wrapping — the unit of work is an engine wave, not a
        # step callable the injection contract could wrap.
        return HarnessCapabilities(
            max_readiness=Readiness.REPRODUCIBLE,
            step_kinds=frozenset({"decode", "serve"}),
            launcher_injection=False,
        )

    def spawn_spec(self):
        return "repro.harnesses.serve:ServeHarness", {
            "batch": self.batch, "max_len": self.max_len,
            "requests": self.requests, "prompt_len": self.prompt_len,
            "max_new_tokens": self.max_new_tokens, "rate_rps": self.rate_rps,
            "temperature": self.temperature,
        }

    def run(self, spec: BenchmarkSpec, injections: Optional[Injections] = None) -> protocol.Report:
        import jax

        from repro import configs
        from repro.models import params as P
        from repro.serve.engine import Engine, Request

        inj = injections or Injections()
        ov = inj.overrides
        batch = int(ov.get("batch", self.batch))
        n_req = int(ov.get("requests", self.requests))
        rate = float(ov.get("rate_rps", self.rate_rps))
        new_tokens = int(ov.get("max_new_tokens", self.max_new_tokens))

        cfg = configs.get_smoke(spec.arch)
        if cfg.input_mode != "tokens":
            raise ValueError(
                f"ServeHarness needs a token-LM arch; {spec.arch!r} uses "
                f"input_mode={cfg.input_mode!r}")

        report = protocol.new_report(
            system=spec.system,
            variant=spec.effective_variant(),
            usecase=spec.shape,
            software_version=jax.__version__,
            parameter={
                "arch": spec.arch,
                "device_kind": jax.devices()[0].device_kind,
                "injections": inj.describe(),
                "scale": "serve",
                "batch": batch,
                "requests": n_req,
                "rate_rps": rate,
            },
        )

        rng = np.random.default_rng(spec.seed)
        arrivals = poisson_arrivals(n_req, rate, spec.seed)
        reqs = [
            Request(
                uid=i,
                prompt=rng.integers(0, cfg.vocab_size, self.prompt_len).astype(np.int32),
                max_new_tokens=new_tokens,
                temperature=self.temperature,
            )
            for i in range(n_req)
        ]

        with injected_env(inj.env):
            t_build = time.perf_counter()
            params = P.init_params(cfg, jax.random.key(spec.seed))
            engine = Engine(cfg, params, batch=batch, max_len=self.max_len,
                            seed=spec.seed)
            # Warm the prefill/decode compilations out of the measured path.
            engine.generate([reqs[0]])

            latencies: List[float] = []
            all_tokens: List[int] = []
            tokens_out = 0
            clock = 0.0  # virtual time: arrivals are simulated, service is real
            i = 0
            while i < n_req:
                # Admit everything that has arrived by `clock`, up to `batch`;
                # if the queue is empty, jump to the next arrival.
                clock = max(clock, float(arrivals[i]))
                wave = []
                while i < n_req and float(arrivals[i]) <= clock and len(wave) < batch:
                    wave.append(reqs[i])
                    i += 1
                t0 = time.perf_counter()
                completions = engine.generate(wave)
                service = time.perf_counter() - t0
                clock += service
                for r, c in zip(wave, completions):
                    latencies.append(clock - float(arrivals[r.uid]))
                    tokens_out += len(c.tokens)
                    all_tokens.extend(c.tokens)
            runtime = time.perf_counter() - t_build

        lat = np.asarray(latencies)
        makespan = clock - float(arrivals[0]) if n_req else 0.0
        entry = protocol.DataEntry(
            success=bool(n_req > 0 and tokens_out > 0),
            runtime=runtime,
            nodes=1,
            tasks_per_node=jax.device_count(),
            job_id=f"local-{os.getpid()}",
            queue=jax.devices()[0].platform,
            metrics={
                "p50_latency_s": float(np.percentile(lat, 50)),
                "p95_latency_s": float(np.percentile(lat, 95)),
                "p99_latency_s": float(np.percentile(lat, 99)),
                "tokens_per_s": tokens_out / makespan if makespan > 0 else 0.0,
                "requests_per_s": n_req / makespan if makespan > 0 else 0.0,
                "step_time_s": float(np.percentile(lat, 50)),
                "artifact_digest": artifact_digest(np.asarray(all_tokens, np.int32)),
                "seed": spec.seed,
            },
        )
        report.data.append(entry)
        return report
