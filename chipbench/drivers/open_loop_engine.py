"""Open-loop load on ``repro.serve.engine.Engine``.

Requests are due on the traffic's schedule from the moment the window
opens.  Whenever the engine is free, the driver passes it every request
that is due, at most ``batch`` of them, in one ``Engine.generate`` call,
and sleeps until the next is due when none is.  Each request is timed
from when it was due to the return of the call that served it; the
window's requests still waiting when it closes are served and counted.

The cell's family builds the program's configuration, draws the weights
and installs its kernels.  The check compares the tokens that
``Engine.generate`` served for a seeded sample of requests.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from chipbench import program as PG
from chipbench import traffic as TR


class Driver:
    def __init__(self, cell, *, seed: int, seconds: float, rehearse: bool):
        self.cell, self.seed, self.seconds, self.rehearse = cell, seed, seconds, rehearse
        wl = cell.workload
        over = wl.get("rehearse", {}) if rehearse else {}
        self.engine_cfg = {**wl["engine"], **over.get("engine", {})}
        self.traffic = {**cell.traffic, **over.get("traffic", {})}
        self.model = cell.family.sizes(cell.config, rehearse)
        self.batch = int(self.engine_cfg["batch"])

    def setup(self) -> None:
        import jax

        from repro.serve.engine import Engine, Request

        cfg = self.cell.family.repo_config(self.cell.config["name"], self.model)
        self.cell.family.install_kernels(interpret=self.rehearse)
        with PG.phase("weights"):
            self.params = self.cell.family.make_params(self.model, self.seed)
            jax.block_until_ready(self.params)
        self.eng = Engine(cfg, self.params, batch=self.batch,
                          max_len=int(self.engine_cfg["max_len"]), seed=0)
        self.plan()
        # Warm every shape the window uses: the prefill and decode steps and
        # the sampling ops on a full wave.
        warm = [Request(uid=-1 - j, prompt=self.requests[j % len(self.requests)].prompt,
                        max_new_tokens=2) for j in range(self.batch)]
        with PG.phase("warm"):
            self.eng.generate(warm)

    def plan(self) -> None:
        """Draw the window's requests and the ones to compare from the seed."""
        from repro.serve.engine import Request

        self.requests = TR.open_loop(self.traffic, seed=self.seed, seconds=self.seconds,
                                     vocab=self.model["vocab_size"])
        longest = int(np.argmax([r.max_new_tokens for r in self.requests]))
        self.check_uids = TR.check_sample(len(self.requests),
                                          int(self.cell.workload["check"]["sample"]),
                                          seed=self.seed, longest=longest)
        self._engine_requests = [Request(uid=r.uid, prompt=r.prompt,
                                         max_new_tokens=r.max_new_tokens)
                                 for r in self.requests]

    def run(self, tracer) -> dict:
        import jax

        n, batch = len(self.requests), self.batch
        t0 = time.perf_counter()
        due = [t0 + r.arrival_s for r in self.requests]
        served: Dict[int, dict] = {}
        calls, lags = [], []
        i = 0
        while i < n:
            now = time.perf_counter()
            tracer.boundary(now - t0)
            if due[i] > now:
                with jax.profiler.TraceAnnotation("chipbench.wait_arrival"):
                    time.sleep(due[i] - now)
                lags.append(time.perf_counter() - due[i])
                continue
            j = i
            while j < n and j - i < batch and due[j] <= now:
                j += 1
            wave = self._engine_requests[i:j]
            start = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("chipbench.generate"):
                    outs = self.eng.generate(wave)
            except Exception as e:  # noqa: BLE001 — a failed call fails its requests
                print(f"[error] generate failed: {e!r}", flush=True)
                outs = []
            end = time.perf_counter()
            by_uid = {c.uid: c.tokens for c in outs}
            for r in wave:
                toks = by_uid.get(r.uid)
                ok = (toks is not None and len(toks) == r.max_new_tokens
                      and all(0 <= t < self.model["vocab_size"] for t in toks))
                served[r.uid] = {"due": due[r.uid] - t0, "start": start - t0, "end": end - t0,
                                 "n_prompt": len(r.prompt), "n_out": r.max_new_tokens,
                                 "tokens": toks, "ok": ok}
            calls.append({"start": start - t0, "end": end - t0, "n": len(wave),
                          "steps": max(r.max_new_tokens for r in wave)})
            i = j
        tracer.close()
        lag = np.asarray(lags) if lags else np.zeros(1)
        return {"seconds": self.seconds, "batch": batch,
                "prompt_len": int(self.traffic["prompt_len"]),
                "requests": [served[r.uid] for r in self.requests], "calls": calls,
                "generator_lag_s": {"waits": len(lags), "p50": float(np.median(lag)),
                                    "max": float(lag.max())}}

    def release(self, record: dict) -> List[PG.Sample]:
        """Free the program's device state; the sequences to compare (a
        request that was not served leaves its sample without tokens)."""
        samples = [PG.Sample(prompt=self.requests[uid].prompt,
                             served=record["requests"][uid]["tokens"])
                   for uid in self.check_uids]
        self.cell.family.uninstall_kernels()
        del self.eng, self.params
        gc.collect()
        return samples

    @property
    def max_out(self) -> int:
        return int(self.traffic["output"]["max"])
