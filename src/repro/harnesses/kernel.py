"""KernelHarness: run a named pallas kernel at a fixed shape/dtype/blocks.

The harness is the measurement primitive of the autotuning plane
(``repro.core.autotune``): a cell is one (kernel, shape, dtype, block
config) point, and the block config arrives through feature-injection
``overrides`` — the same channel every other knob sweep uses.  Block
resolution order:

1. ``Injections.overrides`` (the sweep point),
2. the persistent autotune cache, when ``use_cache`` is on and an entry
   matches (kernel, shape key, dtype, hardware fingerprint),
3. the kernel's shipped defaults.

The kernels are lowered for the TPU; off the chip a caller passes
``interpret=True``, and latencies are then interpret-mode host times,
not device numbers.  Achieved FLOP/s and bytes/s come from analytic
per-kernel counts, not HLO cost analysis: interpret mode lowers to scalar
loops whose HLO costs say nothing about the kernel's arithmetic.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional, Tuple

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

#: Tunable block knobs per kernel — also the sweep axes autotune accepts.
KERNEL_KNOBS: Dict[str, Tuple[str, ...]] = {
    "flash_attention": ("block_q", "block_k"),
    "rglru": ("chunk", "block_w"),
    "ssd": ("chunk",),
}

#: Shipped defaults (must mirror the ops.py signatures) — the fallback when
#: neither the sweep nor the cache names a config.
KERNEL_DEFAULTS: Dict[str, Dict[str, int]] = {
    "flash_attention": {"block_q": 512, "block_k": 512},
    "rglru": {"chunk": 256, "block_w": 512},
    "ssd": {"chunk": 256},
}

#: Problem-size dims each kernel consumes (overridable via injections too).
KERNEL_DIMS: Dict[str, Tuple[str, ...]] = {
    "flash_attention": ("batch", "heads", "seq", "head_dim"),
    "rglru": ("batch", "seq", "width"),
    "ssd": ("batch", "seq", "heads", "head_dim", "state"),
}


def shape_key(kernel: str, dims: Dict[str, int]) -> str:
    """Canonical shape key for the autotune cache, e.g. ``B1.H2.T128.D16``."""
    if kernel == "flash_attention":
        return "B{batch}.H{heads}.T{seq}.D{head_dim}".format(**dims)
    if kernel == "rglru":
        return "B{batch}.T{seq}.W{width}".format(**dims)
    if kernel == "ssd":
        return "B{batch}.T{seq}.H{heads}.P{head_dim}.N{state}".format(**dims)
    raise ValueError(f"unknown kernel {kernel!r}")


class KernelHarness(Harness):
    """Runs one pallas kernel point; reports latency + achieved roofline."""

    name = "kernel"
    uses_device = True

    def __init__(
        self,
        *,
        kernel: str = "flash_attention",
        batch: int = 1,
        heads: int = 2,
        seq: int = 128,
        head_dim: int = 16,
        width: int = 64,
        state: int = 16,
        dtype: str = "float32",
        calls: int = 3,
        warmup: int = 1,
        causal: bool = True,
        interpret: bool = False,
        use_cache: bool = True,
        cache_path: str = "",
    ):
        if kernel not in KERNEL_KNOBS:
            raise ValueError(
                f"unknown kernel {kernel!r}; known: {sorted(KERNEL_KNOBS)}")
        self.kernel = kernel
        self.batch = int(batch)
        self.heads = int(heads)
        self.seq = int(seq)
        self.head_dim = int(head_dim)
        self.width = int(width)
        self.state = int(state)
        self.dtype = str(dtype)
        self.calls = int(calls)
        self.warmup = int(warmup)
        self.causal = bool(causal)
        self.interpret = bool(interpret)
        self.use_cache = bool(use_cache)
        self.cache_path = str(cache_path)

    def capabilities(self) -> HarnessCapabilities:
        # A kernel point is a "kernel" step — any cell naming a model shape
        # from configs.shapes (train/prefill/decode kinds) fails negotiation
        # before dispatch.  No launcher wrapping: the step callable is a
        # jitted kernel whose wrapping would measure dispatch, not compute.
        return HarnessCapabilities(
            max_readiness=Readiness.REPRODUCIBLE,
            step_kinds=frozenset({"kernel"}),
            launcher_injection=False,
        )

    def spawn_spec(self):
        return "repro.harnesses.kernel:KernelHarness", {
            "kernel": self.kernel, "batch": self.batch, "heads": self.heads,
            "seq": self.seq, "head_dim": self.head_dim, "width": self.width,
            "state": self.state, "dtype": self.dtype, "calls": self.calls,
            "warmup": self.warmup, "causal": self.causal,
            "interpret": self.interpret, "use_cache": self.use_cache,
            "cache_path": self.cache_path,
        }

    # -- shape/dims -------------------------------------------------------
    def dims(self, overrides: Optional[Dict[str, Any]] = None) -> Dict[str, int]:
        base = {"batch": self.batch, "heads": self.heads, "seq": self.seq,
                "head_dim": self.head_dim, "width": self.width, "state": self.state}
        for k, v in (overrides or {}).items():
            if k in base:
                base[k] = int(v)
        return {k: base[k] for k in KERNEL_DIMS[self.kernel]}

    def shape_key(self, overrides: Optional[Dict[str, Any]] = None) -> str:
        return shape_key(self.kernel, self.dims(overrides))

    def _resolve_blocks(self, overrides: Dict[str, Any]) -> Tuple[Dict[str, int], str]:
        knobs = KERNEL_KNOBS[self.kernel]
        injected = {k: int(overrides[k]) for k in knobs if k in overrides}
        if injected:
            blocks = dict(KERNEL_DEFAULTS[self.kernel])
            blocks.update(injected)
            return blocks, "injections"
        if self.use_cache:
            from repro.core import autotune

            cached = autotune.cached_blocks(
                self.kernel, self.shape_key(overrides), self.dtype,
                path=self.cache_path or None)
            if cached:
                blocks = dict(KERNEL_DEFAULTS[self.kernel])
                blocks.update({k: int(v) for k, v in cached.items() if k in knobs})
                return blocks, "cache"
        return dict(KERNEL_DEFAULTS[self.kernel]), "default"

    # -- execution --------------------------------------------------------
    def run(self, spec: BenchmarkSpec, injections: Optional[Injections] = None) -> protocol.Report:
        import jax

        inj = injections or Injections()
        overrides = dict(inj.overrides)
        dims = self.dims(overrides)
        blocks, blocks_source = self._resolve_blocks(overrides)
        skey = shape_key(self.kernel, dims)

        report = protocol.new_report(
            system=spec.system,
            variant=spec.effective_variant(),
            usecase=spec.shape,
            software_version=jax.__version__,
            parameter={
                "arch": spec.arch,
                "device_kind": jax.devices()[0].device_kind,
                "injections": inj.describe(),
                "scale": "kernel",
                "kernel": self.kernel,
                "kernel_shape": skey,
                "kernel_dtype": self.dtype,
                "blocks": dict(blocks),
                "blocks_source": blocks_source,
            },
        )

        with injected_env(inj.env):
            fn, args, flops, bytes_moved = self._build(dims, blocks, spec.seed)
            out = jax.block_until_ready(fn(*args))
            for _ in range(max(0, self.warmup - 1)):
                out = jax.block_until_ready(fn(*args))
            times = []
            t_total = time.perf_counter()
            for _ in range(max(1, self.calls)):
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn(*args))
                times.append(time.perf_counter() - t0)
            runtime = time.perf_counter() - t_total

        lat = float(np.median(times))
        entry = protocol.DataEntry(
            success=bool(np.all(np.isfinite(np.asarray(out, dtype=np.float32)))),
            runtime=runtime,
            nodes=1,
            tasks_per_node=jax.device_count(),
            job_id=f"local-{os.getpid()}",
            queue=jax.devices()[0].platform,
            metrics={
                "kernel_latency_s": lat,
                "kernel_latency_min_s": float(np.min(times)),
                # step_time_s aliases the latency so every generic consumer
                # (gate defaults, columnar analyses, reports) sees the kernel
                # series without a special case.
                "step_time_s": lat,
                "step_time_min_s": float(np.min(times)),
                "hlo_flops": float(flops),
                "hlo_bytes": float(bytes_moved),
                "achieved_flops": float(flops) / lat if lat > 0 else 0.0,
                "achieved_bytes_per_s": float(bytes_moved) / lat if lat > 0 else 0.0,
                "artifact_digest": artifact_digest(out),
                "seed": spec.seed,
            },
        )
        report.data.append(entry)
        return report

    def _build(self, dims: Dict[str, int], blocks: Dict[str, int], seed: int):
        """Return (callable, args, analytic_flops, analytic_bytes)."""
        import jax.numpy as jnp

        rng = np.random.default_rng(seed)
        dt = np.dtype(self.dtype)
        itemsize = dt.itemsize
        interp = self.interpret

        if self.kernel == "flash_attention":
            from repro.kernels.flash_attention.ops import flash_attention

            B, H, T, D = dims["batch"], dims["heads"], dims["seq"], dims["head_dim"]
            q = jnp.asarray(rng.standard_normal((B, H, T, D)), dtype=dt)
            k = jnp.asarray(rng.standard_normal((B, H, T, D)), dtype=dt)
            v = jnp.asarray(rng.standard_normal((B, H, T, D)), dtype=dt)
            causal = self.causal
            work = 0.5 if causal else 1.0
            flops = 4.0 * B * H * T * T * D * work
            nbytes = 4 * B * H * T * D * itemsize  # q, k, v, out

            def fn(q, k, v):
                return flash_attention(
                    q, k, v, causal=causal, interpret=interp,
                    block_q=blocks["block_q"], block_k=blocks["block_k"])

            return fn, (q, k, v), flops, nbytes

        if self.kernel == "rglru":
            from repro.kernels.rglru.ops import rglru_scan

            B, T, W = dims["batch"], dims["seq"], dims["width"]
            a = jnp.asarray(rng.uniform(0.5, 0.999, (B, T, W)), dtype=dt)
            g = jnp.asarray(rng.standard_normal((B, T, W)), dtype=dt)
            flops = 8.0 * B * T * W
            nbytes = 3 * B * T * W * itemsize + B * W * itemsize

            def fn(a, g):
                return rglru_scan(
                    a, g, interpret=interp,
                    chunk=blocks["chunk"], block_w=blocks["block_w"])

            return fn, (a, g), flops, nbytes

        # ssd
        from repro.kernels.ssd.ops import ssd_scan

        B, T = dims["batch"], dims["seq"]
        H, P, N = dims["heads"], dims["head_dim"], dims["state"]
        x = jnp.asarray(rng.standard_normal((B, T, H, P)), dtype=dt)
        dtm = jnp.asarray(rng.uniform(0.01, 0.1, (B, T, H)), dtype=np.float32)
        A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), dtype=np.float32)
        Bm = jnp.asarray(rng.standard_normal((B, T, 1, N)), dtype=dt)
        Cm = jnp.asarray(rng.standard_normal((B, T, 1, N)), dtype=dt)
        flops = 4.0 * B * T * H * P * N
        nbytes = (2 * B * T * H * P + 2 * B * T * N + B * T * H) * itemsize

        def fn(x, dtm, A, Bm, Cm):
            return ssd_scan(x, dtm, A, Bm, Cm, interpret=interp, chunk=blocks["chunk"])

        return fn, (x, dtm, A, Bm, Cm), flops, nbytes
