"""Benchmark harness adapters (the paper's JUBE/ReFrame/Ramble slot, §IV-D).

exaCB never executes workloads itself — it orchestrates and delegates to a
harness that conforms to the protocol.  Two adapters are provided:

* ``ExecHarness``  — actually runs the (reduced-scale) workload on the local
  devices and measures wall time; fills deterministic artifact digests so a
  benchmark can reach the REPRODUCIBLE readiness level.
* ``DryRunHarness`` (see ``repro.core.dryrun_harness``) — lowers + compiles
  the full-scale cell for a production mesh and reports roofline terms; this
  is the "system-scale" harness used by the JUREAP-style studies.

A harness receives a ``BenchmarkSpec`` (the cell) plus optional
``Injections`` (feature-injection orchestrator, §V-A3) and returns a
protocol ``Report``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, FrozenSet, List, Optional

import numpy as np

from repro.core import protocol
from repro.core.readiness import Readiness


@dataclasses.dataclass(frozen=True)
class BenchmarkSpec:
    """One benchmark cell: architecture × input shape × system.

    ``require_readiness`` is the cell's demand on the harness (as a
    ``Readiness`` level): a cell requiring REPRODUCIBLE negotiates against
    the harness capability declaration *before* dispatch and fails fast on
    a harness that cannot attain it (see :func:`negotiate`).  0 (FAILED)
    means no requirement — the seed behavior.
    """

    arch: str
    shape: str          # the paper's "usecase"
    system: str         # the paper's "machine"
    variant: str = ""   # defaults to shape
    seed: int = 0
    require_readiness: int = 0

    @property
    def cell(self) -> str:
        return f"{self.arch}.{self.shape}.{self.system}"

    def effective_variant(self) -> str:
        return self.variant or self.shape


@dataclasses.dataclass
class Injections:
    """Framework-level workload augmentation without touching the benchmark
    definition (paper §V-A3, Figs. 6/8)."""

    env: Dict[str, str] = dataclasses.field(default_factory=dict)
    # Wraps the step callable: launcher(step_fn) -> step_fn  (jpwr analogue).
    launcher: Optional[Callable[[Callable], Callable]] = None
    # Config knob overrides (remat policy, microbatches, sharding strategy...).
    overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def describe(self) -> Dict[str, Any]:
        return {
            "env": dict(self.env),
            "launcher": getattr(self.launcher, "__name__", None) if self.launcher else None,
            "overrides": dict(self.overrides),
        }


# os.environ is process-global, so concurrent scheduler workers cannot each
# get a private view.  Two mechanisms make injection safe under the pool:
#
# 1. A per-KEY reentrant lock held for the frame's whole lifetime: workers
#    injecting *distinct* keys (a multi-knob campaign) run fully in
#    parallel, while two cells injecting the SAME key (an env-knob sweep)
#    serialize against each other — each cell really executes under its own
#    value instead of the last entrant's.  Keys are acquired in sorted order
#    to prevent deadlock; RLocks keep same-thread nesting legal.
# 2. A process-wide registry of active frames guarded by ``_ENV_LOCK`` so
#    exits restore the youngest surviving frame's value (same-thread
#    nesting) or the pre-injection original.
#
# PROCESS-WORKER CAVEAT: all of this state is *per interpreter*.  Under the
# ``spawn`` start method a worker process begins with a fresh module — no
# locks, no frames, no saved originals, and (unlike ``fork``) not even the
# parent's merged os.environ mutations.  Injection frames therefore must be
# re-applied INSIDE the worker: the execution plane ships env frames as
# payload/config data and the worker bootstrap re-enters ``injected_env``
# before running cells (see ``repro.core.workers.worker_main``).  A parent
# holding an active frame while spawning workers injects nothing into them.
_ENV_LOCK = threading.RLock()
_ENV_FRAMES: List[Dict[str, str]] = []
_ENV_SAVED: Dict[str, Optional[str]] = {}
_ENV_KEY_LOCKS: Dict[str, threading.RLock] = {}


def _key_locks(keys) -> List[threading.RLock]:
    with _ENV_LOCK:
        return [_ENV_KEY_LOCKS.setdefault(k, threading.RLock()) for k in sorted(keys)]


def _restore_env_key(k: str) -> None:
    """Re-apply the youngest surviving frame's value for ``k``, or the saved
    pre-injection original.  Caller holds ``_ENV_LOCK``."""
    survivor = next((f for f in reversed(_ENV_FRAMES) if k in f), None)
    if survivor is not None:
        os.environ[k] = survivor[k]
        return
    original = _ENV_SAVED.pop(k)
    if original is None:
        os.environ.pop(k, None)
    else:
        os.environ[k] = original


@contextmanager
def injected_env(env: Dict[str, str]):
    # Coerce up front: env values are strings by contract, but YAML-parsed
    # inputs can arrive as ints/bools and os.environ would reject them
    # halfway through the apply loop.
    frame = {str(k): str(v) for k, v in env.items()}
    key_locks = _key_locks(frame)
    for lk in key_locks:
        lk.acquire()
    try:
        with _ENV_LOCK:
            applied = []
            try:
                for k, v in frame.items():
                    if k not in _ENV_SAVED:
                        _ENV_SAVED[k] = os.environ.get(k)
                    os.environ[k] = v
                    applied.append(k)
                _ENV_FRAMES.append(frame)
            except BaseException:
                # Partial application must not leak: roll back what landed.
                for k in applied:
                    _restore_env_key(k)
                raise
        try:
            yield
        finally:
            with _ENV_LOCK:
                _ENV_FRAMES.remove(frame)
                for k in frame:
                    _restore_env_key(k)
    finally:
        for lk in reversed(key_locks):
            lk.release()


@dataclasses.dataclass(frozen=True)
class HarnessCapabilities:
    """What a harness declares it can do — the downward half of the typed
    component contract.  ``BenchmarkSpec`` requirements negotiate against
    this *before* dispatch, so a cell demanding REPRODUCIBLE fails fast on
    a harness that cannot attain it instead of burning an execution slot
    and reporting a mystery gap afterwards.
    """

    max_readiness: Readiness = Readiness.REPRODUCIBLE
    #: Step kinds the harness can execute; empty = unrestricted.
    step_kinds: FrozenSet[str] = frozenset()
    env_injection: bool = True
    override_injection: bool = True
    launcher_injection: bool = True

    def describe(self) -> Dict[str, Any]:
        return {
            "max_readiness": self.max_readiness.name,
            "step_kinds": sorted(self.step_kinds) or "any",
            "env_injection": self.env_injection,
            "override_injection": self.override_injection,
            "launcher_injection": self.launcher_injection,
        }


class CapabilityError(ValueError):
    """A cell's requirements exceed the harness's declared capabilities."""


def _shape_kind(shape: str) -> Optional[str]:
    """Step kind of a named shape (lazy import — harness adapters must stay
    importable without the benchmark collection)."""
    try:
        from repro.configs import shapes as SH
        return getattr(SH.SHAPES.get(shape), "kind", None)
    except Exception:
        return None


def negotiate(spec: BenchmarkSpec, harness: "Harness",
              injections: Optional[Injections] = None) -> HarnessCapabilities:
    """Check one cell (+ its injections) against the harness capability
    declaration; raises :class:`CapabilityError` naming every violated
    capability, returns the capabilities when the cell is dispatchable."""
    caps = harness.capabilities()
    reasons: List[str] = []
    if spec.require_readiness > int(caps.max_readiness):
        reasons.append(
            f"cell requires readiness {Readiness(spec.require_readiness).name} "
            f"but harness attains at most {caps.max_readiness.name}")
    kind = _shape_kind(spec.shape)
    if caps.step_kinds and kind is not None and kind not in caps.step_kinds:
        reasons.append(
            f"shape {spec.shape!r} needs step kind {kind!r} "
            f"(harness supports {sorted(caps.step_kinds)})")
    if injections is not None:
        if injections.env and not caps.env_injection:
            reasons.append("env injection not supported")
        if injections.overrides and not caps.override_injection:
            reasons.append("config-override injection not supported")
        if injections.launcher is not None and not caps.launcher_injection:
            reasons.append("launcher injection not supported")
    if reasons:
        raise CapabilityError(
            f"harness {harness.name!r} cannot run cell {spec.cell}: "
            + "; ".join(reasons))
    return caps


class Harness:
    """Adapter interface: everything exaCB needs from a harness."""

    name = "abstract"
    #: Runs its work with JAX in the calling process, on the device JAX
    #: picks.  A chip serves one process, so such a harness cannot run under
    #: process workers on a TPU host (``CampaignBroker`` refuses it).
    uses_device = False

    def capabilities(self) -> HarnessCapabilities:
        """Capability declaration; the permissive default keeps third-party
        adapters working, but real adapters should narrow it honestly."""
        return HarnessCapabilities()

    def run(self, spec: BenchmarkSpec, injections: Optional[Injections] = None) -> protocol.Report:
        raise NotImplementedError

    def spawn_spec(self) -> "tuple[str, Dict[str, Any]]":
        """Spawn-safe construction recipe: ``("module:factory", kwargs)``.

        Process workers never receive harness *objects* — a spawned
        interpreter rebuilds the harness from this recipe (dotted-path
        factory + plain-data kwargs), which is what makes cell dispatch
        picklable data instead of closures.  Adapters that cannot be
        reconstructed from plain data stay thread-mode only.
        """
        raise NotImplementedError(
            f"harness {self.name!r} declares no spawn_spec(): it cannot run "
            "under process workers (worker_mode: process); use thread mode "
            "or implement spawn_spec()")


def artifact_digest(*arrays) -> str:
    """Deterministic digest of output artifacts (REPRODUCIBLE level)."""
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()[:16]


class ExecHarness(Harness):
    """Runs the reduced-scale cell for real on local devices.

    Smoke-scale analogue of a JUBE run: builds the model, executes the step
    kind the shape dictates, measures wall time, and reports protocol-
    compliant metrics including artifact digests.
    """

    name = "exec"
    uses_device = True

    def capabilities(self) -> HarnessCapabilities:
        # Real execution with deterministic artifact digests: every level up
        # to REPRODUCIBLE, all three step kinds, every injection mechanism.
        return HarnessCapabilities(
            max_readiness=Readiness.REPRODUCIBLE,
            step_kinds=frozenset({"train", "prefill", "decode"}),
        )

    def __init__(self, *, steps: int = 3, batch: int = 2, seq: int = 16):
        self.steps = steps
        self.batch = batch
        self.seq = seq

    def spawn_spec(self):
        return "repro.core.harness:ExecHarness", {
            "steps": self.steps, "batch": self.batch, "seq": self.seq}

    def run(self, spec: BenchmarkSpec, injections: Optional[Injections] = None) -> protocol.Report:
        import jax
        import jax.numpy as jnp

        from repro import configs
        from repro.configs import shapes as SH
        from repro.models import params as P
        from repro.models import transformer as T

        inj = injections or Injections()
        report = protocol.new_report(
            system=spec.system,
            variant=spec.effective_variant(),
            usecase=spec.shape,
            software_version=jax.__version__,
            parameter={"arch": spec.arch, "device_kind": jax.devices()[0].device_kind,
                       "injections": inj.describe(), "scale": "smoke"},
        )
        cfg = configs.get_smoke(spec.arch)
        for k, v in inj.overrides.items():
            if hasattr(cfg, k):
                cfg = dataclasses.replace(cfg, **{k: v})
        remat = str(inj.overrides.get("remat", "none"))
        shape = SH.SHAPES[spec.shape]
        kind = shape.kind

        with injected_env(inj.env):
            t_build = time.perf_counter()
            params = P.init_params(cfg, jax.random.key(spec.seed))
            B, S = self.batch, self.seq
            batch = _smoke_batch(cfg, kind, B, S, spec.seed)

            if kind == SH.TRAIN:
                # Full fwd+bwd so remat/microbatch injections have real effect.
                def step(p, b):
                    loss, grads = jax.value_and_grad(
                        lambda pp: T.train_loss(pp, cfg, b, remat=remat)[0]
                    )(p)
                    return loss + 0.0 * grads["final_norm"]["scale"].sum()
            elif kind == SH.PREFILL:
                def step(p, b):
                    logits, _ = T.prefill(p, cfg, b, max_len=cfg.prefix_len + S, remat=remat)
                    return logits
            else:  # decode
                state0 = T.init_decode_state(cfg, B, cfg.prefix_len + S)

                def step(p, b):
                    logits, _ = T.decode_step(p, cfg, state0, b, jnp.asarray(0, jnp.int32))
                    return logits

            if inj.launcher is not None:
                step = inj.launcher(step)

            fn = jax.jit(step)
            out = jax.block_until_ready(fn(params, batch))
            times = []
            for _ in range(self.steps):
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn(params, batch))
                times.append(time.perf_counter() - t0)
            runtime = time.perf_counter() - t_build

        cost = _cost_analysis(fn, params, batch)
        launcher_metrics = getattr(step, "exacb_metrics", None) or {}
        entry = protocol.DataEntry(
            success=bool(np.all(np.isfinite(np.asarray(out, dtype=np.float32)))),
            runtime=runtime,
            nodes=1,
            tasks_per_node=jax.device_count(),
            job_id=f"local-{os.getpid()}",
            queue=jax.devices()[0].platform,
            metrics={
                "step_time_s": float(np.median(times)),
                "step_time_min_s": float(np.min(times)),
                "hlo_flops": cost.get("flops", 0.0),
                "hlo_bytes": cost.get("bytes accessed", 0.0),
                "collective_bytes": 0.0,  # single local device
                "t_compute": 0.0,
                "t_memory": 0.0,
                "t_collective": 0.0,
                "artifact_digest": artifact_digest(out),
                "seed": spec.seed,
                **launcher_metrics,
            },
        )
        report.data.append(entry)
        return report


def _smoke_batch(cfg, kind, B, S, seed):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    out: Dict[str, Any] = {}
    if kind == "decode":
        if cfg.input_mode == "embeddings":
            out["embeds"] = jnp.asarray(rng.standard_normal((B, 1, cfg.d_model)), dtype=cfg.dtype)
        else:
            out["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), dtype=jnp.int32)
        return out
    if cfg.input_mode == "embeddings":
        out["embeds"] = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)), dtype=cfg.dtype)
    else:
        if cfg.prefix_len:
            out["prefix_embeds"] = jnp.asarray(
                rng.standard_normal((B, cfg.prefix_len, cfg.d_model)), dtype=cfg.dtype
            )
        out["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), dtype=jnp.int32)
    if kind == "train":
        if cfg.n_codebooks > 1:
            out["targets"] = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, cfg.n_codebooks, S)), dtype=jnp.int32
            )
        else:
            out["targets"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), dtype=jnp.int32)
    return out


def _cost_analysis(jitted, *args) -> Dict[str, float]:
    ca = jitted.lower(*args).compile().cost_analysis() or {}
    return {k: float(v) for k, v in ca.items() if isinstance(v, (int, float))}
