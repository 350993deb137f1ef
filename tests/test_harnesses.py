"""Harness family (kernel/serve/train) + autotune plane tests.

Covers the PR-4 negotiation contract per harness (fail-fast before
dispatch), the PR-6 process-worker contract (spawn_spec round-trip and a
payload-declared harness through the real worker code path), the autotune
cache key semantics (hit / miss / fingerprint-drift invalidation), the
ops.py cache consultation, and Poisson load-gen determinism.
"""

import json

import numpy as np
import pytest

from repro import harnesses
from repro.core import fingerprint
from repro.core.autotune import (
    CACHE_ENV,
    AutotuneCache,
    cached_blocks,
    reset_runtime_caches,
)
from repro.core.component import REGISTRY, ComponentContext, PipelineError
from repro.core.harness import (
    BenchmarkSpec,
    CapabilityError,
    Injections,
    negotiate,
)
from repro.core.orchestrator import ExecutionOrchestrator
from repro.core.store import ResultStore
from repro.core.workers import (
    WorkerConfig,
    cell_payload,
    resolve_harness,
    worker_main,
)
from repro.core.workqueue import WorkQueue
from repro.harnesses.kernel import KernelHarness
from repro.harnesses.serve import ServeHarness, poisson_arrivals
from repro.harnesses.train import TrainHarness


def _kernel_harness(**kw):
    base = dict(kernel="flash_attention", batch=1, heads=2, seq=32,
                head_dim=8, calls=1, warmup=1, interpret=True,
                use_cache=False)
    base.update(kw)
    return KernelHarness(**base)


KSPEC = BenchmarkSpec(arch="kernel", shape="fa_smoke", system="local")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_resolves_named_harnesses():
    assert isinstance(harnesses.resolve("kernel"), KernelHarness)
    assert isinstance(harnesses.resolve("serve"), ServeHarness)
    assert isinstance(harnesses.resolve("train"), TrainHarness)
    with pytest.raises(PipelineError, match="unknown harness"):
        harnesses.resolve("warp-drive")
    with pytest.raises(PipelineError, match="kernel"):
        harnesses.resolve("kernel", warp_factor=9)  # bad kwarg names harness


def test_from_inputs_extracts_namespace():
    h = harnesses.from_inputs({
        "harness": "kernel", "harness.kernel": "rglru",
        "harness.seq": 64, "prefix": "x"})
    assert isinstance(h, KernelHarness)
    assert h.kernel == "rglru" and h.seq == 64
    assert harnesses.from_inputs({"prefix": "x"}) is None


# ---------------------------------------------------------------------------
# capability negotiation: fail fast, before any execution
# ---------------------------------------------------------------------------

def test_kernel_harness_rejects_model_shapes_fail_fast():
    ex = ExecutionOrchestrator(
        inputs={"prefix": "t", "record": False}, harness=_kernel_harness())
    res = ex.run_cell(BenchmarkSpec(arch="x", shape="train_4k", system="local"))
    assert res.error and "CapabilityError" in res.error
    assert "step kind" in res.error
    assert res.attempts == 0  # fail-fast: no execution slot burned


def test_kernel_harness_rejects_launcher_injection():
    ex = ExecutionOrchestrator(
        inputs={"prefix": "t", "record": False}, harness=_kernel_harness())
    res = ex.run_cell(KSPEC, injections=Injections(launcher=lambda f: f))
    assert res.error and "CapabilityError" in res.error
    assert res.attempts == 0


def test_serve_and_train_step_kind_negotiation():
    with pytest.raises(CapabilityError):
        negotiate(BenchmarkSpec(arch="a", shape="train_4k", system="s"),
                  ServeHarness())
    with pytest.raises(CapabilityError):
        negotiate(BenchmarkSpec(arch="a", shape="decode_32k", system="s"),
                  TrainHarness())
    # The matching kinds pass.
    negotiate(BenchmarkSpec(arch="a", shape="decode_32k", system="s"),
              ServeHarness())
    negotiate(BenchmarkSpec(arch="a", shape="train_4k", system="s"),
              TrainHarness())


# ---------------------------------------------------------------------------
# kernel harness execution
# ---------------------------------------------------------------------------

def test_kernel_harness_reports_latency_and_roofline_inputs():
    h = _kernel_harness()
    rep = h.run(KSPEC, Injections(overrides={"block_q": 16, "block_k": 16}))
    m = rep.data[-1].metrics
    assert m["kernel_latency_s"] > 0
    assert m["step_time_s"] == m["kernel_latency_s"]
    assert m["hlo_flops"] > 0 and m["hlo_bytes"] > 0
    assert m["achieved_flops"] == pytest.approx(
        m["hlo_flops"] / m["kernel_latency_s"])
    assert rep.parameter["blocks"] == {"block_q": 16, "block_k": 16}
    assert rep.parameter["blocks_source"] == "injections"
    assert rep.parameter["kernel_shape"] == "B1.H2.T32.D8"
    # The report names the device it ran on.
    assert rep.data[-1].queue == "cpu"
    assert rep.parameter["device_kind"] == "cpu"


def test_kernel_harness_default_blocks_without_cache():
    rep = _kernel_harness().run(KSPEC)
    assert rep.parameter["blocks_source"] == "default"
    assert rep.parameter["blocks"] == {"block_q": 512, "block_k": 512}


# ---------------------------------------------------------------------------
# spawn_spec round-trip + process-worker dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda: _kernel_harness(kernel="ssd", seq=16),
    lambda: ServeHarness(batch=3, requests=5, rate_rps=7.5),
    lambda: TrainHarness(steps=2, seq_len=16),
])
def test_spawn_spec_round_trip(make):
    h = make()
    ref, kwargs = h.spawn_spec()
    json.dumps(kwargs)  # plain data only: must cross the spawn boundary
    h2 = resolve_harness(ref, kwargs)
    assert type(h2) is type(h)
    assert h2.spawn_spec() == (ref, kwargs)


def test_worker_runs_payload_declared_harness(tmp_path):
    """The document's harness choice travels in the payload and beats the
    worker's campaign-level default — through the real worker_main path."""
    store = ResultStore(tmp_path / "store")
    payload = cell_payload(
        KSPEC,
        {"prefix": "wk", "record": True, "harness": "kernel",
         "harness.kernel": "flash_attention", "harness.seq": 32,
         "harness.head_dim": 8, "harness.calls": 1, "harness.warmup": 1,
         "harness.interpret": True, "harness.use_cache": False},
        injections=Injections(overrides={"block_q": 16, "block_k": 16}),
    )
    WorkQueue(tmp_path / "q").create([payload], campaign="t")
    cfg = WorkerConfig(
        store_root=str(store.root),
        harness_ref="repro.core.harness:ExecHarness",  # the default to beat
        harness_kwargs={"steps": 1, "batch": 1, "seq": 8},
        idle_timeout=60.0,
    ).to_dict()
    worker_main("w0", str(tmp_path / "q"), cfg)
    reports = store.query("wk")
    assert len(reports) == 1
    assert reports[0].parameter["kernel"] == "flash_attention"
    assert reports[0].parameter["blocks"]["block_q"] == 16
    assert reports[0].data[-1].metrics["kernel_latency_s"] > 0


# ---------------------------------------------------------------------------
# autotune cache semantics
# ---------------------------------------------------------------------------

def test_autotune_cache_hit_miss_and_fingerprint_drift(tmp_path):
    path = tmp_path / "cache.json"
    cache = AutotuneCache(path)
    fp = fingerprint.key(fingerprint.capture())
    cache.put("flash_attention", "B1.H2.T32.D8", "float32", fp,
              {"block_q": 16, "block_k": 32}, latency_s=1e-4)

    hit = cache.lookup("flash_attention", "B1.H2.T32.D8", "float32", fp)
    assert hit is not None and hit["config"] == {"block_q": 16, "block_k": 32}
    # Different shape / dtype: miss.
    assert cache.lookup("flash_attention", "B1.H2.T64.D8", "float32", fp) is None
    assert cache.lookup("flash_attention", "B1.H2.T32.D8", "bfloat16", fp) is None
    # Fingerprint drift (entry tuned on other hardware): invisible.
    drifted = fp.replace("{", '{"governor":"other",', 1)
    assert cache.lookup("flash_attention", "B1.H2.T32.D8", "float32",
                        drifted) is None

    # put() on the same key replaces and counts updates.
    entry = cache.put("flash_attention", "B1.H2.T32.D8", "float32", fp,
                      {"block_q": 64, "block_k": 64})
    assert entry["updates"] == 2


def test_cached_blocks_env_and_drift(tmp_path, monkeypatch):
    path = tmp_path / "cache.json"
    fp = fingerprint.key(fingerprint.capture())
    AutotuneCache(path).put("rglru", "B1.T64.W32", "float32", fp,
                            {"chunk": 32, "block_w": 16})
    reset_runtime_caches()
    # Env unset: the cache is off regardless of what is on disk.
    monkeypatch.delenv(CACHE_ENV, raising=False)
    assert cached_blocks("rglru", "B1.T64.W32", "float32") is None
    monkeypatch.setenv(CACHE_ENV, str(path))
    assert cached_blocks("rglru", "B1.T64.W32", "float32") == {
        "chunk": 32, "block_w": 16}
    # An entry stamped with a drifted fingerprint stops resolving even when
    # its (kernel, shape, dtype) match — re-keyed via a hand-edited file.
    data = json.loads(path.read_text())
    for e in data["entries"].values():
        e["fingerprint_key"] = e["fingerprint_key"] + "x"
    path.write_text(json.dumps(data))
    reset_runtime_caches()
    assert cached_blocks("rglru", "B1.T64.W32", "float32") is None
    reset_runtime_caches()


def test_flash_attention_consults_cache(tmp_path, monkeypatch):
    import jax.numpy as jnp

    from repro.kernels.flash_attention import ops

    path = tmp_path / "cache.json"
    fp = fingerprint.key(fingerprint.capture())
    AutotuneCache(path).put("flash_attention", "B1.H2.T32.D8", "float32", fp,
                            {"block_q": 16, "block_k": 16})
    monkeypatch.setenv(CACHE_ENV, str(path))
    reset_runtime_caches()
    q = jnp.asarray(np.random.default_rng(0).standard_normal((1, 2, 32, 8)),
                    jnp.float32)
    assert ops._autotuned_blocks(q.shape, q.dtype) == {
        "block_q": 16, "block_k": 16}
    tuned = ops.flash_attention(q, q, q, interpret=True)
    explicit = ops.flash_attention(q, q, q, interpret=True,
                                   block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(explicit),
                               atol=1e-6)
    reset_runtime_caches()


def test_kernel_harness_uses_cache_for_defaults(tmp_path):
    path = tmp_path / "cache.json"
    fp = fingerprint.key(fingerprint.capture())
    AutotuneCache(path).put("flash_attention", "B1.H2.T32.D8", "float32", fp,
                            {"block_q": 16, "block_k": 16})
    h = _kernel_harness(use_cache=True, cache_path=str(path))
    rep = h.run(KSPEC)
    assert rep.parameter["blocks_source"] == "cache"
    assert rep.parameter["blocks"] == {"block_q": 16, "block_k": 16}


# ---------------------------------------------------------------------------
# autotune@v1 component
# ---------------------------------------------------------------------------

def test_autotune_component_sweep_promote_and_noop(tmp_path):
    store = ResultStore(tmp_path / "store")
    ctx = ComponentContext(store=store)
    resolved = REGISTRY.resolve("autotune", 1)
    inputs = {"kernel": "flash_attention", "prefix": "autotune.t",
              "seq": 32, "head_dim": 8, "heads": 2, "batch": 1,
              "block_q": [16, 32], "block_k": [16], "calls": 1, "warmup": 1,
              "confirm": 1, "interpret": True}

    out = resolved.run(inputs, ctx)
    assert len(out["points"]) == 2
    assert out["winner"]["config"]["block_q"] in (16, 32)
    # The CPU host has no entry in the peaks table: no roofline terms.
    assert "dominant" not in out["points"][0]
    assert out["winner"]["dominant"] is None
    assert (tmp_path / "store" / "autotune_cache.json").exists()

    from repro.core.regression import BaselineManager
    cur = BaselineManager(store).current("autotune.t", "kernel_latency_s")
    assert cur is not None and cur.pinned

    # Unchanged key: incremental no-op.
    again = resolved.run(inputs, ctx)
    assert again.get("skipped") == "cache-hit"
    assert again["cache"]["hit"] is True
    # force re-sweeps.
    forced = resolved.run({**inputs, "force": True}, ctx)
    assert forced.get("skipped") is None and len(forced["points"]) == 2


def test_autotune_requires_sweep_values(tmp_path):
    ctx = ComponentContext(store=ResultStore(tmp_path / "store"))
    with pytest.raises(PipelineError, match="no block values"):
        REGISTRY.resolve("autotune", 1).run(
            {"kernel": "flash_attention"}, ctx)


# ---------------------------------------------------------------------------
# serve load generation
# ---------------------------------------------------------------------------

def test_poisson_arrivals_deterministic_under_seed():
    a = poisson_arrivals(64, 20.0, seed=7)
    b = poisson_arrivals(64, 20.0, seed=7)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, poisson_arrivals(64, 20.0, seed=8))
    assert a.shape == (64,)
    assert np.all(np.diff(a) >= 0) and np.all(a >= 0)
    # Mean inter-arrival ~ 1/rate.
    assert 1 / 20.0 == pytest.approx(float(np.mean(np.diff(a))), rel=0.5)
    with pytest.raises(ValueError):
        poisson_arrivals(4, 0.0, seed=0)


def test_serve_harness_reports_tail_latencies():
    h = ServeHarness(batch=2, max_len=16, requests=4, prompt_len=3,
                     max_new_tokens=2, rate_rps=200.0)
    rep = h.run(BenchmarkSpec(arch="starcoder2-3b", shape="serve_smoke",
                              system="local"))
    m = rep.data[-1].metrics
    assert 0 < m["p50_latency_s"] <= m["p95_latency_s"] <= m["p99_latency_s"]
    assert m["tokens_per_s"] > 0 and m["requests_per_s"] > 0
    assert rep.data[-1].success


def test_serve_harness_rejects_embedding_archs():
    h = ServeHarness(requests=2)
    with pytest.raises(ValueError, match="input_mode"):
        h.run(BenchmarkSpec(arch="musicgen-medium", shape="serve_smoke",
                            system="local"))


def test_train_harness_step_times():
    h = TrainHarness(steps=2, seq_len=16, global_batch=2)
    rep = h.run(BenchmarkSpec(arch="starcoder2-3b", shape="train_4k",
                              system="local"))
    m = rep.data[-1].metrics
    assert m["step_time_s"] > 0
    assert np.isfinite(m["final_loss"])
