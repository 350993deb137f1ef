#!/usr/bin/env python3
"""Run the serving path on the chip once, at a published width, and check it.

    python chip_smoke.py                  # one TPU: starcoder2-3b serving + kernels
    python chip_smoke.py --four-chips     # four TPUs: glm4-9b sharded prefill/decode
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--four-chips]

One chip: starcoder2-3b at its published widths (30 layers, d_model 3072,
GQA 24/2, bf16) on seeded random weights, through ``repro.launch.serve``'s
engine with the Pallas flash kernel installed, serves 8 greedy requests
(batch 8, cache 2048, 512-token prompts, 32 new tokens).  The first wave's
prefill logits are compared with the same prefill through
``banded_attention``, and each Pallas kernel runs once at a real width
against its ``ref.py``.

Four chips: glm4-9b (about 19 GB of bf16 weights, more than one chip holds)
at full width through ``distributed.steps`` on a (data 1, model 4) mesh, at
batch 4 and sequence 2048, then a 4-layer cut of it on one chip and on four
in the same process, whose logits must agree.

Lines before the last report what ran (device, compile and wall seconds per
phase, tokens, device memory, whether each step holds the kernel); they are
not benchmark metrics.  The last line is one JSON object naming the device,
printed only when every phase passed; any failure exits nonzero.

``--rehearse`` runs the same code on the CPU at the configs' smoke sizes
with the kernels in interpret mode (and on 4 virtual CPU devices with
``--four-chips``); it never reports a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Agreement bounds, as relative RMS error ||got - want|| / ||want|| of f32
# logits or kernel outputs.  bf16 keeps 8 mantissa bits, so one rounding is
# ~2^-9 ≈ 2e-3 relative; the paths compared round at different points in
# every layer (kernel vs jnp attention, sharded vs whole matmuls), and such
# roundings accumulate over 30 layers to ~1e-2.  2e-2 admits that and fails
# a path computing in a precision below bf16 (fp8 alone rounds at 6e-2).
LOGITS_RTOL = 2e-2
# Kernel vs ref.py, by the same measure and bound.  Not elementwise: the
# MXU multiplies f32 operands in bf16 passes unless asked for HIGHEST, so
# an output that is a sum cancelling to near zero keeps the absolute error
# of its terms (SSD at mamba2 widths: 20 of 16.7M elements off by 0.03
# where the reference is 0.007, at relative RMS 2.6e-3 overall).
KERNEL_RTOL = 2e-2

ONE_CHIP = dict(arch="starcoder2-3b", batch=8, requests=8, max_len=2048,
                prompt_len=512, new_tokens=32)
FOUR_CHIPS = dict(arch="glm4-9b", batch=4, seq=2048, decode_steps=8, cut_layers=4)


@contextlib.contextmanager
def phase(name: str):
    """Times one phase; an exception inside it ends the run."""
    t0 = time.perf_counter()
    print(f"[phase] {name} ...", flush=True)
    yield
    print(f"[phase] {name} ok wall_s={time.perf_counter() - t0!r}", flush=True)


def _info(**kv):
    print("[info] " + " ".join(f"{k}={v!r}" for k, v in kv.items()), flush=True)


def _rel_rms(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def _check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _aot(jitted, *args):
    """Compile ``jitted`` for ``args``; (seconds, has Pallas kernel)."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return time.perf_counter() - t0, "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def serve_one_chip(*, rehearse: bool, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.launch import serve
    from repro.models import transformer as T

    sz = ONE_CHIP
    if rehearse:
        sz = dict(sz, max_len=128, prompt_len=32, new_tokens=8)
        cfg = configs.get_smoke(sz["arch"])
    else:
        cfg = configs.get_config(sz["arch"])
    _info(config=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
          n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, dtype=cfg.dtype, **sz)

    fa_ops.install(interpret=rehearse)
    try:
        with phase("init_params"):
            eng = serve.build_engine(cfg, batch=sz["batch"], max_len=sz["max_len"], seed=seed)
            jax.block_until_ready(eng.params)
        reqs = serve.make_requests(cfg, sz["requests"], prompt_len=sz["prompt_len"],
                                   max_new_tokens=sz["new_tokens"], seed=seed)
        first = {"tokens": jnp.asarray(np.stack([r.prompt for r in reqs[:sz["batch"]]]))}

        with phase("compile_steps"):
            t_pre, pre_kernel = _aot(eng._prefill, eng.params, first)
            state = jax.eval_shape(eng._prefill, eng.params, first)[1]
            tok = jax.ShapeDtypeStruct((sz["batch"], 1), jnp.int32)
            t_dec, dec_kernel = _aot(eng._decode, eng.params, state, {"tokens": tok},
                                     jax.ShapeDtypeStruct((), jnp.int32))
            _info(prefill_compile_s=t_pre, prefill_tpu_custom_call=pre_kernel,
                  decode_compile_s=t_dec, decode_tpu_custom_call=dec_kernel)
            if not rehearse:
                _check(pre_kernel, "the prefill step holds no Pallas kernel")

        with phase("serve"):
            t0 = time.perf_counter()
            outs = eng.generate(reqs)
            wall = time.perf_counter() - t0
            tokens = [t for c in outs for t in c.tokens]
            _info(requests=len(outs), tokens_produced=len(tokens), generate_wall_s=wall)
            _check(len(outs) == sz["requests"], "not every request completed")
            _check(all(len(c.tokens) == sz["new_tokens"] for c in outs),
                   "a request produced the wrong number of tokens")
            _check(all(0 <= t < cfg.vocab_size for t in tokens), "token outside the vocabulary")

        with phase("prefill_vs_banded"):
            got = np.asarray(eng._prefill(eng.params, first)[0])
            fa_ops.uninstall()
            ref_prefill = jax.jit(
                lambda p, b: T.prefill(p, cfg, b, max_len=sz["max_len"], remat="none")[0])
            want = np.asarray(ref_prefill(eng.params, first))
            err = _rel_rms(got, want)
            same = bool(np.array_equal(got[:, 0].argmax(-1), want[:, 0].argmax(-1)))
            _info(logits_shape=got.shape, rel_rms=err, bound=LOGITS_RTOL,
                  max_abs_diff=float(np.max(np.abs(got - want))),
                  greedy_first_tokens_equal=same)
            _check(np.isfinite(got).all() and np.isfinite(want).all(), "non-finite logits")
            _check(err <= LOGITS_RTOL, f"kernel vs banded logits rel_rms {err} > {LOGITS_RTOL}")
            _check(same, "greedy first tokens differ between kernel and banded prefill")
        _info(peak_bytes_in_use=_peak_bytes(jax.devices()[0]))
    finally:
        fa_ops.uninstall()
    del eng


def kernels_vs_ref(*, rehearse: bool, seed: int):
    """Each Pallas kernel once at a real width (smoke widths when
    rehearsing) against its ``ref.py``, references at full f32 precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.rglru import ops as lru_ops
    from repro.kernels.rglru import ref as lru_ref
    from repro.kernels.ssd import ops as ssd_ops
    from repro.kernels.ssd import ref as ssd_ref

    T = 256 if rehearse else 4096
    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16

    def compare(name, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = _rel_rms(got, want)
        _info(kernel=name, shape=got.shape, max_abs_diff=float(np.max(np.abs(got - want))),
              rel_rms=err, bound=KERNEL_RTOL)
        _check(np.isfinite(got).all(), f"{name}: non-finite output")
        _check(err <= KERNEL_RTOL, f"{name}: rel_rms {err} > {KERNEL_RTOL}")

    with phase("kernel_flash_attention"):
        Hq, Hkv, D = (4, 2, 32) if rehearse else (24, 2, 128)   # starcoder2-3b
        q = jnp.asarray(rng.standard_normal((1, Hq, T, D)), bf16)
        k, v = (jnp.asarray(rng.standard_normal((1, Hkv, T, D)), bf16) for _ in range(2))
        got = fa_ops.flash_attention(q, k, v, interpret=rehearse)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(fa_ref.attention_ref)(q, k, v)
        compare("flash_attention", got, want)

    with phase("kernel_rglru"):
        W = 128 if rehearse else 2560                            # recurrentgemma-2b
        a = jnp.asarray(rng.uniform(0.7, 0.999, (1, T, W)), bf16)
        g = jnp.asarray(rng.standard_normal((1, T, W)) * 0.1, bf16)
        got = lru_ops.rglru_scan(a, g, interpret=rehearse)
        want = jax.jit(lru_ref.rglru_ref)(a, g, jnp.zeros((1, 1, W), bf16))
        compare("rglru", got, want)

    with phase("kernel_ssd"):
        H, P, N = (4, 32, 16) if rehearse else (64, 64, 128)     # mamba2-1.3b
        x = jnp.asarray(rng.standard_normal((1, T, H, P)), bf16)
        dt = jnp.asarray(rng.uniform(0.001, 0.1, (1, T, H)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
        Bm, Cm = (jnp.asarray(rng.standard_normal((1, T, 1, N)), bf16) for _ in range(2))
        got = ssd_ops.ssd_scan(x, dt, A, Bm, Cm, interpret=rehearse)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ssd_ref.ssd_scan_ref)(x, dt, A, Bm, Cm)
        compare("ssd", got, want)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _sharded_run(cfg, mesh, *, batch, seq, decode_steps, seed, tag=""):
    """Prefill ``seq - decode_steps`` tokens then decode greedily on ``mesh``;
    returns the logits of every step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import shapes as SH
    from repro.distributed import sharding as S
    from repro.distributed import steps as ST
    from repro.models import params as P

    strat = S.STRATEGIES["tp_dp"]
    prompt = seq - decode_steps
    pre = ST.build_prefill_step(cfg, SH.ShapeSpec("prompt", SH.PREFILL, prompt, batch),
                                mesh, strat, remat="none", max_len=seq)
    dec = ST.build_decode_step(cfg, SH.ShapeSpec("cache", SH.DECODE, seq, batch), mesh, strat)
    with phase(f"init_params{tag}"):
        params = P.init_params(cfg, jax.random.key(seed),
                               shardings=S.param_shardings(cfg, mesh, strat))
        jax.block_until_ready(params)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (batch, prompt)), jnp.int32)
    with phase(f"compile{tag}"), mesh:
        pre_fn, dec_fn = pre.jit(), dec.jit()
        t_pre, _ = _aot(pre_fn, *pre.abstract_args)
        t_dec, _ = _aot(dec_fn, *dec.abstract_args)
        _info(prefill_compile_s=t_pre, decode_compile_s=t_dec)
    out = []
    with phase(f"prefill_decode{tag}"), mesh:
        logits, state = pre_fn(params, {"tokens": tokens})
        out.append(np.asarray(logits))
        for i in range(decode_steps):
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            logits, state = dec_fn(params, state, {"tokens": nxt},
                                   jnp.asarray(prompt + i, jnp.int32))
            out.append(np.asarray(logits))
    return out


def serve_four_chips(*, rehearse: bool, seed: int):
    import jax
    import numpy as np

    from repro import configs
    from repro.hardware import V5E_2X2
    from repro.launch.mesh import make_mesh, make_smoke_mesh

    sz = dict(FOUR_CHIPS)
    if rehearse:
        sz.update(seq=64, decode_steps=4)
    cfg = (configs.get_smoke if rehearse else configs.get_config)(sz["arch"])
    devices = jax.devices()
    _check(len(devices) == 4, f"--four-chips needs 4 devices, found {len(devices)}")
    mesh4 = make_mesh(V5E_2X2)
    _info(config=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
          n_kv_heads=cfg.n_kv_heads, mesh=dict(mesh4.shape), **sz)

    logits = _sharded_run(cfg, mesh4, batch=sz["batch"], seq=sz["seq"],
                          decode_steps=sz["decode_steps"], seed=seed)
    _check(all(np.isfinite(l).all() for l in logits), "non-finite logits at full width")
    _check(logits[-1].shape == (sz["batch"], 1, cfg.vocab_size), "wrong logits shape")
    peaks = [_peak_bytes(d) for d in devices]
    _info(peak_bytes_in_use=peaks)
    if all(p is not None for p in peaks):
        mean = sum(peaks) / len(peaks)
        _check(max(peaks) <= 1.5 * mean, f"device memory unbalanced: {peaks}")
    del logits

    with phase("cut_one_vs_four"):
        cut = dataclasses.replace(cfg, n_layers=min(cfg.n_layers, sz["cut_layers"]))
        kw = dict(batch=sz["batch"], seq=sz["seq"], decode_steps=sz["decode_steps"], seed=seed)
        one = _sharded_run(cut, make_smoke_mesh(), tag=".one", **kw)
        four = _sharded_run(cut, mesh4, tag=".four", **kw)
        errs = [_rel_rms(f, o) for f, o in zip(four, one)]
        _info(n_layers=cut.n_layers, steps=len(errs), rel_rms_max=max(errs), bound=LOGITS_RTOL,
              max_abs_diff=max(float(np.max(np.abs(f - o))) for f, o in zip(four, one)))
        _check(all(np.isfinite(o).all() for o in one), "non-finite one-chip logits")
        _check(max(errs) <= LOGITS_RTOL,
               f"one-chip vs four-chip logits rel_rms {max(errs)} > {LOGITS_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded path and its comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke sizes on the CPU, kernels in interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # Only committed files decide what runs: no autotune cache, shipped blocks.
    os.environ.pop("EXACB_AUTOTUNE_CACHE", None)
    if args.rehearse and args.four_chips:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")
    import jax

    dev = jax.devices()[0]
    want = "cpu" if args.rehearse else "tpu"
    if dev.platform != want:
        print(f"chip_smoke: JAX found {dev.platform!r}, not a {want!r} device", file=sys.stderr)
        return 1
    if not args.rehearse:
        from repro.launch.cache import enable_compile_cache

        enable_compile_cache()
    _info(platform=dev.platform, device_kind=dev.device_kind, device_count=len(jax.devices()),
          jax=jax.__version__,
          compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
          or jax.config.jax_compilation_cache_dir)

    t0 = time.perf_counter()
    try:
        if args.four_chips:
            serve_four_chips(rehearse=args.rehearse, seed=args.seed)
        else:
            serve_one_chip(rehearse=args.rehearse, seed=args.seed)
            kernels_vs_ref(rehearse=args.rehearse, seed=args.seed)
    except Exception:  # noqa: BLE001 — any failed phase fails the run
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    _info(total_wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
