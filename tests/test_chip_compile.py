"""Compile the chip path for a described TPU v5e, without the chip.

The TPU compiler refuses what interpret mode accepts (unsupported
primitives, misaligned slices, programs that do not fit), so the kernels at
real widths and the full-width starcoder2-3b serving steps are compiled here
for a ``v5e:2x2`` topology description.  Nothing runs; this proves only that
the chip's compiler accepts the programs and that they fit its memory.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not describable here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """Compiles for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_attention_compiles_at_starcoder2_widths(one_chip, window):
    from repro.kernels.flash_attention.ops import flash_attention

    q = _sds((1, 24, 4096, 128), "bfloat16", one_chip)
    kv = _sds((1, 2, 4096, 128), "bfloat16", one_chip)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, window=window, interpret=False),
        q, kv, kv)
    _assert_kernel_fits(compiled)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_rglru_compiles_at_recurrentgemma_width(one_chip, dtype):
    from repro.kernels.rglru.ops import rglru_scan

    a = _sds((1, 4096, 2560), dtype, one_chip)
    compiled = _compile(lambda a, g: rglru_scan(a, g, interpret=False), a, a)
    _assert_kernel_fits(compiled)


def test_ssd_compiles_at_mamba2_widths(one_chip):
    from repro.kernels.ssd.ops import ssd_scan

    B, T, H, P, N = 1, 4096, 64, 64, 128
    compiled = _compile(
        lambda x, dt, A, Bm, Cm: ssd_scan(x, dt, A, Bm, Cm, interpret=False),
        _sds((B, T, H, P), "bfloat16", one_chip),
        _sds((B, T, H), "float32", one_chip),
        _sds((H,), "float32", one_chip),
        _sds((B, T, 1, N), "bfloat16", one_chip),
        _sds((B, T, 1, N), "bfloat16", one_chip))
    _assert_kernel_fits(compiled)


@pytest.fixture(scope="module")
def starcoder2(one_chip):
    from repro import configs
    from repro.models import params as P

    cfg = configs.get_config("starcoder2-3b")
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), P.abstract_params(cfg))
    return cfg, params


def test_starcoder2_prefill_compiles_with_flash_kernel(one_chip, starcoder2):
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.models import transformer as T

    cfg, params = starcoder2
    tokens = {"tokens": _sds((8, 512), "int32", one_chip)}
    fa_ops.install(interpret=False)
    try:
        compiled = _compile(
            lambda p, b: T.prefill(p, cfg, b, max_len=2048, remat="none"), params, tokens)
    finally:
        fa_ops.uninstall()
    _assert_kernel_fits(compiled)


def test_starcoder2_decode_step_fits_one_chip(one_chip, starcoder2):
    from repro.models import transformer as T

    cfg, params = starcoder2
    state = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: T.init_decode_state(cfg, 8, 2048)))
    compiled = _compile(
        lambda p, s, b, i: T.decode_step(p, cfg, s, b, i),
        params, state, {"tokens": _sds((8, 1), "int32", one_chip)},
        _sds((), "int32", one_chip))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES
