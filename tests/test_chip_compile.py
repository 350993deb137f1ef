"""Compile the chip path for a described TPU v5e, without the chip.

The TPU compiler refuses what interpret mode accepts (unsupported
primitives, misaligned slices, programs that do not fit), so the kernels at
real widths and the full-width starcoder2-3b serving steps are compiled here
for a ``v5e:2x2`` topology description.  Nothing runs; this proves only that
the chip's compiler accepts the programs and that they fit its memory.
"""

import collections
import dataclasses
import math
import os
import re

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


@pytest.fixture(scope="module")
def starcoder2_decode(starcoder2):
    """The engine's decode step, compiled through its own path, and the
    shapes of the model's state."""
    from repro.models import transformer as T
    from repro.serve.engine import compile_decode_step

    cfg, params = starcoder2
    compiled = compile_decode_step(cfg, params, batch=8, max_len=2048)
    state = jax.eval_shape(lambda: T.init_decode_state(cfg, 8, 2048))
    return compiled, params, state


def test_starcoder2_decode_step_fits_one_chip(starcoder2_decode):
    compiled = starcoder2_decode[0]
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


def _assert_state_stays_in_place(compiled, params, state):
    """Every state leaf is aliased from input to output, and the entry
    computation copies no buffer the size of one layer's state: no copy or
    transpose of the state into another layout and back around the step."""
    leaves = jax.tree.leaves(state)
    text = compiled.as_text()
    aliased = {int(p) for p in re.findall(r"\{\d+\}: \((\d+), \{\}, \w+-alias\)",
                                          text.splitlines()[0])}
    first = len(jax.tree.leaves(params))
    assert aliased == set(range(first, first + len(leaves)))
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        math.prod(a.shape) * a.dtype.itemsize for a in leaves)
    entry = text[text.index("\nENTRY"):]
    entry = entry[: entry.index("\n}\n")]
    copied = [math.prod(int(d) for d in m.group(1).split(",") if d)
              for m in re.finditer(r"= \(?\w+\[([\d,]*)\]\S* (?:copy|copy-start|transpose)\(",
                                   entry)]
    assert copied and max(copied) < min(math.prod(a.shape[1:]) for a in leaves)


def test_starcoder2_decode_state_stays_in_place(starcoder2_decode):
    """The step keeps its stacked cache in the model's own order, as the
    loop reads each layer's cache in place and writes one token into it."""
    compiled, params, state = starcoder2_decode
    assert {a.shape for a in jax.tree.leaves(state)} == {(30, 8, 2, 2048, 128)}
    _assert_state_stays_in_place(compiled, params, state)


@pytest.fixture(scope="module")
def qwen3_4b_decode(one_chip):
    """The engine's decode step at qwen3-4b's published widths (hf
    Qwen/Qwen3-4B: 36 layers, d_model 2560, 32 query heads on 8 KV heads of
    128, SwiGLU 9728, tied head), batch 16 and ``max_len`` 1152, its params'
    and state's shapes, and the shape of one layer's K cache."""
    from repro import configs
    from repro.models import params as P
    from repro.models import transformer as T
    from repro.serve.engine import compile_decode_step

    cfg = dataclasses.replace(configs.get_config("qwen3-32b"), name="qwen3-4b", n_layers=36,
                              d_model=2560, n_heads=32, d_ff=9728, tie_embeddings=True)
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), P.abstract_params(cfg))
    compiled = compile_decode_step(cfg, params, batch=16, max_len=1152)
    state = jax.eval_shape(lambda: T.init_decode_state(cfg, 16, 1152))
    return compiled, params, state, (16, cfg.n_kv_heads, 1152, cfg.head_dim)


def test_qwen3_4b_decode_state_stays_in_place(qwen3_4b_decode):
    compiled, params, state, layer = qwen3_4b_decode
    assert {a.shape for a in jax.tree.leaves(state)} == {(36,) + layer}
    _assert_state_stays_in_place(compiled, params, state)


def _computation(text, name):
    """The instructions of computation ``name``: (name, result shapes,
    opcode, operand names) each."""
    start = re.search(rf"^%?{re.escape(name)} ", text, re.M).start()
    body = text[start: text.index("\n}", start)]
    out = []
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\((.*)$", body, re.M):
        shapes = [tuple(int(d) for d in dims.split(",") if d)
                  for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(2))]
        out.append((m.group(1), shapes, m.group(3), re.findall(r"%([\w.\-]+)", m.group(4))))
    return out


def test_decode_loop_writes_only_the_token(qwen3_4b_decode):
    """Each attention layer reads its cache where it lies in the stacked
    carry and writes back one token: the scan's body makes no copy, slice
    or fusion result that holds a layer's K or V cache, every update of the
    stacked cache is one token's size, and no temporary is as large as one
    layer's K cache."""
    compiled, _, _, layer = qwen3_4b_decode
    text = compiled.as_text()
    bodies = re.findall(r"while\(.*?body=%?([\w.\-]+)", text)
    assert len(bodies) == 1
    body = _computation(text, bodies[0])
    shapes = {name: shapes for name, shapes, _, _ in body}

    def holds_a_layer(shape):
        return not collections.Counter(d for d in layer if d != 1) - collections.Counter(shape)

    big = [(name, op, s) for name, ss, op, _ in body for s in ss
           if op in ("fusion", "copy", "copy-start", "copy-done", "dynamic-slice")
           and holds_a_layer(s)]
    assert not big
    token = math.prod(layer) // layer[2]
    updates = [math.prod(shapes[operands[1]][0]) for name, ss, op, operands in body
               if op == "dynamic-update-slice" and len(ss[0]) == 5 and holds_a_layer(ss[0])]
    assert updates == [token, token]
    assert compiled.memory_analysis().temp_size_in_bytes < math.prod(layer) * 2
