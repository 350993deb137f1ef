"""Pallas TPU flash-attention kernel (causal / sliding-window / GQA).

TPU-native adaptation of the flash algorithm: q blocks are pinned to VMEM
across the innermost (sequential) kv-block grid dimension; the online-softmax
state (m, l, acc) lives in VMEM scratch; causal/window block skipping is a
``pl.when`` predicate on grid indices, so out-of-band blocks issue no MXU
work.  Block shapes default to 512×512 — q/k/v tiles of 512×128 bf16 plus
f32 scratch fit comfortably in the ~16 MB v5e VMEM while keeping the MXU's
128×128 systolic array fully fed.

Layout contract (``ops.py`` prepares it): q: (BH, T, D) with BH = B*Hq;
k/v: (BKV, T, D) with BKV = B*Hkv; the index map folds GQA head groups.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # VMEM blocks
    o_ref,
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    block_q: int,
    block_k: int,
    seq_len: int,
    causal: bool,
    window: int,          # 0 = global
    n_kv_blocks: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level band check (static per grid step via program ids).
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = kj * block_k
    k_hi = k_lo + block_k - 1
    live = jnp.asarray(True)
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window > 0:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)           # (bq, D)
        k = k_ref[0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0]                               # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                   # (bq, bk)
        iq = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        jk = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jk < seq_len
        if causal:
            mask = jnp.logical_and(mask, jk <= iq)
        if window > 0:
            mask = jnp.logical_and(mask, jk > iq - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                     # (bq, bk)
        corr = jnp.exp(m_prev - m_new)             # (bq, 1)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,   # (BH, T, D)
    k: jax.Array,   # (BKV, T, D)
    v: jax.Array,   # (BKV, T, Dv)
    *,
    n_q_heads: int,
    n_kv_heads: int,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    BH, T, D = q.shape
    Dv = v.shape[-1]
    group = n_q_heads // n_kv_heads
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    assert T % block_q == 0 and T % block_k == 0, "ops.py must pad"
    nq = T // block_q
    nk = T // block_k

    def kv_index(bh, i, j):
        b = bh // n_q_heads
        h = bh % n_q_heads
        return b * n_kv_heads + h // group, j, 0

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        seq_len=T,
        causal=causal,
        window=window,
        n_kv_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, Dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
