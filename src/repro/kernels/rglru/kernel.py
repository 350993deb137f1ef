"""Pallas TPU kernel for the RG-LRU gated linear recurrence.

h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

The recurrence is elementwise over the width dimension (VPU work, no MXU),
so the TPU-native win is purely memory locality: the running state h stays
in VMEM scratch across sequence chunks (innermost sequential grid axis), and
within a chunk the recurrence unrolls as a log-depth Blelloch-style
associative combine on registers instead of T sequential HBM round-trips.

Grid: (B, n_chunks, W/block_w).  Inputs are pre-gated: callers pass
a (decay, already exp()'d) and the gated input g = i_t * x_t * sqrt(1-a^2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, g_ref, h0_ref, y_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)  # chunk axis is innermost: it carries the state

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)  # (1, bw) initial state

    a = a_ref[0].astype(jnp.float32)   # (c, bw)
    g = g_ref[0].astype(jnp.float32)   # (c, bw)

    # Blelloch scan over the chunk (log2(c) combine rounds, on registers).
    # Combine: (a1, b1) ∘ (a2, b2) = (a1*a2, b1*a2 + b2).
    av, bv = a, g
    shift = 1
    while shift < chunk:
        a_prev = jnp.pad(av, ((shift, 0), (0, 0)), constant_values=1.0)[:chunk]
        b_prev = jnp.pad(bv, ((shift, 0), (0, 0)), constant_values=0.0)[:chunk]
        av, bv = a_prev * av, b_prev * av + bv
        shift *= 2
    # h_t = prefix_a_t * h_in + prefix_b_t
    h_in = h_scr[...]
    y = av * h_in + bv
    h_scr[...] = y[-1:, :]
    y_ref[0] = y.astype(y_ref.dtype)


def rglru_pallas(
    a: jax.Array,    # (B, T, W) decay in (0,1)
    g: jax.Array,    # (B, T, W) gated input
    h0: jax.Array,   # (B, 1, W) initial state
    *,
    chunk: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, T, W = a.shape
    chunk = min(chunk, T)
    block_w = min(block_w, W)
    assert T % chunk == 0 and W % block_w == 0, "ops.py must pad"
    nc = T // chunk
    nw = W // block_w
    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    # Chunk axis must be INNERMOST: the scratch state is per-(b, w-block) and
    # is re-initialized when the chunk index wraps to 0.
    return pl.pallas_call(
        kernel,
        grid=(B, nw, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, chunk, block_w), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, 1, block_w), lambda b, w, c: (b, 0, w)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_w), lambda b, w, c: (b, c, w)),
        out_shape=jax.ShapeDtypeStruct((B, T, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, g, h0)
