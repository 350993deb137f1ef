"""Pallas TPU kernel for the Mamba-2 SSD (state-space duality) scan.

TPU adaptation of the chunked SSD algorithm: the chunk dimension is the
innermost (sequential) grid axis; the running inter-chunk state S (N×P per
head) lives in VMEM scratch and never round-trips to HBM — the key win over
the XLA lowering, which materializes per-chunk states.  Each grid step does
three MXU contractions (CB^T score matrix, intra-chunk y, state update) on
a (chunk × head_dim) tile plus VPU work for the decay masks.

The in-chunk prefix sum of the log-decay is two matmuls against a
lower-triangular ones matrix (Mosaic has no ``cumsum``), one per layout:
``cum_i`` broadcast along columns and ``cum_j`` broadcast along rows.  They
run at HIGHEST precision because the log-decays are summed over a whole
chunk and exponentiated.  ``ops.py`` premultiplies ``dt * A`` and passes
``dt``/``dA`` in both column and row layouts, so no scalar is read from a
vector block and no vector is transposed in the kernel.

Grid: (B*H, n_chunks).  ``ops.py`` flattens heads and broadcasts groups.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _ssd_kernel(
    x_ref,       # (1, c, P)
    dt_c_ref,    # (1, c, 1)  f32 dt (post-softplus), column layout
    da_c_ref,    # (1, c, 1)  f32 dt * A (log-decay per step), column layout
    dt_r_ref,    # (1, 1, c)  the same two in row layout
    da_r_ref,    # (1, 1, c)
    b_ref,       # (1, c, N)
    c_ref,       # (1, c, N)
    y_ref,       # (1, c, P)
    s_scr,       # VMEM (N, P) f32 — running inter-chunk state
    *,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0].astype(jnp.float32)           # (c, P)
    dt_c = dt_c_ref[0]                         # (c, 1)
    da_c = da_c_ref[0]                         # (c, 1)
    dt_r = dt_r_ref[0]                         # (1, c)
    da_r = da_r_ref[0]                         # (1, c)
    Bm = b_ref[0].astype(jnp.float32)          # (c, N)
    Cm = c_ref[0].astype(jnp.float32)          # (c, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = jj <= ii
    tri = causal.astype(jnp.float32)           # tri[i, k] = k <= i
    # cum_i[i, j] = sum_{k<=i} dA_k ;  cum_j[i, j] = sum_{k<=j} dA_k.
    cum_i = jax.lax.dot_general(
        tri, jnp.broadcast_to(da_c, (chunk, chunk)), (((1,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    cum_j = jax.lax.dot_general(
        jnp.broadcast_to(da_r, (chunk, chunk)), tri, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    cum_c = jnp.max(cum_i, axis=1, keepdims=True)   # (c, 1): every column is cum_i
    total = jnp.sum(da_c, axis=0, keepdims=True)    # (1, 1): cum at chunk end

    # Intra-chunk: scores[i,j] = C_i.B_j * exp(cum_i - cum_j) * dt_j, j<=i.
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # (c, c)
    decay = jnp.where(causal, jnp.exp(jnp.minimum(cum_i - cum_j, 0.0)), 0.0)
    scores = scores * decay * dt_r
    y = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # (c, P)

    # Inter-chunk: y_i += C_i @ S_prev * exp(cum_i).
    y = y + jnp.exp(cum_c) * jax.lax.dot_general(
        Cm, s_scr[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # State update: S = exp(cum_end) * S_prev + sum_j exp(cum_end-cum_j) dt_j B_j x_j^T.
    seg = jnp.exp(total - cum_c) * dt_c        # (c, 1)
    s_new = jax.lax.dot_general(
        Bm * seg, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # (N, P)
    s_scr[...] = jnp.exp(total) * s_scr[...] + s_new

    y_ref[0] = y.astype(y_ref.dtype)


def ssd_pallas(
    x: jax.Array,    # (BH, T, P)
    dt: jax.Array,   # (BH, T)     f32, post-softplus
    dA: jax.Array,   # (BH, T)     f32, dt * A (A < 0 per head)
    Bm: jax.Array,   # (BH, T, N)  group-broadcast
    Cm: jax.Array,   # (BH, T, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    BH, T, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, "ops.py must pad"
    nc = T // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    col = pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0))
    row = pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c))
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            col, col, row, row,
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt[..., None], dA[..., None], dt[:, None, :], dA[:, None, :], Bm, Cm)
