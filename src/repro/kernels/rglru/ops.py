"""Public RG-LRU scan entry point."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.rglru.kernel import rglru_pallas


def _autotuned_blocks(a_shape, dtype) -> dict:
    """Promoted (chunk, block_w) from the autotune cache, when enabled."""
    import os

    if not os.environ.get("EXACB_AUTOTUNE_CACHE"):
        return {}
    from repro.core import autotune

    B, T, W = a_shape
    return autotune.cached_blocks("rglru", f"B{B}.T{T}.W{W}", str(dtype)) or {}


def rglru_scan(
    a: jax.Array,    # (B, T, W)
    g: jax.Array,    # (B, T, W)
    h0: Optional[jax.Array] = None,  # (B, W)
    *,
    chunk: Optional[int] = None,
    block_w: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    # Explicit arguments win, then the autotune cache, then 256/512.
    if chunk is None or block_w is None:
        tuned = _autotuned_blocks(a.shape, a.dtype)
        chunk = int(tuned.get("chunk", 256)) if chunk is None else chunk
        block_w = int(tuned.get("block_w", 512)) if block_w is None else block_w
    return _rglru_scan_jit(a, g, h0, chunk=chunk, block_w=block_w,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def _rglru_scan_jit(
    a: jax.Array,
    g: jax.Array,
    h0: Optional[jax.Array] = None,
    *,
    chunk: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, T, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), a.dtype)
    c = min(chunk, T)
    bw = min(block_w, W)
    pad_t = (-T) % c
    pad_w = (-W) % bw
    if pad_t or pad_w:
        # pad decay with 1s (identity) and input with 0s
        a = jnp.pad(a, ((0, 0), (0, pad_t), (0, pad_w)), constant_values=1.0)
        g = jnp.pad(g, ((0, 0), (0, pad_t), (0, pad_w)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    out = rglru_pallas(a, g, h0[:, None, :], chunk=c, block_w=bw, interpret=interpret)
    return out[:, :T, :W]
