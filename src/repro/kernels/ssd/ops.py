"""Public SSD entry point: model layout in, kernel layout out."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ssd.kernel import ssd_pallas


def _autotuned_blocks(x_shape, n_state, dtype) -> dict:
    """Promoted chunk size from the autotune cache, when enabled."""
    import os

    if not os.environ.get("EXACB_AUTOTUNE_CACHE"):
        return {}
    from repro.core import autotune

    B, T, H, P = x_shape
    key = f"B{B}.T{T}.H{H}.P{P}.N{n_state}"
    return autotune.cached_blocks("ssd", key, str(dtype)) or {}


def ssd_scan(
    x: jax.Array,    # (B, T, H, P)
    dt: jax.Array,   # (B, T, H)  f32, post-softplus
    A: jax.Array,    # (H,)       f32, negative
    Bm: jax.Array,   # (B, T, G, N)
    Cm: jax.Array,   # (B, T, G, N)
    *,
    chunk: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    # Explicit argument wins, then the autotune cache, then 256.
    if chunk is None:
        tuned = _autotuned_blocks(x.shape, Bm.shape[3], x.dtype)
        chunk = int(tuned.get("chunk", 256))
    return _ssd_scan_jit(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_scan_jit(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    Cm: jax.Array,
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    c = min(chunk, T)
    pad = (-T) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Tp = T + pad
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, Tp, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, Tp).astype(jnp.float32)
    af = jnp.broadcast_to(A[None, :], (B, H)).reshape(B * H).astype(jnp.float32)
    Bh = jnp.repeat(Bm.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, Tp, N)
    Ch = jnp.repeat(Cm.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, Tp, N)
    y = ssd_pallas(xf, dtf, dtf * af[:, None], Bh, Ch, chunk=c, interpret=interpret)
    return y.reshape(B, H, Tp, P).transpose(0, 2, 1, 3)[:, :T]
