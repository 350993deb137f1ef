"""Public entry point for flash attention: jit wrapper + layout handling.

Call ``flash_attention(q, k, v, ...)`` with model-layout tensors
(B, H, T, D).  The kernel is lowered for the TPU; a caller without one
passes ``interpret=True``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas


def _autotuned_blocks(q_shape, dtype) -> dict:
    """Promoted (block_q, block_k) for this shape on this hardware, if the
    autotune cache is enabled (``EXACB_AUTOTUNE_CACHE``) and holds a
    matching entry.  Import stays local: a bare kernel call must not pull
    the benchmarking core unless the cache is actually switched on."""
    import os

    if not os.environ.get("EXACB_AUTOTUNE_CACHE"):
        return {}
    from repro.core import autotune

    B, H, T, D = q_shape
    key = f"B{B}.H{H}.T{T}.D{D}"
    return autotune.cached_blocks("flash_attention", key, str(dtype)) or {}


def flash_attention(
    q: jax.Array,   # (B, Hq, T, D)
    k: jax.Array,   # (B, Hkv, T, D)
    v: jax.Array,   # (B, Hkv, T, Dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Block resolution happens *outside* the jit: explicit arguments win,
    then the autotune cache, then the shipped 512/512 defaults — so a
    promoted config changes behavior without any call-site edits."""
    if block_q is None or block_k is None:
        tuned = _autotuned_blocks(q.shape, q.dtype)
        block_q = int(tuned.get("block_q", 512)) if block_q is None else block_q
        block_k = int(tuned.get("block_k", 512)) if block_k is None else block_k
    return _flash_attention_jit(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def _flash_attention_jit(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, T, D = q.shape
    Hkv = k.shape[1]
    Dv = v.shape[-1]
    bq = min(block_q, T)
    bk = min(block_k, T)
    pad = (-T) % max(bq, bk)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    Tp = T + pad
    out = flash_attention_pallas(
        q.reshape(B * Hq, Tp, D),
        k.reshape(B * Hkv, Tp, D),
        v.reshape(B * Hkv, Tp, Dv),
        n_q_heads=Hq,
        n_kv_heads=Hkv,
        causal=causal,
        window=window or 0,
        scale=scale,
        block_q=bq,
        block_k=bk,
        interpret=interpret,
    )
    return out.reshape(B, Hq, Tp, Dv)[:, :, :T]


def _impl_adapter(q, k, v, *, causal=True, window=None, prefix_len=0, scale=None,
                  interpret=False, **_):
    if prefix_len:
        # Prefix-LM masks are not in the kernel's contract; jnp path handles.
        from repro.models.layers import banded_attention

        return banded_attention(
            q, k, v, causal=causal, window=window, prefix_len=prefix_len, scale=scale
        )
    return flash_attention(q, k, v, causal=causal, window=window, scale=scale,
                           interpret=interpret)


def install(*, interpret: bool = False) -> None:
    """Route model attention through the Pallas kernel."""
    from repro.models import layers as L

    L.set_attention_impl(functools.partial(_impl_adapter, interpret=interpret))


def uninstall() -> None:
    from repro.models import layers as L

    L.set_attention_impl(None)
