"""Layer implementations for the unified decoder substrate.

Every temporal mixer exposes two entry points:

* ``*_fullseq(params, x, ...) -> y``                — training / prefill
* ``*_decode(params, x, state, ...) -> (y, state)`` — one-token decode

States are pytrees so the whole stack's state can be stacked along the scan
axis.  All heavy attention paths go through ``chunked_attention`` — a pure-jnp
flash-style online-softmax implementation that (a) keeps compiled memory
realistic at 32k+ sequence lengths and (b) doubles as the oracle for the
Pallas TPU kernels in ``repro.kernels``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import LayerSpec, ModelConfig

Pytree = Any

# Kernel dispatch hook: repro.kernels.ops installs TPU Pallas implementations
# here when enabled; the default is the pure-jnp path (CPU / dry-run).
_ATTENTION_IMPL = {"impl": None}


def set_attention_impl(fn) -> None:
    _ATTENTION_IMPL["impl"] = fn


# ---------------------------------------------------------------------------
# Basics
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, D) with positions (..., T) or (T,). Rotates pairs (even/odd
    split convention, as used by llama/gemma/qwen)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, wi: jax.Array, wo: jax.Array) -> jax.Array:
    """Fused SwiGLU MLP.  wi: (d, 2, ff); wo: (ff, d)."""
    h = jnp.einsum("btd,dcf->btcf", x, wi)
    gate, up = h[..., 0, :], h[..., 1, :]
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return jnp.einsum("btf,fd->btd", act, wo)


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — pure jnp
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _block_mask(
    iq: jax.Array,
    jk: jax.Array,
    *,
    causal: bool,
    window: Optional[int],
    prefix_len: int,
    kv_valid: Optional[jax.Array],
) -> jax.Array:
    """Boolean mask (Tq_blk, Tk_blk) from absolute position vectors."""
    m = jnp.ones((iq.shape[0], jk.shape[0]), bool)
    if causal:
        c = jk[None, :] <= iq[:, None]
        if prefix_len:
            c = c | ((iq[:, None] < prefix_len) & (jk[None, :] < prefix_len))
        m = m & c
    if window is not None:
        m = m & (jk[None, :] > iq[:, None] - window)
    if kv_valid is not None:
        m = m & (jk[None, :] < kv_valid)
    return m


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
    q_offset: int = 0,
    kv_positions: Optional[jax.Array] = None,
    kv_valid: Optional[jax.Array] = None,
    chunk_q: int = 1024,
    chunk_k: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention over KV chunks.

    q: (B, Hq, Tq, D);  k, v: (B, Hkv, Tk, D) with Hq % Hkv == 0.
    ``kv_positions``: absolute position of each kv slot (Tk,) — used by
    ring-buffer caches; defaults to arange.
    ``kv_valid``: number of valid kv slots (scalar) for linear caches.
    """
    B, Hq, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    Dv = v.shape[-1]  # MLA: value dim may differ from qk dim
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    cq = min(chunk_q, Tq)
    ck = min(chunk_k, Tk)
    pad_q = (-Tq) % cq
    pad_k = (-Tk) % ck
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Tqp, Tkp = Tq + pad_q, Tk + pad_k
    nq, nk = Tqp // cq, Tkp // ck

    if kv_positions is None:
        kv_pos = jnp.arange(Tkp, dtype=jnp.int32)
    else:
        kv_pos = jnp.pad(kv_positions.astype(jnp.int32), (0, pad_k), constant_values=-1)
    kv_in_range = jnp.arange(Tkp) < Tk  # mask out pure padding slots

    qr = q.reshape(B, Hkv, G, nq, cq, D)
    kr = k.reshape(B, Hkv, nk, ck, D)
    vr = v.reshape(B, Hkv, nk, ck, Dv)

    def q_block(qi, q_blk):
        iq = q_offset + qi * cq + jnp.arange(cq, dtype=jnp.int32)

        def kv_step(carry, inputs):
            m_run, l_run, acc = carry
            k_blk, v_blk, jpos, jvalid = inputs
            s = jnp.einsum(
                "bhgqd,bhkd->bhgqk", q_blk, k_blk, preferred_element_type=jnp.float32
            ) * scale
            mask = _block_mask(
                iq, jpos, causal=causal, window=window,
                prefix_len=prefix_len, kv_valid=kv_valid,
            )
            mask = mask & jvalid[None, :] & (jpos[None, :] >= 0)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, Hkv, G, cq), NEG_INF, jnp.float32),
            jnp.zeros((B, Hkv, G, cq), jnp.float32),
            jnp.zeros((B, Hkv, G, cq, Dv), jnp.float32),
        )
        jpos_blocks = kv_pos.reshape(nk, ck)
        jvalid_blocks = kv_in_range.reshape(nk, ck)
        (m_f, l_f, acc_f), _ = jax.lax.scan(
            kv_step,
            init,
            (kr.swapaxes(0, 2).swapaxes(1, 2), vr.swapaxes(0, 2).swapaxes(1, 2),
             jpos_blocks, jvalid_blocks),
        )
        return acc_f / jnp.maximum(l_f, 1e-30)[..., None]

    if nq == 1:
        out = q_block(0, qr[:, :, :, 0])
        out = out[:, :, :, None]
    else:
        out = jax.lax.map(
            lambda args: q_block(args[0], args[1]),
            (jnp.arange(nq), qr.swapaxes(0, 3).swapaxes(1, 3).swapaxes(2, 3)),
        )  # (nq, B, Hkv, G, cq, D)
        out = jnp.moveaxis(out, 0, 3)  # (B, Hkv, G, nq, cq, D)
    out = out.reshape(B, Hq, Tqp, Dv)[:, :, :Tq]
    return out.astype(q.dtype)


def banded_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
    q_offset: int = 0,
    kv_positions: Optional[jax.Array] = None,
    kv_valid: Optional[jax.Array] = None,
    chunk_q: int = 1024,
    chunk_k: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causally-banded blocked attention: only lower-triangular (and, for
    sliding windows, in-band) block pairs are COMPUTED — the pure-jnp
    analogue of flash attention's causal block skipping.  Halves attention
    FLOPs vs ``chunked_attention`` for causal masks and cuts them ~T/window-
    fold for local layers.  Offsets are processed as a static Python loop
    (HLO size O(n_blocks)); within each offset all block rows batch into one
    einsum.  Semantics identical to ``chunked_attention`` (tested).
    """
    if kv_positions is not None or kv_valid is not None or q.shape[2] != k.shape[2]:
        # Ring caches / unequal lengths: fall back to the scanning variant.
        return chunked_attention(
            q, k, v, causal=causal, window=window, prefix_len=prefix_len,
            q_offset=q_offset, kv_positions=kv_positions, kv_valid=kv_valid,
            chunk_q=chunk_q, chunk_k=chunk_k, scale=scale,
        )
    B, Hq, T, D = q.shape
    _, Hkv, _, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    c = min(chunk_q, chunk_k, T)
    pad = (-T) % c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    Tp = T + pad
    n = Tp // c
    qr = q.reshape(B, Hkv, G, n, c, D)
    kr = k.reshape(B, Hkv, n, c, D)
    vr = v.reshape(B, Hkv, n, c, Dv)

    m_run = jnp.full((B, Hkv, G, n, c), NEG_INF, jnp.float32)
    l_run = jnp.zeros((B, Hkv, G, n, c), jnp.float32)
    acc = jnp.zeros((B, Hkv, G, n, c, Dv), jnp.float32)

    max_back = n - 1
    if window is not None:
        max_back = min(max_back, (window - 1) // c + 1)
    pb = (prefix_len + c - 1) // c if prefix_len else 0  # prefix blocks

    def apply_block(m_run, l_run, acc, rows, cols, qs, ks, vs):
        """rows/cols: block indices (len R). qs: (B,Hkv,G,R,c,D)."""
        iq = q_offset + rows[:, None] * c + jnp.arange(c)[None, :]  # (R,c)
        jk = cols[:, None] * c + jnp.arange(c)[None, :]
        s = jnp.einsum("bhgrqd,bhrkd->bhgrqk", qs, ks,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((rows.shape[0], c, c), bool)
        if causal:
            cm = jk[:, None, :] <= iq[:, :, None]
            if prefix_len:
                cm = cm | ((iq[:, :, None] < prefix_len) & (jk[:, None, :] < prefix_len))
            mask = mask & cm
        if window is not None:
            mask = mask & (jk[:, None, :] > iq[:, :, None] - window)
        mask = mask & (jk[:, None, :] < T)  # padding
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)                                  # (B,H,G,R,c)
        m_old = m_run[:, :, :, rows]
        m_new = jnp.maximum(m_old, m_blk)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_old - m_new)
        l_new = l_run[:, :, :, rows] * corr + jnp.sum(p, axis=-1)
        upd = jnp.einsum("bhgrqk,bhrkd->bhgrqd", p.astype(vs.dtype), vs,
                         preferred_element_type=jnp.float32)
        acc_new = acc[:, :, :, rows] * corr[..., None] + upd
        return (
            m_run.at[:, :, :, rows].set(m_new),
            l_run.at[:, :, :, rows].set(l_new),
            acc.at[:, :, :, rows].set(acc_new),
        )

    for o in range(0, max_back + 1):
        rows = jnp.arange(o, n)
        cols = rows - o
        if int(rows.shape[0]) == 0:
            continue
        qs = qr[:, :, :, o:]
        ks = kr[:, :, : n - o]
        vs = vr[:, :, : n - o]
        m_run, l_run, acc = apply_block(m_run, l_run, acc, rows, cols, qs, ks, vs)
    if pb > 1 and causal:
        # Prefix-LM: early rows also attend FORWARD within the prefix blocks.
        for u in range(1, pb):
            rows = jnp.arange(0, pb - u)
            cols = rows + u
            qs = qr[:, :, :, : pb - u]
            ks = kr[:, :, u:pb]
            vs = vr[:, :, u:pb]
            m_run, l_run, acc = apply_block(m_run, l_run, acc, rows, cols, qs, ks, vs)

    out = acc / jnp.maximum(l_run, 1e-30)[..., None]
    out = out.reshape(B, Hq, Tp, Dv)[:, :, :T]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Softmax attention layer (GQA / MQA / MHA, RoPE, qk-norm, windows, prefix-LM)
# ---------------------------------------------------------------------------

def _rope_theta(cfg: ModelConfig, spec: LayerSpec) -> float:
    return spec.rope_theta if spec.rope_theta is not None else cfg.rope_theta


def _attention_remat(q, k, v, *, window, prefix_len, scale=None):
    """Flash-style AD: discard the online-softmax internals (the per-chunk
    scan residuals are enormous) and recompute attention in the backward
    pass — the same trade flash attention's backward makes."""
    impl = _ATTENTION_IMPL["impl"] or banded_attention

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def inner(q, k, v):
        return impl(q, k, v, causal=True, window=window, prefix_len=prefix_len, scale=scale)

    return inner(q, k, v)


def attn_fullseq(
    p: Pytree,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    spec: LayerSpec,
    prefix_len: int = 0,
) -> jax.Array:
    B, T, _ = x.shape
    theta = _rope_theta(cfg, spec)
    q = jnp.einsum("btd,dhk->bhtk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bhtk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bhtk", x, p["wv"])
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    pos = jnp.arange(T, dtype=jnp.int32)
    q = apply_rope(q, pos, theta)
    k = apply_rope(k, pos, theta)
    out = _attention_remat(q, k, v, window=spec.window, prefix_len=prefix_len)
    return jnp.einsum("bhtk,hkd->btd", out, p["wo"])


def attn_init_cache(
    cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int, dtype=None
) -> Pytree:
    """Linear cache for global layers; ring buffer (size=window) for local."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    L = min(max_len, spec.window) if spec.window else max_len
    shape = (batch, cfg.n_kv_heads, L, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def _cache_write(cache_arr: jax.Array, new: jax.Array, idx: jax.Array, ring: bool):
    """Write one token (B, H, 1, D) at logical position idx.

    Uses a scatter (``.at[].set``) rather than dynamic-update-slice: with the
    cache sequence dim sharded over the model axis, SPMD lowers a DUS at a
    traced index to a masked select over the WHOLE local shard (measured 2x
    cache traffic per layer on musicgen decode); a single-row scatter
    partitions sparsely.
    """
    L = cache_arr.shape[2]
    slot = (idx % L) if ring else idx
    return cache_arr.at[:, :, slot].set(new[:, :, 0].astype(cache_arr.dtype))


def attn_prefill_cache(
    p: Pytree, x: jax.Array, *, cfg: ModelConfig, spec: LayerSpec, cache: Pytree
) -> Pytree:
    """Populate the cache from a full prefill sequence (post-RoPE K)."""
    B, T, _ = x.shape
    theta = _rope_theta(cfg, spec)
    k = jnp.einsum("btd,dhk->bhtk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bhtk", x, p["wv"])
    if cfg.use_qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    pos = jnp.arange(T, dtype=jnp.int32)
    k = apply_rope(k, pos, theta)
    L = cache["k"].shape[2]
    if spec.window and T > L:
        # Ring buffer: keep the last L tokens at slots pos % L.
        keep = jnp.arange(T - L, T)
        slots = keep % L
        k_keep = jnp.take(k, keep, axis=2)
        v_keep = jnp.take(v, keep, axis=2)
        order = jnp.argsort(slots)
        knew = jnp.take(k_keep, order, axis=2)
        vnew = jnp.take(v_keep, order, axis=2)
        return {"k": knew.astype(cache["k"].dtype), "v": vnew.astype(cache["v"].dtype)}
    pad = L - T
    if pad > 0:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return {"k": k.astype(cache["k"].dtype), "v": v.astype(cache["v"].dtype)}


def attn_decode(
    p: Pytree,
    x: jax.Array,
    cache: Pytree,
    idx: jax.Array,
    *,
    cfg: ModelConfig,
    spec: LayerSpec,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Pytree]:
    """x: (B, 1, d); idx: scalar int32, the position being generated.

    Attends over the cache as it stands, with the new token's own key and
    value joined in the softmax, then writes the token into the cache.  With
    ``layer``, the cache arrays are the decode scan's stacked (n, B, H, L, D)
    carry: the layer's cache is read where it lies and only the token's slot
    is written back, so a step moves one token's bytes per layer, not the
    layer's whole cache.
    """
    theta = _rope_theta(cfg, spec)
    q = jnp.einsum("btd,dhk->bhtk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bhtk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bhtk", x, p["wv"])
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    posv = jnp.full((1,), idx, jnp.int32)
    q = apply_rope(q, posv, theta)
    k = apply_rope(k, posv, theta)
    # The token attends as the cache will hold it.
    k = k.astype(cache["k"].dtype)
    v = v.astype(cache["v"].dtype)
    L = cache["k"].shape[-2]
    ring = spec.window is not None and L == spec.window
    if layer is None:
        old = cache
        cache = {"k": _cache_write(cache["k"], k, idx, ring),
                 "v": _cache_write(cache["v"], v, idx, ring)}
    else:
        # A dynamic-update-slice, not a scatter: the scatter makes the
        # compiler keep the stacked cache sequence-major and copy layers.
        old = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
               for n, a in cache.items()}
        start = (layer, 0, 0, (idx % L) if ring else idx, 0)
        cache = {"k": jax.lax.dynamic_update_slice(cache["k"], k[None], start),
                 "v": jax.lax.dynamic_update_slice(cache["v"], v[None], start)}
    out = _decode_attention(q, old["k"], old["v"], k, v, idx, spec)
    return jnp.einsum("bhtk,hkd->btd", out, p["wo"]), cache


def _decode_attention(q, k, v, k_new, v_new, idx, spec):
    """One-token attention at position ``idx`` over a (possibly ring) cache
    that does not hold the token yet, and the token's own key and value."""
    B, Hkv, L, D = k.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    qr = q.reshape(B, Hkv, G, 1, D)

    def scores(keys):
        return jnp.einsum("bhgqd,bhkd->bhgqk", qr, keys.astype(qr.dtype),
                          preferred_element_type=jnp.float32) / math.sqrt(D)

    slots = jnp.arange(L, dtype=jnp.int32)
    if spec.window is not None and L == spec.window:
        # Ring: the slot the token goes to holds position idx - L, and is
        # excluded with the slots not yet written (kv_pos < 0).
        kv_pos = idx - jnp.mod(idx - slots, L)
        mask = (kv_pos >= 0) & (kv_pos < idx)
    else:
        kv_pos = slots
        mask = slots < idx
    if spec.window is not None:
        mask = mask & (kv_pos > idx - spec.window)
    s = jnp.where(mask[None, None, None, None, :], scores(k), NEG_INF)
    p_attn = jax.nn.softmax(jnp.concatenate([s, scores(k_new)], axis=-1), axis=-1)
    out = (jnp.einsum("bhgqk,bhkd->bhgqd", p_attn[..., :L].astype(v.dtype), v,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bhgqk,bhkd->bhgqd", p_attn[..., L:].astype(v_new.dtype), v_new,
                        preferred_element_type=jnp.float32))
    return out.reshape(B, Hq, 1, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA — DeepSeek multi-head latent attention
# ---------------------------------------------------------------------------

def mla_fullseq(p: Pytree, x: jax.Array, *, cfg: ModelConfig, spec: LayerSpec) -> jax.Array:
    m = cfg.mla
    B, T, _ = x.shape
    nope, rpe, vdim = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    # Queries through the low-rank path.
    cq = rms_norm(jnp.einsum("btd,dr->btr", x, p["q_down"]), p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("btr,rhk->bhtk", cq, p["q_up"])  # (B, H, T, nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    # Compressed KV cache + decoupled rope key.
    ckv_full = jnp.einsum("btd,dr->btr", x, p["kv_down"])
    ckv, k_rope = ckv_full[..., : m.kv_lora_rank], ckv_full[..., m.kv_lora_rank :]
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    kv = jnp.einsum("btr,rhk->bhtk", ckv, p["kv_up"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    pos = jnp.arange(T, dtype=jnp.int32)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    k_rope = apply_rope(k_rope[:, None], pos, cfg.rope_theta)  # (B, 1, T, rpe)
    k_rope_b = jnp.broadcast_to(k_rope, (B, cfg.n_heads, T, rpe))
    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_cat = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    scale = 1.0 / math.sqrt(nope + rpe)
    out = _attention_remat(q_cat, k_cat, v, window=spec.window, prefix_len=0, scale=scale)
    return jnp.einsum("bhtk,hkd->btd", out, p["wo"])


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> Pytree:
    m = cfg.mla
    dtype = dtype or jnp.dtype(cfg.dtype)
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
    }


def mla_prefill_cache(p, x, *, cfg: ModelConfig, cache: Pytree) -> Pytree:
    m = cfg.mla
    T = x.shape[1]
    ckv_full = jnp.einsum("btd,dr->btr", x, p["kv_down"])
    ckv = rms_norm(ckv_full[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(
        ckv_full[..., m.kv_lora_rank :][:, None], jnp.arange(T, dtype=jnp.int32), cfg.rope_theta
    )[:, 0]
    L = cache["ckv"].shape[1]
    pad = L - T
    ckv = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0)))
    k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    return {"ckv": ckv.astype(cache["ckv"].dtype), "krope": k_rope.astype(cache["krope"].dtype)}


def mla_decode(
    p: Pytree, x: jax.Array, cache: Pytree, idx: jax.Array, *, cfg: ModelConfig
) -> Tuple[jax.Array, Pytree]:
    """Absorbed-matrix MLA decode: attend directly in the compressed space."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    nope, rpe, vdim = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    cq = rms_norm(jnp.einsum("btd,dr->btr", x, p["q_down"]), p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("btr,rhk->bhtk", cq, p["q_up"])[:, :, 0]  # (B, H, nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    posv = jnp.full((1,), idx, jnp.int32)
    q_rope = apply_rope(q_rope[:, :, None], posv, cfg.rope_theta)[:, :, 0]
    # New cache entry.
    ckv_full = jnp.einsum("btd,dr->btr", x, p["kv_down"])[:, 0]
    ckv_new = rms_norm(ckv_full[: , : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    krope_new = apply_rope(ckv_full[:, m.kv_lora_rank :][:, None, None], posv, cfg.rope_theta)[:, 0, 0]
    cache = {
        "ckv": cache["ckv"].at[:, idx].set(ckv_new.astype(cache["ckv"].dtype)),
        "krope": cache["krope"].at[:, idx].set(krope_new.astype(cache["krope"].dtype)),
    }
    # Absorb kv_up(K) into the query: q_c = q_nope @ W_uk  -> compressed space.
    w_uk = p["kv_up"][..., :nope]  # (r, H, nope)
    q_c = jnp.einsum("bhk,rhk->bhr", q_nope, w_uk)  # (B, H, r)
    s = jnp.einsum("bhr,btr->bht", q_c.astype(cache["ckv"].dtype), cache["ckv"],
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhk,btk->bht", q_rope.astype(cache["krope"].dtype),
                       cache["krope"], preferred_element_type=jnp.float32)
    s = s / math.sqrt(nope + rpe)
    L = cache["ckv"].shape[1]
    mask = jnp.arange(L) <= idx
    s = jnp.where(mask[None, None], s, NEG_INF)
    attn = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bht,btr->bhr", attn.astype(cache["ckv"].dtype), cache["ckv"],
                     preferred_element_type=jnp.float32)  # (B, H, r)
    w_uv = p["kv_up"][..., nope:]  # (r, H, v)
    out = jnp.einsum("bhr,rhv->bhv", ctx.astype(x.dtype), w_uv)
    y = jnp.einsum("bhv,hvd->bd", out, p["wo"])[:, None]
    return y.astype(x.dtype), cache


# ---------------------------------------------------------------------------
# MoE — capacity-based sort/scatter dispatch (no O(T·E·C) one-hot einsums)
# ---------------------------------------------------------------------------

def moe_forward(
    p: Pytree, x: jax.Array, *, cfg: ModelConfig, deterministic: bool = True,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss).  x: (B, T, d).

    Dispatch is PER BATCH ROW (GShard "groups" = batch rows): sort/scatter
    stays local to each data shard, expert tensors are sharded on the model
    axis, and the only cross-device traffic is the expert-dim resharding of
    the (B, E, C, d) buffers — measured ~40x less collective volume than a
    global-token sort on deepseek-v3 prefill (EXPERIMENTS.md §Perf).
    """
    from repro.distributed import constraints as DC

    m = cfg.moe
    if m.dispatch == "global":
        return moe_forward_global(p, x, cfg=cfg, deterministic=deterministic, rng=rng)
    B, T, d = x.shape
    E, K = m.n_experts, m.top_k
    logits = jnp.einsum(
        "btd,de->bte", x.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    if m.router_noise and not deterministic and rng is not None:
        logits = logits + jax.random.normal(rng, logits.shape) * m.router_noise
    if m.router_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        gate_w, gate_i = jax.lax.top_k(scores, K)
        gate_w = gate_w / jnp.maximum(jnp.sum(gate_w, -1, keepdims=True), 1e-9)
        gate_w = gate_w * m.routed_scale
        probs = scores / jnp.maximum(jnp.sum(scores, -1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_i = jax.lax.top_k(probs, K)
        gate_w = gate_w / jnp.maximum(jnp.sum(gate_w, -1, keepdims=True), 1e-9)

    # Load-balancing auxiliary loss (Switch-style), over the global batch.
    # NOTE: mean over explicit axes (no reshape) — reshaping the sharded
    # (B, T, E) probs forced a 24 GB all-gather per layer (measured).
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.zeros((E,), jnp.float32).at[gate_i.reshape(-1)].add(1.0) / (B * T * K)
    aux = E * jnp.sum(me * ce) * m.aux_loss_weight

    capacity = int(math.ceil(m.capacity_factor * T * K / E))
    capacity = max(capacity, 4)

    def dispatch_row(xr, er, wr):
        """xr: (T, d); er/wr: (T, K).

        Returns the (E, C, d) expert buffer plus an INVERTED slot map
        (dst, wslot): destination token and gate weight per expert slot.
        The combine then scatter-adds from the expert-sharded domain, so
        only the (T, d) output crosses shards — not the (T*K, d) gather
        (8x less all-reduce volume at top-8; EXPERIMENTS.md §Perf cell B).
        """
        e_flat = er.reshape(-1)
        w_flat = wr.reshape(-1).astype(jnp.float32)
        tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
        order = jnp.argsort(e_flat, stable=True)
        e_s, w_s, tok_s = e_flat[order], w_flat[order], tok[order]
        first = jnp.searchsorted(e_s, e_s, side="left")
        pos = jnp.arange(e_s.shape[0], dtype=jnp.int32) - first.astype(jnp.int32)
        keep = pos < capacity
        slot = jnp.where(keep, e_s * capacity + pos, E * capacity)
        buf = jnp.zeros((E * capacity + 1, d), xr.dtype)
        buf = buf.at[slot].set(xr[tok_s], mode="drop")
        # Inverted map: expert slot -> (destination token, gate weight).
        dst = jnp.full((E * capacity + 1,), T, jnp.int32).at[slot].set(
            jnp.where(keep, tok_s, T), mode="drop"
        )
        wslot = jnp.zeros((E * capacity + 1,), jnp.float32).at[slot].set(
            jnp.where(keep, w_s, 0.0), mode="drop"
        )
        return buf[:-1].reshape(E, capacity, d), (
            dst[:-1].reshape(E, capacity),
            wslot[:-1].reshape(E, capacity),
        )

    buf, (dst, wslot) = jax.vmap(dispatch_row)(x, gate_i, gate_w)  # (B, E, C, d)
    buf = DC.constrain(buf, ("batch", "experts", None, None))

    h = jnp.einsum("becd,edgf->becgf", buf, p["wi"])
    act = jax.nn.silu(h[..., 0, :].astype(jnp.float32)).astype(x.dtype) * h[..., 1, :]
    eo = jnp.einsum("becf,efd->becd", act, p["wo"])            # (B, E, C, d)
    eo = DC.constrain(eo, ("batch", "experts", None, None))

    def combine_row(eor, dstr, wr):
        contrib = eor.astype(jnp.float32) * wr[..., None]      # (E, C, d)
        y = jnp.zeros((T + 1, d), jnp.float32)
        y = y.at[dstr.reshape(-1)].add(contrib.reshape(E * capacity, d), mode="drop")
        return y[:T]

    y = jax.vmap(combine_row)(eo, dst, wslot).astype(x.dtype)   # (B, T, d)

    if m.n_shared_experts:
        y = y + swiglu(x, p["shared"]["wi"], p["shared"]["wo"])
    return y, aux


def moe_forward_global(
    p: Pytree, x: jax.Array, *, cfg: ModelConfig, deterministic: bool = True,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Global-token-sort dispatch (the naive baseline, kept selectable via
    ``MoEConfig.dispatch='global'``): sorts ALL tokens across the batch, which
    SPMD cannot shard — every device gathers every token.  Retained so the
    §Perf before/after and the Fig. 6-style injection sweep can measure it."""
    m = cfg.moe
    B, T, d = x.shape
    n_tok = B * T
    xf = x.reshape(n_tok, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"].astype(jnp.float32))
    if m.router_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        gate_w, gate_i = jax.lax.top_k(scores, m.top_k)
        gate_w = gate_w / jnp.maximum(jnp.sum(gate_w, -1, keepdims=True), 1e-9)
        gate_w = gate_w * m.routed_scale
        probs = scores / jnp.maximum(jnp.sum(scores, -1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_i = jax.lax.top_k(probs, m.top_k)
        gate_w = gate_w / jnp.maximum(jnp.sum(gate_w, -1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((m.n_experts,), jnp.float32).at[gate_i.reshape(-1)].add(1.0) / (
        n_tok * m.top_k
    )
    aux = m.n_experts * jnp.sum(me * ce) * m.aux_loss_weight
    capacity = max(int(math.ceil(m.capacity_factor * n_tok * m.top_k / m.n_experts)), 4)
    e_flat = gate_i.reshape(-1)
    w_flat = gate_w.reshape(-1).astype(jnp.float32)
    tok_flat = jnp.repeat(jnp.arange(n_tok, dtype=jnp.int32), m.top_k)
    order = jnp.argsort(e_flat, stable=True)
    e_s, w_s, tok_s = e_flat[order], w_flat[order], tok_flat[order]
    first = jnp.searchsorted(e_s, e_s, side="left")
    pos = jnp.arange(e_s.shape[0], dtype=jnp.int32) - first.astype(jnp.int32)
    keep = pos < capacity
    slot = jnp.where(keep, e_s * capacity + pos, m.n_experts * capacity)
    buf = jnp.zeros((m.n_experts * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[tok_s], mode="drop")
    eb = buf[:-1].reshape(m.n_experts, capacity, d)
    h = jnp.einsum("ecd,edgf->ecgf", eb, p["wi"])
    act = jax.nn.silu(h[..., 0, :].astype(jnp.float32)).astype(x.dtype) * h[..., 1, :]
    eo = jnp.einsum("ecf,efd->ecd", act, p["wo"])
    out_rows = eo.reshape(m.n_experts * capacity, d)
    gathered = jnp.where(keep[:, None], out_rows[jnp.minimum(slot, out_rows.shape[0] - 1)], 0)
    y = jnp.zeros((n_tok, d), jnp.float32)
    y = y.at[tok_s].add(gathered.astype(jnp.float32) * w_s[:, None])
    y = y.astype(x.dtype)
    if m.n_shared_experts:
        y = y + swiglu(xf[None], p["shared"]["wi"], p["shared"]["wo"])[0]
    return y.reshape(B, T, d), aux


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma) recurrent block
# ---------------------------------------------------------------------------

def _causal_conv_fullseq(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: (B, T, C); w: (W, C); b: (C,). Depthwise causal conv via shifts."""
    W = w.shape[0]
    T = x.shape[1]
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(W):
        # Tap i sees x[t - (W-1-i)]: left-pad by W-1-i, keep first T steps.
        shifted = jnp.pad(x, ((0, 0), (W - 1 - i, 0), (0, 0)))[:, :T]
        out = out + shifted.astype(jnp.float32) * w[i].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def _block_diag_gate(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: (B, T, H, bw); w: (H, bw, bw); b: (H, bw) -> sigmoid gate."""
    g = jnp.einsum("bthi,hij->bthj", x.astype(jnp.float32), w.astype(jnp.float32))
    return jax.nn.sigmoid(g + b.astype(jnp.float32))


def rglru_fullseq(p: Pytree, x: jax.Array, *, cfg: ModelConfig) -> jax.Array:
    r = cfg.rglru
    B, T, d = x.shape
    w = r.lru_width or d
    H = cfg.n_heads
    bw = w // H
    xb = jnp.einsum("btd,dw->btw", x, p["wx"])
    yb = jnp.einsum("btd,dw->btw", x, p["wy"])
    xc = _causal_conv_fullseq(xb, p["conv_w"], p["conv_b"])
    xh = xc.reshape(B, T, H, bw)
    gi = _block_diag_gate(xh, p["gate_w"][0], p["gate_b"][0])  # input gate
    gr = _block_diag_gate(xh, p["gate_w"][1], p["gate_b"][1])  # recurrence gate
    log_a = -8.0 * gr * jax.nn.softplus(p["a_param"].astype(jnp.float32)).reshape(H, bw)
    a = jnp.exp(log_a)
    gated_x = xh.astype(jnp.float32) * gi
    multiplier = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    inp = gated_x * multiplier

    # h_t = a_t * h_{t-1} + inp_t  — associative scan over T.
    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    a_seq = a.reshape(B, T, w)
    b_seq = inp.reshape(B, T, w)
    _, h = jax.lax.associative_scan(combine, (a_seq, b_seq), axis=1)
    h = h.reshape(B, T, w).astype(x.dtype)
    out = h * jax.nn.gelu(yb.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("btw,wd->btd", out, p["wo"])


def rglru_init_state(cfg: ModelConfig, batch: int, dtype=None) -> Pytree:
    r = cfg.rglru
    dtype = dtype or jnp.dtype(cfg.dtype)
    w = r.lru_width or cfg.d_model
    return {
        "h": jnp.zeros((batch, w), jnp.float32),
        "conv": jnp.zeros((batch, r.conv_width - 1, w), dtype),
    }


def rglru_decode(
    p: Pytree, x: jax.Array, state: Pytree, *, cfg: ModelConfig
) -> Tuple[jax.Array, Pytree]:
    r = cfg.rglru
    B = x.shape[0]
    d = cfg.d_model
    w = r.lru_width or d
    H = cfg.n_heads
    bw = w // H
    xb = jnp.einsum("btd,dw->btw", x, p["wx"])[:, 0]  # (B, w)
    yb = jnp.einsum("btd,dw->btw", x, p["wy"])[:, 0]
    hist = jnp.concatenate([state["conv"], xb[:, None].astype(state["conv"].dtype)], axis=1)
    xc = (
        jnp.sum(hist.astype(jnp.float32) * p["conv_w"].astype(jnp.float32), axis=1)
        + p["conv_b"].astype(jnp.float32)
    ).astype(x.dtype)
    xh = xc.reshape(B, 1, H, bw)
    gi = _block_diag_gate(xh, p["gate_w"][0], p["gate_b"][0])[:, 0]
    gr = _block_diag_gate(xh, p["gate_w"][1], p["gate_b"][1])[:, 0]
    log_a = -8.0 * gr * jax.nn.softplus(p["a_param"].astype(jnp.float32)).reshape(H, bw)
    a = jnp.exp(log_a).reshape(B, w)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)).reshape(B, w)
    h_new = a * state["h"] + xc.astype(jnp.float32).reshape(B, w) * gi.reshape(B, w) * mult
    out = h_new.astype(x.dtype) * jax.nn.gelu(yb.astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("bw,wd->bd", out, p["wo"])[:, None]
    new_state = {"h": h_new, "conv": hist[:, 1:]}
    return y, new_state


# ---------------------------------------------------------------------------
# Mamba-2 SSD block
# ---------------------------------------------------------------------------

def _ssd_project(p, x, cfg):
    s = cfg.ssd
    z = jnp.einsum("btd,dhk->bthk", x, p["wz"])
    xi = jnp.einsum("btd,dhk->bthk", x, p["wx"])
    bc = jnp.einsum("btd,dcgn->btcgn", x, p["wBC"])
    dt = jnp.einsum("btd,dh->bth", x, p["wdt"])
    return z, xi, bc, dt


def _ssd_conv_fullseq(xi, bc, p, cfg):
    s = cfg.ssd
    B, T = xi.shape[:2]
    nh, hd = xi.shape[2], xi.shape[3]
    xi_f = xi.reshape(B, T, nh * hd)
    conv_wx = p["conv_x"].reshape(s.conv_width, nh * hd)
    xi_c = _causal_conv_fullseq(xi_f, conv_wx, p["conv_b_x"].reshape(-1))
    xi_c = jax.nn.silu(xi_c.astype(jnp.float32)).astype(xi.dtype).reshape(B, T, nh, hd)
    bc_f = bc.reshape(B, T, -1)
    conv_wbc = p["conv_BC"].reshape(s.conv_width, -1)
    bc_c = _causal_conv_fullseq(bc_f, conv_wbc, p["conv_b_BC"].reshape(-1))
    bc_c = jax.nn.silu(bc_c.astype(jnp.float32)).astype(bc.dtype).reshape(bc.shape)
    return xi_c, bc_c


def ssd_fullseq(p: Pytree, x: jax.Array, *, cfg: ModelConfig) -> jax.Array:
    """Chunked SSD (Mamba-2 alg. 1), pure jnp (oracle for the Pallas kernel)."""
    s = cfg.ssd
    B, T, d = x.shape
    z, xi, bc, dt = _ssd_project(p, x, cfg)
    xi, bc = _ssd_conv_fullseq(xi, bc, p, cfg)
    Bm, Cm = bc[:, :, 0], bc[:, :, 1]  # (B, T, G, N)
    nh = xi.shape[2]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # (B,T,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,) negative
    y = ssd_scan_ref(xi, dt, A, Bm, Cm, chunk=s.chunk_size)
    y = y + xi * p["D"].astype(xi.dtype)[None, None, :, None]
    # Gated RMSNorm (mamba2): norm(y * silu(z)).
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + 1e-6) * p["gnorm"].astype(jnp.float32)
    g = g.astype(x.dtype)
    return jnp.einsum("bthk,hkd->btd", g, p["wo"])


def ssd_scan_ref(xi, dt, A, Bm, Cm, *, chunk: int = 256) -> jax.Array:
    """Reference chunked SSD scan.

    xi: (B,T,H,P) values; dt: (B,T,H) f32; A: (H,) f32 negative;
    Bm, Cm: (B,T,G,N).  Groups broadcast over heads (H % G == 0).
    Returns (B,T,H,P) in xi.dtype.
    """
    Bsz, T, H, P = xi.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    c = min(chunk, T)
    pad = (-T) % c
    if pad:
        xi = jnp.pad(xi, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Tp = T + pad
    nc = Tp // c
    xi_c = xi.reshape(Bsz, nc, c, H, P)
    dt_c = dt.reshape(Bsz, nc, c, H)
    B_c = Bm.reshape(Bsz, nc, c, G, N)
    C_c = Cm.reshape(Bsz, nc, c, G, N)
    # Broadcast groups to heads.
    B_h = jnp.repeat(B_c, rep, axis=3)  # (B,nc,c,H,N)
    C_h = jnp.repeat(C_c, rep, axis=3)

    dA = dt_c * A[None, None, None, :]               # (B,nc,c,H)  log-decay
    cum = jnp.cumsum(dA, axis=2)                     # within-chunk cumulative
    # Intra-chunk (lower-triangular "attention-like" matrix).
    # L[i,j] = exp(cum[i]-cum[j]) for i >= j.
    li = cum[:, :, :, None, :]                       # (B,nc,c,1,H)
    lj = cum[:, :, None, :, :]                       # (B,nc,1,c,H)
    decay = jnp.exp(jnp.minimum(li - lj, 0.0))       # clip avoids inf on upper tri
    tri = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.where(tri[None, None, :, :, None], decay, 0.0)
    scores = jnp.einsum("bkihn,bkjhn->bkijh", C_h.astype(jnp.float32), B_h.astype(jnp.float32))
    scores = scores * decay
    xdt = xi_c.astype(jnp.float32) * dt_c[..., None]  # (B,nc,c,H,P)
    y_intra = jnp.einsum("bkijh,bkjhp->bkihp", scores, xdt)

    # Chunk summary states: S_k = sum_j exp(cum_end - cum_j) dt_j B_j x_j^T.
    seg = jnp.exp(cum[:, :, -1:, :] - cum)           # (B,nc,c,H)
    S_chunk = jnp.einsum("bkjhn,bkjhp->bkhnp", (B_h.astype(jnp.float32) * (seg * dt_c)[..., None]),
                         xi_c.astype(jnp.float32))
    chunk_decay = jnp.exp(cum[:, :, -1, :])          # (B,nc,H)

    def step(Sprev, inp):
        Sc, dk = inp
        Snew = Sprev * dk[..., None, None] + Sc
        return Snew, Sprev

    S0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
    _, S_before = jax.lax.scan(
        step, S0, (S_chunk.swapaxes(0, 1), chunk_decay.swapaxes(0, 1))
    )
    S_before = S_before.swapaxes(0, 1)               # (B,nc,H,N,P) state entering chunk
    inter_decay = jnp.exp(cum)                       # decay from chunk start to i
    y_inter = jnp.einsum("bkihn,bkhnp->bkihp", C_h.astype(jnp.float32) * inter_decay[..., None],
                         S_before)
    y = (y_intra + y_inter).reshape(Bsz, Tp, H, P)[:, :T]
    return y.astype(xi.dtype)


def ssd_init_state(cfg: ModelConfig, batch: int, dtype=None) -> Pytree:
    s = cfg.ssd
    dtype = dtype or jnp.dtype(cfg.dtype)
    di = s.d_inner(cfg.d_model)
    nh = di // s.head_dim
    return {
        "S": jnp.zeros((batch, nh, s.d_state, s.head_dim), jnp.float32),
        "conv_x": jnp.zeros((batch, s.conv_width - 1, nh, s.head_dim), dtype),
        "conv_BC": jnp.zeros((batch, s.conv_width - 1, 2, s.n_groups, s.d_state), dtype),
    }


def ssd_decode(
    p: Pytree, x: jax.Array, state: Pytree, *, cfg: ModelConfig
) -> Tuple[jax.Array, Pytree]:
    s = cfg.ssd
    B = x.shape[0]
    z, xi, bc, dt = _ssd_project(p, x, cfg)
    z, xi, bc, dt = z[:, 0], xi[:, 0], bc[:, 0], dt[:, 0]
    # Conv state update.
    hist_x = jnp.concatenate([state["conv_x"], xi[:, None].astype(state["conv_x"].dtype)], axis=1)
    xi_c = jnp.sum(hist_x.astype(jnp.float32) * p["conv_x"].astype(jnp.float32)[None], axis=1)
    xi_c = jax.nn.silu(xi_c + p["conv_b_x"].astype(jnp.float32)[None]).astype(x.dtype)
    hist_bc = jnp.concatenate([state["conv_BC"], bc[:, None].astype(state["conv_BC"].dtype)], axis=1)
    bc_c = jnp.sum(hist_bc.astype(jnp.float32) * p["conv_BC"].astype(jnp.float32)[None], axis=1)
    bc_c = jax.nn.silu(bc_c + p["conv_b_BC"].astype(jnp.float32)[None]).astype(x.dtype)
    Bv, Cv = bc_c[:, 0], bc_c[:, 1]                   # (B, G, N)
    H = xi_c.shape[1]
    rep = H // s.n_groups
    B_h = jnp.repeat(Bv, rep, axis=1)                 # (B, H, N)
    C_h = jnp.repeat(Cv, rep, axis=1)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dtv * A[None])                       # (B,H)
    S = state["S"] * dA[..., None, None] + jnp.einsum(
        "bhn,bhp->bhnp", B_h.astype(jnp.float32) * dtv[..., None], xi_c.astype(jnp.float32)
    )
    y = jnp.einsum("bhn,bhnp->bhp", C_h.astype(jnp.float32), S)
    y = y + xi_c.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, :, None]
    g = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = (g * jax.lax.rsqrt(var + 1e-6) * p["gnorm"].astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bhk,hkd->bd", g, p["wo"])[:, None]
    new_state = {"S": S, "conv_x": hist_x[:, 1:], "conv_BC": hist_bc[:, 1:]}
    return out, new_state
