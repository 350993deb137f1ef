"""Unit tests for attention variants, the SSD scan, and optimizer numerics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.models import layers as L
from repro.train import optimizer as O


def naive_attention(q, k, v, *, causal=True, window=None, prefix_len=0, scale=None):
    B, Hq, T, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    scale = scale or 1.0 / np.sqrt(D)
    qr = q.reshape(B, Hkv, G, T, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qr, k).astype(jnp.float32) * scale
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = jnp.ones((T, T), bool)
    if causal:
        cm = j <= i
        if prefix_len:
            cm = cm | ((i < prefix_len) & (j < prefix_len))
        mask = mask & cm
    if window is not None:
        mask = mask & (j > i - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
    return out.reshape(B, Hq, T, -1).astype(q.dtype)


def _qkv(T=192, B=2, Hq=4, Hkv=2, D=16, Dv=None, seed=0):
    rng = np.random.default_rng(seed)
    Dv = Dv or D
    q = jnp.asarray(rng.standard_normal((B, Hq, T, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, T, Dv)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("window,prefix", [(None, 0), (37, 0), (None, 70), (64, 0)])
@pytest.mark.parametrize("impl", [L.chunked_attention, L.banded_attention])
def test_attention_matches_naive(impl, window, prefix):
    q, k, v = _qkv()
    ref = naive_attention(q, k, v, window=window, prefix_len=prefix)
    got = impl(q, k, v, window=window, prefix_len=prefix, chunk_q=64, chunk_k=64)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_attention_mla_shaped_values():
    # MLA: v dim differs from qk dim.
    q, k, v = _qkv(D=24, Dv=16)
    ref = naive_attention(q, k, v)
    for impl in (L.chunked_attention, L.banded_attention):
        got = impl(q, k, v, chunk_q=64, chunk_k=64)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


@settings(max_examples=12, deadline=None)
@given(
    T=st.integers(min_value=8, max_value=257),
    chunk=st.sampled_from([16, 64, 128]),
    window=st.sampled_from([None, 16, 100]),
    seed=st.integers(min_value=0, max_value=5),
)
def test_banded_attention_property(T, chunk, window, seed):
    """Property: banded == naive for any (T, chunk, window) combination."""
    q, k, v = _qkv(T=T, seed=seed)
    ref = naive_attention(q, k, v, window=window)
    got = L.banded_attention(q, k, v, window=window, chunk_q=chunk, chunk_k=chunk)
    np.testing.assert_allclose(got, ref, rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# One-token decode attention over a stacked cache
# ---------------------------------------------------------------------------

def _write_attend(p, x, cache, idx, *, cfg, spec):
    """The slice-write-attend path's layer: the token written into the
    layer's cache, then attended over with everything at or before it."""
    theta = L._rope_theta(cfg, spec)
    q = jnp.einsum("btd,dhk->bhtk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bhtk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bhtk", x, p["wv"])
    if cfg.use_qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    posv = jnp.full((1,), idx, jnp.int32)
    q = L.apply_rope(q, posv, theta)
    k = L.apply_rope(k, posv, theta)
    B, Hkv, Lc, D = cache["k"].shape
    ring = spec.window is not None and Lc == spec.window
    slot = idx % Lc if ring else idx
    cache = {n: cache[n].at[:, :, slot].set(t[:, :, 0].astype(cache[n].dtype))
             for n, t in (("k", k), ("v", v))}
    qr = q.reshape(B, Hkv, q.shape[1] // Hkv, 1, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qr, cache["k"].astype(qr.dtype),
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    slots = jnp.arange(Lc)
    kv_pos = idx - jnp.mod(idx - slots, Lc) if ring else slots
    mask = (kv_pos >= 0) & (kv_pos <= idx)
    if spec.window is not None:
        mask = mask & (kv_pos > idx - spec.window)
    pr = jax.nn.softmax(jnp.where(mask, s, L.NEG_INF), axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", pr.astype(cache["v"].dtype), cache["v"],
                     preferred_element_type=jnp.float32).reshape(q.shape).astype(q.dtype)
    return jnp.einsum("bhtk,hkd->btd", out, p["wo"]), cache


def _slice_write_attend(p, x, cache, idx, *, cfg, spec, layer=None):
    """``attn_decode`` as it was: a stacked layer's whole cache sliced out,
    written, attended over, and written back whole."""
    if layer is None:
        return _write_attend(p, x, cache, idx, cfg=cfg, spec=spec)
    one = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False) for n, a in cache.items()}
    out, one = _write_attend(p, x, one, idx, cfg=cfg, spec=spec)
    return out, {n: jax.lax.dynamic_update_index_in_dim(cache[n], one[n], layer, 0)
                 for n in cache}


def _attn_layer(window, n_layers=3, B=2, Hq=4, Hkv=2, D=16, d=32, max_len=20, seed=0):
    """A float32 attention layer, a stacked bfloat16 cache of random values
    (so that every slot the mask lets through, or not, is visible) and one
    token's input."""
    from repro.models.config import ATTN, LayerSpec, ModelConfig

    cfg = ModelConfig(name="t", n_layers=n_layers, d_model=d, n_heads=Hq, n_kv_heads=Hkv,
                      head_dim=D, d_ff=64, vocab_size=64, block_pattern=(LayerSpec(ATTN),),
                      use_qk_norm=True, dtype="float32")
    spec = LayerSpec(ATTN, window=window)
    rng = np.random.default_rng(seed)

    def draw(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[0]), dtype)

    p = {"wq": draw(d, Hq, D), "wk": draw(d, Hkv, D), "wv": draw(d, Hkv, D),
         "wo": draw(Hq, D, d), "q_norm": 1 + draw(D) / 4, "k_norm": 1 + draw(D) / 4}
    Lc = min(max_len, window) if window else max_len
    cache = {n: draw(n_layers, B, Hkv, Lc, D, dtype=jnp.bfloat16) * 4 for n in ("k", "v")}
    return cfg, spec, p, cache, draw(B, 1, d) * 4


@pytest.mark.parametrize("window,idx", [
    (None, 0), (None, 7), (None, 19),        # dense cache
    (24, 5), (24, 19),                       # a window wider than the cache: dense
    (8, 3), (8, 7), (8, 8), (8, 13),         # ring: before, at and after it wraps
])
def test_attn_decode_in_place_matches_slice_write_attend(window, idx):
    cfg, spec, p, cache, x = _attn_layer(window)
    idx = jnp.asarray(idx, jnp.int32)
    for layer in range(3):
        layer = jnp.asarray(layer, jnp.int32)
        got, got_cache = L.attn_decode(p, x, cache, idx, cfg=cfg, spec=spec, layer=layer)
        ref, ref_cache = _slice_write_attend(p, x, cache, idx, cfg=cfg, spec=spec, layer=layer)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        for n in ("k", "v"):
            assert got_cache[n].dtype == ref_cache[n].dtype
            np.testing.assert_array_equal(np.asarray(got_cache[n], np.float32),
                                          np.asarray(ref_cache[n], np.float32))


@pytest.mark.parametrize("arch", ["qwen3-32b", "gemma3-4b"])
def test_decode_step_matches_slice_write_attend(arch, monkeypatch):
    """Whole decode steps, from a prefill through a wrap of gemma3's 8-slot
    rings (with its unstacked remainder layers): logits and every layer's
    cache as the slice-write-attend path gives them."""
    import dataclasses

    from repro import configs
    from repro.models import params as P
    from repro.models import transformer as T

    cfg = dataclasses.replace(configs.get_smoke(arch), dtype="float32")
    params = P.init_params(cfg, jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 256, (2, 5)), jnp.int32)
    _, state = T.prefill(params, cfg, {"tokens": toks}, max_len=20, remat="none")

    def steps(state):
        step = jax.jit(lambda s, t, i: T.decode_step(params, cfg, s, {"tokens": t}, i))
        cur, out = toks[:, -1:], []
        for i in range(5, 15):
            logits, state = step(state, cur, jnp.asarray(i, jnp.int32))
            cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append((logits, state))
        return out

    got = steps(state)
    monkeypatch.setattr(L, "attn_decode", _slice_write_attend)
    ref = steps(state)
    for (g_logits, g_state), (r_logits, r_state) in zip(got, ref):
        np.testing.assert_allclose(g_logits, r_logits, rtol=1e-5, atol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
                     g_state, r_state)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

def ssd_sequential(xi, dt, A, Bm, Cm):
    """O(T) sequential reference for the chunked SSD scan."""
    Bsz, T, H, P = xi.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    B_h = np.repeat(np.asarray(Bm, np.float64), rep, axis=2)
    C_h = np.repeat(np.asarray(Cm, np.float64), rep, axis=2)
    x = np.asarray(xi, np.float64)
    d = np.asarray(dt, np.float64)
    a = np.asarray(A, np.float64)
    S = np.zeros((Bsz, H, N, P))
    ys = np.zeros_like(x)
    for t in range(T):
        dA = np.exp(d[:, t] * a[None, :])  # (B,H)
        S = S * dA[..., None, None] + np.einsum(
            "bhn,bhp->bhnp", B_h[:, t] * d[:, t][..., None], x[:, t]
        )
        ys[:, t] = np.einsum("bhn,bhnp->bhp", C_h[:, t], S)
    return ys


@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_ssd_scan_matches_sequential(chunk):
    rng = np.random.default_rng(0)
    B, T, H, P, G, N = 2, 48, 4, 8, 2, 16
    xi = jnp.asarray(rng.standard_normal((B, T, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, T, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, T, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, T, G, N)), jnp.float32)
    ref = ssd_sequential(xi, dt, A, Bm, Cm)
    got = L.ssd_scan_ref(xi, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Optimizer numerics
# ---------------------------------------------------------------------------

def test_q8_roundtrip_error():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 1000)) * 0.01, jnp.float32)
    enc = O.q8_encode(x)
    dec = O.q8_decode(enc, x.shape)
    err = np.max(np.abs(np.asarray(dec - x)))
    scale = np.max(np.abs(np.asarray(x)))
    assert err <= scale / 127.0 * 1.01


def test_q8_adam_tracks_f32_adam():
    """q8-state AdamW must stay close to f32-state AdamW over steps."""
    rng = np.random.default_rng(2)
    p0 = {"w": jnp.asarray(rng.standard_normal((64, 64)) * 0.1, jnp.float32)}
    cfg32 = O.OptConfig(lr=1e-2, warmup_steps=0, schedule="constant")
    cfg8 = O.OptConfig(lr=1e-2, warmup_steps=0, schedule="constant", state_dtype="q8")
    s32, s8 = O.init(p0, cfg32), O.init(p0, cfg8)
    pa, pb = p0, p0
    for i in range(10):
        g = {"w": jnp.asarray(rng.standard_normal((64, 64)) * 0.05, jnp.float32)}
        pa, s32, _ = O.apply(g, pa, s32, cfg32)
        pb, s8, _ = O.apply(g, pb, s8, cfg8)
    diff = float(jnp.max(jnp.abs(pa["w"] - pb["w"])))
    denom = float(jnp.max(jnp.abs(pa["w"] - p0["w"])))
    assert diff < 0.1 * denom, (diff, denom)


def test_stochastic_rounding_unbiased():
    x = jnp.full((20000,), 1.0 + 2.0**-9, jnp.float32)  # between bf16 grid pts
    key = jax.random.key(0)
    r = O.stochastic_round_bf16(x, key).astype(jnp.float32)
    vals = np.unique(np.asarray(r))
    assert len(vals) == 2  # rounds both directions
    mean = float(jnp.mean(r))
    assert abs(mean - float(x[0])) < 2e-4  # unbiased in expectation


def test_learning_rate_schedule():
    cfg = O.OptConfig(lr=1.0, warmup_steps=10, total_steps=110, schedule="cosine")
    assert float(O.learning_rate(cfg, jnp.asarray(5))) == pytest.approx(0.5)
    assert float(O.learning_rate(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(O.learning_rate(cfg, jnp.asarray(110))) == pytest.approx(0.0, abs=1e-6)
