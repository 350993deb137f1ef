"""Unified decoder: block dispatch, scan-over-layers stacking, heads, losses.

Depth layout (see ``params.block_layout``): the repeating block pattern is
scanned ``n_full`` times (weights stacked on a leading "layers" axis), and a
possibly-partial final period is applied unrolled.  This keeps HLO size O(1)
in depth — required to compile 94-layer models against 512 devices — and is
what production JAX LLM stacks (MaxText et al.) do.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import constraints as C
from repro.models import layers as L
from repro.models import params as P
from repro.models.config import (
    ATTN,
    MLA,
    MLP_DENSE,
    MLP_MOE,
    MLP_NONE,
    RGLRU,
    SSD,
    LayerSpec,
    ModelConfig,
)

Pytree = Any

REMAT_POLICIES = {
    "none": None,  # no remat
    "full": "full",  # remat everything
    "dots": "dots",  # save matmul outputs with no batch dims
    "minimal": "minimal",  # save nothing except inputs
}


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if policy == "minimal":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.everything_saveable)
    raise ValueError(policy)


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------

def block_fullseq(
    p: Pytree,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    spec: LayerSpec,
    prefix_len: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Residual block; returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
    if spec.kind == ATTN:
        mix = L.attn_fullseq(p["attn"], h, cfg=cfg, spec=spec, prefix_len=prefix_len)
    elif spec.kind == MLA:
        mix = L.mla_fullseq(p["attn"], h, cfg=cfg, spec=spec)
    elif spec.kind == RGLRU:
        mix = L.rglru_fullseq(p["rglru"], h, cfg=cfg)
    elif spec.kind == SSD:
        mix = L.ssd_fullseq(p["ssd"], h, cfg=cfg)
    else:
        raise ValueError(spec.kind)
    x = x + mix
    if spec.mlp != MLP_NONE:
        h = L.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
        if spec.mlp == MLP_DENSE:
            y = L.swiglu(h, p["mlp"]["wi"], p["mlp"]["wo"])
        else:
            y, aux = L.moe_forward(p["moe"], h, cfg=cfg)
        x = x + y
    return x, aux


def block_init_state(
    cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int
) -> Pytree:
    if spec.kind == ATTN:
        return {"cache": L.attn_init_cache(cfg, spec, batch, max_len)}
    if spec.kind == MLA:
        return {"cache": L.mla_init_cache(cfg, batch, max_len)}
    if spec.kind == RGLRU:
        return {"state": L.rglru_init_state(cfg, batch)}
    if spec.kind == SSD:
        return {"state": L.ssd_init_state(cfg, batch)}
    raise ValueError(spec.kind)


def block_prefill(
    p: Pytree,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    spec: LayerSpec,
    max_len: int,
    prefix_len: int = 0,
) -> Tuple[jax.Array, Pytree]:
    """Full-seq forward that also returns the decode state."""
    aux_state: Pytree
    h = L.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
    if spec.kind == ATTN:
        mix = L.attn_fullseq(p["attn"], h, cfg=cfg, spec=spec, prefix_len=prefix_len)
        cache = L.attn_init_cache(cfg, spec, x.shape[0], max_len)
        cache = L.attn_prefill_cache(p["attn"], h, cfg=cfg, spec=spec, cache=cache)
        aux_state = {"cache": cache}
    elif spec.kind == MLA:
        mix = L.mla_fullseq(p["attn"], h, cfg=cfg, spec=spec)
        cache = L.mla_init_cache(cfg, x.shape[0], max_len)
        cache = L.mla_prefill_cache(p["attn"], h, cfg=cfg, cache=cache)
        aux_state = {"cache": cache}
    elif spec.kind == RGLRU:
        mix, st = _rglru_fullseq_with_state(p["rglru"], h, cfg)
        aux_state = {"state": st}
    elif spec.kind == SSD:
        mix, st = _ssd_fullseq_with_state(p["ssd"], h, cfg)
        aux_state = {"state": st}
    else:
        raise ValueError(spec.kind)
    x = x + mix
    if spec.mlp != MLP_NONE:
        h = L.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
        if spec.mlp == MLP_DENSE:
            y = L.swiglu(h, p["mlp"]["wi"], p["mlp"]["wo"])
        else:
            y, _ = L.moe_forward(p["moe"], h, cfg=cfg)
        x = x + y
    return x, aux_state


def _rglru_fullseq_with_state(p, h, cfg):
    """Full-seq RG-LRU returning final recurrent + conv state."""
    y = L.rglru_fullseq(p, h, cfg=cfg)
    # Recompute final hidden state cheaply: rerun the scan's last step values.
    # The associative scan already produced h_T inside rglru_fullseq; to avoid
    # replumbing we recompute the input branch and take the final state from a
    # second (cheap, memory-light) pass over the last conv_width tokens is NOT
    # possible for the recurrence (depends on full history), so we rerun the
    # recurrence here.  XLA CSEs the shared projections.
    r = cfg.rglru
    w = r.lru_width or cfg.d_model
    B, T, _ = h.shape
    H = cfg.n_heads
    bw = w // H
    xb = jnp.einsum("btd,dw->btw", h, p["wx"])
    xc = L._causal_conv_fullseq(xb, p["conv_w"], p["conv_b"])
    xh = xc.reshape(B, T, H, bw)
    gi = L._block_diag_gate(xh, p["gate_w"][0], p["gate_b"][0])
    gr = L._block_diag_gate(xh, p["gate_w"][1], p["gate_b"][1])
    log_a = -8.0 * gr * jax.nn.softplus(p["a_param"].astype(jnp.float32)).reshape(H, bw)
    a = jnp.exp(log_a).reshape(B, T, w)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b_seq = (xh.astype(jnp.float32) * gi * mult).reshape(B, T, w)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    af, bf = jax.lax.associative_scan(combine, (a, b_seq), axis=1)
    h_last = bf[:, -1]
    conv_state = xb[:, -(r.conv_width - 1):].astype(jnp.dtype(cfg.dtype))
    # Pad if T < conv_width-1 (tiny smoke shapes).
    need = r.conv_width - 1
    if conv_state.shape[1] < need:
        conv_state = jnp.pad(conv_state, ((0, 0), (need - conv_state.shape[1], 0), (0, 0)))
    return y, {"h": h_last, "conv": conv_state}


def _ssd_fullseq_with_state(p, h, cfg):
    s = cfg.ssd
    y = L.ssd_fullseq(p, h, cfg=cfg)
    # Final SSM state: rerun the (cheap) state recurrence over chunk summaries.
    z, xi, bc, dt = L._ssd_project(p, h, cfg)
    xi_c, bc_c = L._ssd_conv_fullseq(xi, bc, p, cfg)
    Bm = bc_c[:, :, 0]
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    Bsz, T, H, Pd = xi_c.shape
    G, N = Bm.shape[2], Bm.shape[3]
    B_h = jnp.repeat(Bm, H // G, axis=2)
    dA = dtv * A[None, None, :]
    cum = jnp.cumsum(dA, axis=1)
    seg = jnp.exp(cum[:, -1:, :] - cum)
    S = jnp.einsum(
        "bthn,bthp->bhnp",
        B_h.astype(jnp.float32) * (seg * dtv)[..., None],
        xi_c.astype(jnp.float32),
    )
    conv_x = xi[:, -(s.conv_width - 1):].astype(jnp.dtype(cfg.dtype))
    conv_BC = bc[:, -(s.conv_width - 1):].astype(jnp.dtype(cfg.dtype))
    need = s.conv_width - 1
    if conv_x.shape[1] < need:
        conv_x = jnp.pad(conv_x, ((0, 0), (need - conv_x.shape[1], 0), (0, 0), (0, 0)))
        conv_BC = jnp.pad(
            conv_BC, ((0, 0), (need - conv_BC.shape[1], 0), (0, 0), (0, 0), (0, 0))
        )
    return y, {"S": S, "conv_x": conv_x, "conv_BC": conv_BC}


def _at_layer(decode, state: Pytree, layer: jax.Array) -> Tuple[jax.Array, Pytree]:
    """``decode`` on layer ``layer`` of a stacked ``state``, sliced out, and
    its new state written back whole."""
    one = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
                       state)
    out, new = decode(one)
    return out, jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n.astype(a.dtype), layer, 0),
        state, new)


def block_decode(
    p: Pytree,
    x: jax.Array,
    state: Pytree,
    idx: jax.Array,
    *,
    cfg: ModelConfig,
    spec: LayerSpec,
    layer: jax.Array,
) -> Tuple[jax.Array, Pytree]:
    """One layer's decode step.  ``state`` is the layer kind's state stacked
    on a leading layers axis (see ``stack_decode``), ``layer`` this layer's
    index in it; returns the stacked state, updated.  An attention layer
    reads its cache where it lies and writes one token; other states are
    sliced out and written back whole."""
    h = L.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
    if spec.kind == ATTN:
        mix, cache = L.attn_decode(p["attn"], h, state["cache"], idx, cfg=cfg, spec=spec,
                                   layer=layer)
        state = {"cache": cache}
    elif spec.kind == MLA:
        mix, cache = _at_layer(lambda c: L.mla_decode(p["attn"], h, c, idx, cfg=cfg),
                               state["cache"], layer)
        state = {"cache": cache}
    elif spec.kind == RGLRU:
        mix, st = _at_layer(lambda s: L.rglru_decode(p["rglru"], h, s, cfg=cfg),
                            state["state"], layer)
        state = {"state": st}
    elif spec.kind == SSD:
        mix, st = _at_layer(lambda s: L.ssd_decode(p["ssd"], h, s, cfg=cfg),
                            state["state"], layer)
        state = {"state": st}
    else:
        raise ValueError(spec.kind)
    x = x + mix
    if spec.mlp != MLP_NONE:
        h = L.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
        if spec.mlp == MLP_DENSE:
            y = L.swiglu(h, p["mlp"]["wi"], p["mlp"]["wo"])
        else:
            y, _ = L.moe_forward(p["moe"], h, cfg=cfg)
        x = x + y
    return x, state


# ---------------------------------------------------------------------------
# Stack (scan over periods)
# ---------------------------------------------------------------------------

def stack_fullseq(
    blocks: Pytree,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    prefix_len: int = 0,
    remat: str = "dots",
) -> Tuple[jax.Array, jax.Array]:
    n_full, rem = P.block_layout(cfg)
    aux = jnp.zeros((), jnp.float32)
    if n_full:
        def period_body(carry, xs):
            x, aux = carry
            x = C.constrain(x, ("batch", None, None))
            for i, spec in enumerate(cfg.block_pattern):
                x, a = block_fullseq(xs[f"p{i}"], x, cfg=cfg, spec=spec, prefix_len=prefix_len)
                x = C.constrain(x, ("batch", None, None))
                aux = aux + a
            return (x, aux), None

        body = _remat(period_body, remat)
        (x, aux), _ = jax.lax.scan(body, (x, aux), blocks["period"])
    for i in range(rem):
        x, a = block_fullseq(
            blocks["rem"][f"r{i}"], x, cfg=cfg, spec=cfg.block_pattern[i], prefix_len=prefix_len
        )
        aux = aux + a
    return x, aux


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> Pytree:
    """Zeroed decode state, stacked to mirror the block scan layout."""
    n_full, rem = P.block_layout(cfg)
    out: Dict[str, Any] = {}
    if n_full:
        out["period"] = {}
        for i, spec in enumerate(cfg.block_pattern):
            single = block_init_state(cfg, spec, batch, max_len)
            out["period"][f"p{i}"] = jax.tree.map(
                lambda a: jnp.zeros((n_full,) + a.shape, a.dtype), single
            )
    if rem:
        out["rem"] = {
            f"r{i}": block_init_state(cfg, cfg.block_pattern[i], batch, max_len)
            for i in range(rem)
        }
    return out


def stack_prefill(
    blocks: Pytree,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    max_len: int,
    prefix_len: int = 0,
    remat: str = "dots",
) -> Tuple[jax.Array, Pytree]:
    n_full, rem = P.block_layout(cfg)
    state: Dict[str, Any] = {}
    if n_full:
        def body(x, xs):
            st = {}
            x = C.constrain(x, ("batch", None, None))
            for i, spec in enumerate(cfg.block_pattern):
                x, s = block_prefill(
                    xs[f"p{i}"], x, cfg=cfg, spec=spec, max_len=max_len, prefix_len=prefix_len
                )
                x = C.constrain(x, ("batch", None, None))
                st[f"p{i}"] = s
            return x, st

        body = _remat(body, remat) if remat != "none" else body
        x, state_p = jax.lax.scan(body, x, blocks["period"])
        state["period"] = state_p
    if rem:
        state["rem"] = {}
        for i in range(rem):
            x, s = block_prefill(
                blocks["rem"][f"r{i}"], x, cfg=cfg, spec=cfg.block_pattern[i],
                max_len=max_len, prefix_len=prefix_len,
            )
            state["rem"][f"r{i}"] = s
    return x, state


def stack_decode(
    blocks: Pytree,
    state: Pytree,
    x: jax.Array,
    idx: jax.Array,
    *,
    cfg: ModelConfig,
) -> Tuple[jax.Array, Pytree]:
    n_full, rem = P.block_layout(cfg)
    new_state: Dict[str, Any] = {}
    if n_full:
        # The stacked decode state rides in the scan CARRY: a while-loop
        # carry is updated in place, where scan xs/ys would restack the
        # whole cache every step.  Each layer gets the stacked state and its
        # index in it.
        def body(carry, xs):
            x, st = carry
            ps, layer = xs
            st = dict(st)
            x = C.constrain(x, ("batch", None, None))
            for i, spec in enumerate(cfg.block_pattern):
                x, st[f"p{i}"] = block_decode(ps[f"p{i}"], x, st[f"p{i}"], idx, cfg=cfg,
                                              spec=spec, layer=layer)
            return (x, st), None

        (x, ns), _ = jax.lax.scan(
            body, (x, state["period"]),
            (blocks["period"], jnp.arange(n_full, dtype=jnp.int32)),
        )
        new_state["period"] = ns
    if rem:
        # A remainder layer's state is unstacked: decode it as a stack of one.
        new_state["rem"] = {}
        for i in range(rem):
            x, s = block_decode(
                blocks["rem"][f"r{i}"], x,
                jax.tree.map(lambda a: a[None], state["rem"][f"r{i}"]), idx,
                cfg=cfg, spec=cfg.block_pattern[i], layer=0,
            )
            new_state["rem"][f"r{i}"] = jax.tree.map(lambda a: a[0], s)
    return x, new_state


# ---------------------------------------------------------------------------
# Embedding / head / loss
# ---------------------------------------------------------------------------

def embed_inputs(params: Pytree, cfg: ModelConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    if cfg.input_mode == "embeddings":
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        return C.constrain(x, ("batch", None, None))
    x = jnp.take(params["embed"]["table"], batch["tokens"], axis=0).astype(
        jnp.dtype(cfg.dtype)
    )
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    if cfg.prefix_len and "prefix_embeds" in batch:
        x = jnp.concatenate([batch["prefix_embeds"].astype(x.dtype), x], axis=1)
    return C.constrain(x, ("batch", None, None))


def apply_head(params: Pytree, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    """h: (B, T, d) -> logits (B, T, V) or (B, K, T, V) for multi-codebook."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", h, params["embed"]["table"])
    elif cfg.n_codebooks > 1:
        logits = jnp.einsum("btd,kdv->bktv", h, params["head"]["w"])
    else:
        logits = jnp.einsum("btd,dv->btv", h, params["head"]["w"])
    logits = logits.astype(jnp.float32)
    if cfg.logits_softcap:
        c = cfg.logits_softcap
        logits = jnp.tanh(logits / c) * c
    return logits


def cross_entropy(logits: jax.Array, targets: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Mean CE over valid (target >= 0) positions. logits f32 (..., V)."""
    valid = targets >= 0
    tsafe = jnp.maximum(targets, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tsafe[..., None], axis=-1)[..., 0]
    ce = (lse - tgt) * valid
    n = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(ce) / n, n.astype(jnp.float32)


def forward_fullseq(
    params: Pytree,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    *,
    remat: str = "dots",
) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden (B,T,d), aux_loss)."""
    x = embed_inputs(params, cfg, batch)
    x, aux = stack_fullseq(
        params["blocks"], x, cfg=cfg, prefix_len=cfg.prefix_len, remat=remat
    )
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return x, aux


def train_loss(
    params: Pytree,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    *,
    remat: str = "dots",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    h, aux = forward_fullseq(params, cfg, batch, remat=remat)
    if cfg.prefix_len:
        # Loss only over the text region (after the stub prefix).
        h = h[:, cfg.prefix_len :]
    logits = apply_head(params, cfg, h)
    if cfg.n_codebooks > 1:
        targets = batch["targets"]  # (B, K, T)
        ce, n = cross_entropy(logits, targets)
    else:
        ce, n = cross_entropy(logits, batch["targets"])
    loss = ce + aux
    metrics = {"loss": loss, "ce": ce, "aux": aux, "n_tokens": n}
    if cfg.mtp_depth:
        mtp_l = mtp_loss(params, cfg, h_backbone=h, batch=batch)
        loss = loss + cfg.mtp_loss_weight * mtp_l
        metrics["mtp"] = mtp_l
        metrics["loss"] = loss
    return loss, metrics


def mtp_loss(params: Pytree, cfg: ModelConfig, *, h_backbone: jax.Array, batch) -> jax.Array:
    """DeepSeek-V3 multi-token-prediction auxiliary loss (depth 1+)."""
    tokens = batch["tokens"]
    total = jnp.zeros((), jnp.float32)
    h = h_backbone
    for k in range(cfg.mtp_depth):
        p = params["mtp"][f"d{k}"]
        # Combine h_t with the embedding of token t+k+1.
        emb = jnp.take(params["embed"]["table"], tokens[:, k + 1 :], axis=0)
        h_in = h[:, : emb.shape[1]]
        cat = jnp.concatenate(
            [L.rms_norm(h_in, p["ln_h"]["scale"], cfg.norm_eps),
             L.rms_norm(emb, p["ln_e"]["scale"], cfg.norm_eps)],
            axis=-1,
        )
        h = jnp.einsum("bte,ed->btd", cat, p["proj"])
        h, _ = block_fullseq(p["block"], h, cfg=cfg, spec=cfg.block_pattern[-1])
        logits = apply_head(params, cfg, h)
        # Predict token t+k+2 at position t.
        tgt = batch["targets"][:, k + 1 :]
        ce, _ = cross_entropy(logits, tgt)
        total = total + ce
    return total / max(cfg.mtp_depth, 1)


def prefill(
    params: Pytree,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    *,
    max_len: int,
    remat: str = "dots",
) -> Tuple[jax.Array, Pytree]:
    """Returns (last-token logits (B, V...), decode state)."""
    x = embed_inputs(params, cfg, batch)
    x, state = stack_prefill(
        params["blocks"], x, cfg=cfg, max_len=max_len, prefix_len=cfg.prefix_len, remat=remat
    )
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = apply_head(params, cfg, x[:, -1:])
    return logits, state


def decode_step(
    params: Pytree,
    cfg: ModelConfig,
    state: Pytree,
    batch: Dict[str, jax.Array],
    idx: jax.Array,
) -> Tuple[jax.Array, Pytree]:
    """One decode step.  batch carries 'tokens' (B,1) or 'embeds' (B,1,d)."""
    x = embed_inputs(params, cfg, batch)
    x, state = stack_decode(params["blocks"], state, x, idx, cfg=cfg)
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = apply_head(params, cfg, x)
    return logits, state
