"""The plain reference: a dense GQA decoder (RMSNorm, per-head query and key
RMSNorm before RoPE where the model has it, causal softmax attention, gated
SiLU MLP, LM head tied to the embedding) in float32 ``jax.numpy``
under ``default_matmul_precision("highest")``, from the sizes in a
configuration file and the weights that ``chipbench.weights`` draws from
the seed.

It imports nothing of the program and takes nothing the program made: it
draws each layer's weights again, one layer at a time, and runs the whole
forward pass over each compared sequence (prompt and served tokens) with
no cache.  ``quantize="fp8"`` gives the control: the same pass with both
operands of every projection and of the LM head rounded to float8 e4m3,
scaled per row of activations and per output column of weights.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence

import numpy as np

from chipbench import weights as W

FP8_MAX = 448.0   # largest finite float8_e4m3fn


def _fq(x, axis):
    """Fake-quantize ``x`` to float8 e4m3, scaled by its max over ``axis``."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _proj(spec, x, w, quantize, x_axis, w_axis):
    import jax.numpy as jnp

    if quantize == "fp8":
        x, w = _fq(x, x_axis), _fq(w, w_axis)
    return jnp.einsum(spec, x, w)


def _rms(x, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def _rope(x, theta):
    """x: (B, T, H, D); rotates the two halves of each head (split-half)."""
    import jax.numpy as jnp

    D = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs      # (T, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(m: dict, quantize: Optional[str], x, w):
    import jax
    import jax.numpy as jnp

    eps, G = m["norm_eps"], m["n_heads"] // m["n_kv_heads"]
    h = _rms(x, eps)
    q = _proj("btd,dhk->bthk", h, w["attn/wq"], quantize, -1, 0)
    k = _proj("btd,dhk->bthk", h, w["attn/wk"], quantize, -1, 0)
    v = _proj("btd,dhk->bthk", h, w["attn/wv"], quantize, -1, 0)
    if m["use_qk_norm"]:
        q, k = _rms(q, eps), _rms(k, eps)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)    # query head h reads kv head h // G
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(m["head_dim"])
    T = x.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + _proj("bqhd,hdm->bqm", o, w["attn/wo"], quantize, (-2, -1), (0, 1))
    h = _rms(x, eps)
    gate = _proj("btd,df->btf", h, w["mlp/wi"][:, 0], quantize, -1, 0)
    up = _proj("btd,df->btf", h, w["mlp/wi"][:, 1], quantize, -1, 0)
    return x + _proj("btf,fd->btd", jax.nn.silu(gate) * up, w["mlp/wo"], quantize, -1, 0)


def _logits(m: dict, quantize: Optional[str], x, head, rows):
    """Final norm, then the head at positions ``rows`` (B, n) of each sequence."""
    import jax.numpy as jnp

    h = jnp.take_along_axis(_rms(x, m["norm_eps"]), rows[..., None], axis=1)
    return _proj("bnd,dv->bnv", h, head.astype(jnp.float32), quantize, -1, 0)


def logits(m: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
           *, quantize: Optional[str] = None, device=None) -> np.ndarray:
    """Logits (B, n, V) at positions ``rows`` (B, n) of ``tokens`` (B, T).

    Sequences may be padded on the right: attention is causal, so what
    follows a position never changes its logits."""
    import jax
    import jax.numpy as jnp

    device = device or jax.devices()[0]
    dt = jnp.dtype(m["dtype"])
    with jax.default_matmul_precision("highest"), jax.default_device(device):
        key = W.base_key(seed)
        draw = jax.jit(lambda k, name, l: W.layer_leaf(m, k, name, l, dt).astype(jnp.float32),
                       static_argnums=1)
        layer = jax.jit(functools.partial(_layer, m, quantize))
        table = jax.jit(lambda k: W.embed_table(m, k, dt))(key)
        x = jnp.take(table, jnp.asarray(tokens), axis=0).astype(jnp.float32)
        for l in range(m["n_layers"]):
            w = {name: draw(key, name, l) for name in W._LAYER}
            x = layer(x, w)
            del w
        out = jax.jit(functools.partial(_logits, m, quantize))(x, table.T, jnp.asarray(rows))
        return np.asarray(out)


def compare(served: Sequence[np.ndarray], ref: np.ndarray) -> dict:
    """The number that decides ``correct`` for a set of sequences.

    ``served[b]``: the tokens served for sequence ``b``; ``ref[b, i]`` the
    reference's logits at the position that chose ``served[b][i]``.
    ``token_gap`` is the widest gap by which a served token's logit lies
    below the reference's best at its position (greedy decoding only).
    """
    gaps: List[float] = []
    for b, toks in enumerate(served):
        r = ref[b, : len(toks)].astype(np.float64)
        gaps.extend(r.max(axis=1) - r[np.arange(len(toks)), toks])
    return {"token_gap": float(max(gaps))}


def control_readings(ref: np.ndarray, ctl: np.ndarray, lengths: Sequence[int]) -> dict:
    """The control's number: at each compared position, the gap of the token
    that the control puts first."""
    return compare([ctl[b, :n].argmax(axis=1) for b, n in enumerate(lengths)], ref)
