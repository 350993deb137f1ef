"""What every family's plain reference shares, and the comparison that
decides ``correct``.

A family's ``reference_logits`` (``chipbench/families/``) computes in
float32 ``jax.numpy`` from the sizes in a configuration file and the weights
that ``chipbench.weights`` draws from the seed.  It imports nothing of the
program and takes nothing the program made: it draws each layer's weights
again, one layer at a time, and runs the whole forward pass over each
compared sequence (prompt and served tokens) with no cache.
``quantize="fp8"`` gives the control: the same pass with both operands of
every projection (``_proj``) rounded to float8 e4m3 (``_fq``), scaled per
row of activations and per output column of weights.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

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
