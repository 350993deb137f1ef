"""Parameter-tree description and initialization.

Parameters are plain nested dicts of arrays.  A parallel tree of ``ParamSpec``
describes each leaf: shape, dtype, *logical axis names* and initializer.
Logical axes ("embed", "ffn", "q_heads", "experts", ...) are mapped to mesh
axes by ``repro.distributed.sharding`` — model code never mentions a mesh.

Depth is stacked for ``jax.lax.scan``: the repeating block pattern produces
one stacked entry per period position (leading logical axis "layers"), plus
unstacked entries for the truncated final period.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    init: str = "fan_in"      # fan_in | zeros | ones | rglru_a | ssd_a_log | ssd_dt_bias | std_<std>
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (self.shape, self.logical_axes)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


def _norm(d: int) -> Dict[str, ParamSpec]:
    return {"scale": ParamSpec((d,), ("embed",), "ones", "float32")}


def _attn_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out: Dict[str, ParamSpec] = {
        "wq": ParamSpec((d, h, hd), ("embed", "q_heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("q_heads", "head_dim", "embed")),
    }
    if cfg.use_qk_norm:
        out["q_norm"] = ParamSpec((hd,), ("head_dim",), "ones", "float32")
        out["k_norm"] = ParamSpec((hd,), ("head_dim",), "ones", "float32")
    return out


def _mla_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "q_down": ParamSpec((d, m.q_lora_rank), ("embed", "q_lora")),
        "q_norm": ParamSpec((m.q_lora_rank,), ("q_lora",), "ones", "float32"),
        "q_up": ParamSpec((m.q_lora_rank, h, qk), ("q_lora", "q_heads", "head_dim")),
        # kv_down projects to the compressed cache [c_kv | k_rope].
        "kv_down": ParamSpec(
            (d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "kv_lora")
        ),
        "kv_norm": ParamSpec((m.kv_lora_rank,), ("kv_lora",), "ones", "float32"),
        "kv_up": ParamSpec(
            (m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim),
            ("kv_lora", "q_heads", "head_dim"),
        ),
        "wo": ParamSpec((h, m.v_head_dim, d), ("q_heads", "head_dim", "embed")),
    }


def _dense_mlp_specs(d_model: int, d_ff: int) -> Dict[str, ParamSpec]:
    return {
        # Fused [gate; up] SwiGLU input projection.
        "wi": ParamSpec((d_model, 2, d_ff), ("embed", None, "ffn")),
        "wo": ParamSpec((d_ff, d_model), ("ffn", "embed")),
    }


def _moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    m = cfg.moe
    d = cfg.d_model
    out: Dict[str, ParamSpec] = {
        "router": ParamSpec((d, m.n_experts), ("embed", None), "fan_in", "float32"),
        "wi": ParamSpec((m.n_experts, d, 2, m.d_ff), ("experts", "embed", None, "moe_ffn")),
        "wo": ParamSpec((m.n_experts, m.d_ff, d), ("experts", "moe_ffn", "embed")),
    }
    if m.n_shared_experts:
        ff = m.shared_d_ff or m.d_ff * m.n_shared_experts
        out["shared"] = _dense_mlp_specs(d, ff)
    return out


def _rglru_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    r = cfg.rglru
    d = cfg.d_model
    w = r.lru_width or d
    h = cfg.n_heads
    assert w % h == 0, "lru_width must divide into gate heads"
    bw = w // h
    return {
        "wx": ParamSpec((d, w), ("embed", "lru")),
        "wy": ParamSpec((d, w), ("embed", "lru")),
        "conv_w": ParamSpec((r.conv_width, w), (None, "lru")),
        "conv_b": ParamSpec((w,), ("lru",), "zeros"),
        # Block-diagonal input & recurrence gates (Griffin eq. 3-4).
        "gate_w": ParamSpec((2, h, bw, bw), (None, "lru_heads", None, None)),
        "gate_b": ParamSpec((2, h, bw), (None, "lru_heads", None), "zeros"),
        "a_param": ParamSpec((w,), ("lru",), "rglru_a", "float32"),
        "wo": ParamSpec((w, d), ("lru", "embed")),
    }


def _ssd_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    s = cfg.ssd
    d = cfg.d_model
    di = s.d_inner(d)
    nh = di // s.head_dim
    g, st = s.n_groups, s.d_state
    return {
        "wz": ParamSpec((d, nh, s.head_dim), ("embed", "q_heads", "head_dim")),
        "wx": ParamSpec((d, nh, s.head_dim), ("embed", "q_heads", "head_dim")),
        "wBC": ParamSpec((d, 2, g, st), ("embed", None, None, "state")),
        "wdt": ParamSpec((d, nh), ("embed", "q_heads")),
        "conv_x": ParamSpec((s.conv_width, nh, s.head_dim), (None, "q_heads", "head_dim")),
        "conv_BC": ParamSpec((s.conv_width, 2, g, st), (None, None, None, "state")),
        "conv_b_x": ParamSpec((nh, s.head_dim), ("q_heads", "head_dim"), "zeros"),
        "conv_b_BC": ParamSpec((2, g, st), (None, None, "state"), "zeros"),
        "A_log": ParamSpec((nh,), ("q_heads",), "ssd_a_log", "float32"),
        "dt_bias": ParamSpec((nh,), ("q_heads",), "ssd_dt_bias", "float32"),
        "D": ParamSpec((nh,), ("q_heads",), "ones", "float32"),
        "gnorm": ParamSpec((nh, s.head_dim), ("q_heads", "head_dim"), "ones", "float32"),
        "wo": ParamSpec((nh, s.head_dim, d), ("q_heads", "head_dim", "embed")),
    }


def layer_specs_tree(cfg: ModelConfig, spec: LayerSpec) -> Dict[str, Any]:
    """Spec tree for a single layer of the given kind."""
    out: Dict[str, Any] = {"ln1": _norm(cfg.d_model)}
    if spec.kind == ATTN:
        out["attn"] = _attn_specs(cfg)
    elif spec.kind == MLA:
        out["attn"] = _mla_specs(cfg)
    elif spec.kind == RGLRU:
        out["rglru"] = _rglru_specs(cfg)
    elif spec.kind == SSD:
        out["ssd"] = _ssd_specs(cfg)
    else:
        raise ValueError(spec.kind)
    if spec.mlp != MLP_NONE:
        out["ln2"] = _norm(cfg.d_model)
        if spec.mlp == MLP_DENSE:
            out["mlp"] = _dense_mlp_specs(cfg.d_model, cfg.d_ff)
        elif spec.mlp == MLP_MOE:
            out["moe"] = _moe_specs(cfg)
        else:
            raise ValueError(spec.mlp)
    return out


def _stack_specs(tree: Pytree, n: int) -> Pytree:
    """Add a leading 'layers' axis of size n to every spec leaf."""

    def f(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, ("layers",) + s.logical_axes, s.init, s.dtype)

    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def block_layout(cfg: ModelConfig) -> Tuple[int, int]:
    """(n_full_periods, n_remainder_layers) for the scan layout."""
    period = len(cfg.block_pattern)
    return cfg.n_layers // period, cfg.n_layers % period


def _retype(tree: Pytree, dtype: str) -> Pytree:
    """Weight dtype follows cfg.dtype; f32 leaves (norms, gates) stay f32."""

    def f(s: ParamSpec) -> ParamSpec:
        if s.dtype == "bfloat16" and dtype != "bfloat16":
            return ParamSpec(s.shape, s.logical_axes, s.init, dtype)
        return s

    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    cfg.validate()
    d, v = cfg.d_model, cfg.vocab_size
    out: Dict[str, Any] = {}
    if cfg.input_mode == "tokens":
        out["embed"] = {
            "table": ParamSpec((v, d), ("vocab", "embed"), "std_1.0")
        }
    n_full, rem = block_layout(cfg)
    blocks: Dict[str, Any] = {}
    if n_full:
        blocks["period"] = {
            f"p{i}": _stack_specs(layer_specs_tree(cfg, s), n_full)
            for i, s in enumerate(cfg.block_pattern)
        }
    if rem:
        blocks["rem"] = {
            f"r{i}": layer_specs_tree(cfg, cfg.block_pattern[i]) for i in range(rem)
        }
    out["blocks"] = blocks
    out["final_norm"] = _norm(d)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks > 1:
            out["head"] = {
                "w": ParamSpec((cfg.n_codebooks, d, v), (None, "embed", "vocab"))
            }
        else:
            out["head"] = {"w": ParamSpec((d, v), ("embed", "vocab"))}
    if cfg.mtp_depth:
        # DeepSeek-V3 MTP: one extra block per depth, input = proj([h; e(t+k)]).
        out["mtp"] = {
            f"d{k}": {
                "proj": ParamSpec((2 * d, d), (None, "embed")),
                "ln_h": _norm(d),
                "ln_e": _norm(d),
                "block": layer_specs_tree(cfg, cfg.block_pattern[-1]),
            }
            for k in range(cfg.mtp_depth)
        }
    return _retype(out, cfg.dtype)


# ---------------------------------------------------------------------------
# Tree helpers
# ---------------------------------------------------------------------------

def iter_specs(tree: Pytree, prefix: str = "") -> Iterator[Tuple[str, ParamSpec]]:
    if isinstance(tree, ParamSpec):
        yield prefix, tree
        return
    for k in sorted(tree):
        yield from iter_specs(tree[k], f"{prefix}/{k}" if prefix else k)


def count_params(specs: Pytree, active_only: bool = False) -> int:
    total = 0
    for _, s in iter_specs(specs):
        n = s.size
        if active_only and "experts" in s.logical_axes:
            # Routed experts: only top_k of n_experts are active per token.
            e_dim = s.shape[s.logical_axes.index("experts")]
            frac = min(1.0, _ACTIVE_TOPK[0] / e_dim) if _ACTIVE_TOPK[0] else 1.0
            n = int(n * frac)
        total += n
    return total


# count_params needs the top_k without re-threading cfg; set by callers.
_ACTIVE_TOPK = [0]


def count_params_cfg(cfg: ModelConfig, active_only: bool = False) -> int:
    specs = param_specs(cfg)
    if cfg.moe:
        _ACTIVE_TOPK[0] = cfg.moe.top_k
    try:
        return count_params(specs, active_only=active_only)
    finally:
        _ACTIVE_TOPK[0] = 0


def non_embedding_param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Parameter count excluding vocab tables (for 6·N·D MODEL_FLOPS)."""
    specs = param_specs(cfg)
    if cfg.moe:
        _ACTIVE_TOPK[0] = cfg.moe.top_k
    try:
        total = 0
        for _, s in iter_specs(specs):
            if "vocab" in s.logical_axes:
                continue
            n = s.size
            if active_only and "experts" in s.logical_axes:
                e_dim = s.shape[s.logical_axes.index("experts")]
                frac = min(1.0, _ACTIVE_TOPK[0] / e_dim) if _ACTIVE_TOPK[0] else 1.0
                n = int(n * frac)
            total += n
        return total
    finally:
        _ACTIVE_TOPK[0] = 0


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _uniform_std(key: jax.Array, shape, std: float) -> jax.Array:
    """Zero-mean uniform draw with standard deviation ``std``.  Uniform, not
    normal: on a TPU v5e a normal draw of one full-width leaf compiles in
    ~30 s against ~5 s for a uniform one, and init compiles a program per
    leaf shape."""
    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a)


def _init_leaf(key: jax.Array, s: ParamSpec) -> jax.Array:
    dt = jnp.dtype(s.dtype)
    if s.init == "zeros":
        return jnp.zeros(s.shape, dt)
    if s.init == "ones":
        return jnp.ones(s.shape, dt)
    if s.init == "rglru_a":
        # Λ such that a = exp(-8·softplus(Λ)) lands in [0.9, 0.999].
        u = jax.random.uniform(key, s.shape, jnp.float32, 0.9, 0.999)
        y = -jnp.log(u) / 8.0
        lam = jnp.log(jnp.expm1(y))
        return lam.astype(dt)
    if s.init == "ssd_a_log":
        a = jax.random.uniform(key, s.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(a).astype(dt)
    if s.init == "ssd_dt_bias":
        dtv = jax.random.uniform(key, s.shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(dtv)).astype(dt)  # inverse softplus
    if s.init.startswith("std_"):
        return _uniform_std(key, s.shape, float(s.init.split("_", 1)[1])).astype(dt)
    if s.init == "fan_in":
        # Fan-in = product of all axes left of the last "output block".
        # Heuristic: treat the first axis (after any 'layers' stack) as input.
        shape = s.shape
        offset = 1 if (s.logical_axes and s.logical_axes[0] == "layers") else 0
        fan_in = shape[offset] if len(shape) > offset else 1
        return _uniform_std(key, s.shape, 1.0 / math.sqrt(max(1, fan_in))).astype(dt)
    raise ValueError(s.init)


def init_params(cfg: ModelConfig, key: jax.Array, shardings: Optional[Pytree] = None) -> Pytree:
    """Materialize a parameter tree.

    Each leaf is drawn by its own jitted program, so the f32 draw fuses into
    the leaf's dtype and never exists whole beside the resident weights (at
    full width it would not fit on one chip).  ``shardings`` (a tree like
    ``distributed.sharding.param_shardings``) places every leaf as it is
    drawn; without it leaves land on the default device.  Per-leaf keys
    derive from a CRC of the path — deterministic across processes
    (readiness L3 requires bit-reproducible init).
    """
    import zlib

    specs = param_specs(cfg)
    placement = flatten(shardings) if shardings is not None else {}
    leaves = {}
    for path, s in iter_specs(specs):
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        draw = jax.jit(_init_leaf, static_argnums=1, out_shardings=placement.get(path))
        leaves[path] = draw(k, s)
    return unflatten(leaves)


def unflatten(flat: Dict[str, Any]) -> Pytree:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        parts = path.split("/")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def flatten(tree: Pytree, prefix: str = "") -> Dict[str, Any]:
    if not isinstance(tree, dict):
        return {prefix: tree}
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        out.update(flatten(v, f"{prefix}/{k}" if prefix else k))
    return out


def abstract_params(cfg: ModelConfig) -> Pytree:
    """ShapeDtypeStruct tree for dry-run lowering (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        param_specs(cfg),
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )
