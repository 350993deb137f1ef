"""The dense GQA decoder: RMSNorm, grouped-query attention with per-head
query and key RMSNorm before RoPE where the model has it, gated SiLU MLP, LM
head tied to the embedding.  A configuration file names it with
``"family": "dense_gqa"``.

Sizes: a configuration file holds the model's published ``config.json`` as
it is run (Hugging Face key names); ``sizes`` maps it to the program's field
names and refuses a file whose activation, biases, rope scaling or sliding
window this decoder does not compute.

Weights: the tree follows the layout of a dense GQA decoder in
``repro.models.params`` (one stacked period ``p0``; per-head query and key
norms where the model has them), drawn as ``chipbench.weights`` says.  The
embedding is also the LM head (tied), drawn at the head's fan-in standard
deviation; norm scales are ones.  ``check_layout`` refuses a program whose
layout differs, an untied head among others.

Reference: the plain forward pass in float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, causal softmax attention with no
cache, one layer's weights drawn again at a time.  ``quantize="fp8"`` gives
the control: both operands of every projection and of the LM head rounded to
float8 e4m3, scaled per row of activations and per output column of weights.

Operations: a multiply-add is two.  Counted: every projection (query, key,
value, output, the gated MLP's two inputs and its output), attention over
the real context (a causal token at position ``p`` attends ``p + 1`` keys,
through both the score and the value product), and the LM head for each
token whose logits are computed.  Not counted: norms, RoPE, softmax and the
embedding gather, which are small beside these.

Kernel: the Pallas flash-attention kernel serves the prefill, installed by
``repro.kernels.flash_attention.ops.install``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import numpy as np

from chipbench import flops as F
from chipbench import reference as R
from chipbench import weights as W

# Published key -> the program's ModelConfig field.
FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}
# What the program's dense decoder computes; a file that states otherwise
# is refused rather than run as something else.
SERVED = {"hidden_act": "silu", "attention_bias": False, "rope_scaling": None,
          "use_sliding_window": False}
# Model types whose attention RMS-normalizes each query and key head before RoPE.
QK_NORM_TYPES = ("qwen3",)

# Substring of the flash kernel's op names in the device trace.
KERNEL = "flash"


def sizes(config: dict, rehearse: bool) -> dict:
    """The sizes a run uses: the file's, or its rehearsal sizes."""
    off = {k: (config.get(k), v) for k, v in SERVED.items() if config.get(k) != v}
    if off:
        raise ValueError(f"{config['name']}: the program's dense decoder does not compute {off}")
    src = {**config, **config["rehearse"]} if rehearse else config
    m = {field: src[key] for key, field in FIELDS.items()}
    m["rope_theta"] = float(m["rope_theta"])
    m["use_qk_norm"] = config["model_type"] in QK_NORM_TYPES
    return m


def repo_config(name: str, model: dict):
    """The program's ModelConfig for these sizes, refused unless its
    parameter tree has the benchmark's layout."""
    from repro.models import params as P
    from repro.models.config import ATTN, LayerSpec, ModelConfig

    cfg = ModelConfig(name=name, block_pattern=(LayerSpec(ATTN),), family="dense", **model)
    check_layout(model, P.abstract_params(cfg))
    return cfg


def install_kernels(interpret: bool) -> None:
    from repro.kernels.flash_attention import ops as fa

    fa.install(interpret=interpret)


def uninstall_kernels() -> None:
    from repro.kernels.flash_attention import ops as fa

    fa.uninstall()


# --- weights ---------------------------------------------------------------

# path -> (shape without the layer axis, fan-in) as functions of the sizes.
_LAYER = {
    "attn/wq": (lambda m: (m["d_model"], m["n_heads"], m["head_dim"]), lambda m: m["d_model"]),
    "attn/wk": (lambda m: (m["d_model"], m["n_kv_heads"], m["head_dim"]), lambda m: m["d_model"]),
    "attn/wv": (lambda m: (m["d_model"], m["n_kv_heads"], m["head_dim"]), lambda m: m["d_model"]),
    "attn/wo": (lambda m: (m["n_heads"], m["head_dim"], m["d_model"]),
                lambda m: m["n_heads"] * m["head_dim"]),
    "mlp/wi": (lambda m: (m["d_model"], 2, m["d_ff"]), lambda m: m["d_model"]),
    "mlp/wo": (lambda m: (m["d_ff"], m["d_model"]), lambda m: m["d_ff"]),
}
_NORMS = ("ln1/scale", "ln2/scale")
_QK_NORMS = ("q_norm", "k_norm")
_PERIOD = "blocks/period/p0/"


def layer_leaf(m: dict, key, name: str, layer, dtype):
    """Layer ``layer``'s slice of leaf ``name`` (a key of ``_LAYER``)."""
    import jax

    shape, fan_in = _LAYER[name]
    k = jax.random.fold_in(W._key(key, _PERIOD + name), layer)
    return W._uniform(k, shape(m), 1.0 / math.sqrt(fan_in(m)), dtype)


def embed_table(m: dict, key, dtype):
    return W._uniform(W._key(key, "embed/table"), (m["vocab_size"], m["d_model"]),
                      1.0 / math.sqrt(m["d_model"]), dtype)


def _tree(m: dict, key):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(m["dtype"])
    L, d = m["n_layers"], m["d_model"]
    p0: Dict[str, dict] = {"attn": {}, "mlp": {}}
    for name in _LAYER:
        group, leaf = name.split("/")
        # vmap over the layer index draws each layer's slice as layer_leaf
        # does, straight into the stacked leaf.
        p0[group][leaf] = jax.vmap(lambda l, name=name: layer_leaf(m, key, name, l, dt))(
            jnp.arange(L))
    if m["use_qk_norm"]:
        for leaf in _QK_NORMS:
            p0["attn"][leaf] = jnp.ones((L, m["head_dim"]), jnp.float32)
    for name in _NORMS:
        group, leaf = name.split("/")
        p0[group] = {leaf: jnp.ones((L, d), jnp.float32)}
    return {
        "embed": {"table": embed_table(m, key, dt)},
        "blocks": {"period": {"p0": p0}},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
    }


def make_params(m: dict, seed: int, shardings=None):
    """The whole tree, on the device(s), from one jitted call."""
    import jax

    return jax.jit(functools.partial(_tree, m), out_shardings=shardings)(W.base_key(seed))


def check_layout(m: dict, program_abstract) -> None:
    """Raise unless the program's parameter tree has this layout."""
    import jax

    ours = jax.eval_shape(functools.partial(_tree, m), W.base_key(0))
    flat = lambda t: {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
                      for p, x in jax.tree_util.tree_flatten_with_path(t)[0]}
    a, b = flat(ours), flat(program_abstract)
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))
        raise ValueError(f"the program's parameter layout differs from the benchmark's: {diff[:6]}")


# --- reference -------------------------------------------------------------

def _layer(m: dict, quantize: Optional[str], x, w):
    import jax
    import jax.numpy as jnp

    eps, G = m["norm_eps"], m["n_heads"] // m["n_kv_heads"]
    h = R._rms(x, eps)
    q = R._proj("btd,dhk->bthk", h, w["attn/wq"], quantize, -1, 0)
    k = R._proj("btd,dhk->bthk", h, w["attn/wk"], quantize, -1, 0)
    v = R._proj("btd,dhk->bthk", h, w["attn/wv"], quantize, -1, 0)
    if m["use_qk_norm"]:
        q, k = R._rms(q, eps), R._rms(k, eps)
    q, k = R._rope(q, m["rope_theta"]), R._rope(k, m["rope_theta"])
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)    # query head h reads kv head h // G
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(m["head_dim"])
    T = x.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + R._proj("bqhd,hdm->bqm", o, w["attn/wo"], quantize, (-2, -1), (0, 1))
    h = R._rms(x, eps)
    gate = R._proj("btd,df->btf", h, w["mlp/wi"][:, 0], quantize, -1, 0)
    up = R._proj("btd,df->btf", h, w["mlp/wi"][:, 1], quantize, -1, 0)
    return x + R._proj("btf,fd->btd", jax.nn.silu(gate) * up, w["mlp/wo"], quantize, -1, 0)


def _logits(m: dict, quantize: Optional[str], x, head, rows):
    """Final norm, then the head at positions ``rows`` (B, n) of each sequence."""
    import jax.numpy as jnp

    h = jnp.take_along_axis(R._rms(x, m["norm_eps"]), rows[..., None], axis=1)
    return R._proj("bnd,dv->bnv", h, head.astype(jnp.float32), quantize, -1, 0)


def reference_logits(m: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
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
        draw = jax.jit(lambda k, name, l: layer_leaf(m, k, name, l, dt).astype(jnp.float32),
                       static_argnums=1)
        layer = jax.jit(functools.partial(_layer, m, quantize))
        table = jax.jit(lambda k: embed_table(m, k, dt))(key)
        x = jnp.take(table, jnp.asarray(tokens), axis=0).astype(jnp.float32)
        for l in range(m["n_layers"]):
            w = {name: draw(key, name, l) for name in _LAYER}
            x = layer(x, w)
            del w
        out = jax.jit(functools.partial(_logits, m, quantize))(x, table.T, jnp.asarray(rows))
        return np.asarray(out)


# --- operations ------------------------------------------------------------

def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    mlp = 3 * d * m["d_ff"]
    return attn + mlp


def attention_flops(m: dict, pairs: int) -> int:
    """Score and value products of all layers for ``pairs`` (query, key)
    pairs per head."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * pairs


def forward_flops(m: dict, *, tokens: int, pairs: int, head_tokens: int) -> int:
    """A forward pass over ``tokens`` tokens that attend ``pairs`` keys in
    all, computing logits for ``head_tokens`` of them."""
    dense = 2 * tokens * m["n_layers"] * layer_matmul_params(m)
    head = 2 * head_tokens * m["d_model"] * m["vocab_size"]
    return dense + attention_flops(m, pairs) + head


def prefill_flops(m: dict, prompt_len: int) -> int:
    """One sequence's prefill; logits for its last token only."""
    return forward_flops(m, tokens=prompt_len, pairs=F.causal_pairs(0, prompt_len),
                         head_tokens=1)


def decode_flops(m: dict, position: int) -> int:
    """One decoded token at ``position`` (it attends ``position + 1`` keys)."""
    return forward_flops(m, tokens=1, pairs=position + 1, head_tokens=1)


def sequence_flops(m: dict, prompt_len: int, n_out: int) -> int:
    """A request's whole work: its prefill, which yields token 0, then
    ``n_out - 1`` decode steps at positions ``prompt_len .. prompt_len + n_out - 2``."""
    dec = n_out - 1
    return (prefill_flops(m, prompt_len)
            + forward_flops(m, tokens=dec, pairs=F.causal_pairs(prompt_len, prompt_len + dec),
                            head_tokens=dec))


def kernel_cost(m: dict, *, batch: int, seq: int, bytes_per_el: int = 2):
    """(operations, bytes) of one causal flash-attention call over one layer:
    ``batch`` sequences of ``seq`` tokens.  Bytes: Q, K and V read once and
    the output written once."""
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    ops = 4 * batch * hq * hd * F.causal_pairs(0, seq)
    nbytes = bytes_per_el * batch * seq * hd * (2 * hq + 2 * hkv)
    return ops, nbytes
