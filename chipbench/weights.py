"""The weights every cell serves, made by the benchmark from ``--seed``.

Each layer's slice of each leaf is drawn from its own key: the seed's key
folded with a CRC of the leaf's path and then with the layer index.  So the
program's stacked tree comes from one jitted call, and the reference can
draw any single layer again without the rest.  Draws are uniform with the
fan-in standard deviation, in the dtype served; norm scales are ones.  The
embedding is also the LM head (tied), drawn at the head's fan-in standard
deviation.  The tree follows the layout of a dense GQA decoder in
``repro.models.params`` (one stacked period ``p0``; per-head query and key
norms where the model has them); ``check_layout`` refuses a program whose
layout differs, an untied head among others.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Dict

from chipbench.traffic import rng_for

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


def weight_seed(seed: int) -> int:
    """A 31-bit key seed from any integer seed (a JAX key keeps 32 bits)."""
    return int(rng_for(seed, 3).integers(0, 2**31 - 1))


def base_key(seed: int):
    """The key all weights derive from.  Jitted draws take it as an
    argument, so one compiled program serves every seed."""
    import jax

    return jax.random.key(weight_seed(seed))


def _key(key, path: str):
    import jax

    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _uniform(key, shape, std, dtype):
    import jax
    import jax.numpy as jnp

    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def layer_leaf(m: dict, key, name: str, layer, dtype):
    """Layer ``layer``'s slice of leaf ``name`` (a key of ``_LAYER``)."""
    import jax

    shape, fan_in = _LAYER[name]
    k = jax.random.fold_in(_key(key, _PERIOD + name), layer)
    return _uniform(k, shape(m), 1.0 / math.sqrt(fan_in(m)), dtype)


def embed_table(m: dict, key, dtype):
    return _uniform(_key(key, "embed/table"), (m["vocab_size"], m["d_model"]),
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

    return jax.jit(functools.partial(_tree, m), out_shardings=shardings)(base_key(seed))


def check_layout(m: dict, program_abstract) -> None:
    """Raise unless the program's parameter tree has this layout."""
    import jax

    ours = jax.eval_shape(functools.partial(_tree, m), base_key(0))
    flat = lambda t: {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
                      for p, x in jax.tree_util.tree_flatten_with_path(t)[0]}
    a, b = flat(ours), flat(program_abstract)
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))
        raise ValueError(f"the program's parameter layout differs from the benchmark's: {diff[:6]}")
