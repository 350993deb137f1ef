"""The dense family's weights: the stacked tree holds each layer's own draw,
the layout is the program's, and every seed shares one compiled program."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import loader
from chipbench import weights as W


CELL = "qwen3-4b-complete-poisson"
D = loader.load_family(loader.ROOT / "chipbench" / "configs" / "qwen3-4b.json")


@pytest.mark.parametrize("tied", [True, False])
def test_layout_is_the_programs(tied):
    """The program's tree for the file's sizes is the benchmark's; one with
    another width or an untied head is refused."""
    from repro.models import params as P

    c = loader.load_cell(CELL, trace=False)
    m = dict(D.sizes(c.config, rehearse=True), tie_embeddings=tied)
    if not tied:
        with pytest.raises(ValueError, match="differs"):
            D.repo_config(c.config["name"], m)
        return
    cfg = D.repo_config(c.config["name"], m)        # raises on any difference
    assert cfg.use_qk_norm and cfg.tie_embeddings
    bad = dataclasses.replace(cfg, d_ff=m["d_ff"] * 2)
    with pytest.raises(ValueError, match="differs"):
        D.check_layout(m, P.abstract_params(bad))


def test_configuration_is_the_published_one():
    """The file runs the published config.json's numbers, and a file that
    states what the program's decoder does not compute is refused."""
    c = loader.load_cell(CELL, trace=False)
    m = D.sizes(c.config, rehearse=False)
    assert (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["d_ff"], m["vocab_size"]) == (36, 2560, 32, 8, 128, 9728, 151936)
    assert m["tie_embeddings"] and m["use_qk_norm"] and m["rope_theta"] == 1e6
    for key, value in [("hidden_act", "gelu"), ("attention_bias", True),
                       ("rope_scaling", {"type": "linear", "factor": 4.0})]:
        with pytest.raises(ValueError, match="does not compute"):
            D.sizes(dict(c.config, **{key: value}), rehearse=False)


def test_stacked_leaf_is_each_layers_draw():
    c = loader.load_cell(CELL, trace=False)
    m = D.sizes(c.config, rehearse=True)
    params = D.make_params(m, 2**33 + 7)
    key = W.base_key(2**33 + 7)
    for name in D._LAYER:
        group, leaf = name.split("/")
        stacked = params["blocks"]["period"]["p0"][group][leaf]
        for layer in range(m["n_layers"]):
            alone = D.layer_leaf(m, key, name, layer, jnp.bfloat16)
            assert np.array_equal(np.asarray(stacked[layer]), np.asarray(alone))
    other = D.make_params(m, 7)
    assert not np.array_equal(np.asarray(other["embed"]["table"]),
                              np.asarray(params["embed"]["table"]))


def test_seeds_share_one_program():
    c = loader.load_cell(CELL, trace=False)
    m = D.sizes(c.config, rehearse=True)
    import functools

    lowered = [jax.jit(functools.partial(D._tree, m)).lower(W.base_key(s)).as_text()
               for s in (1, 2**33 + 1)]
    assert lowered[0] == lowered[1]
