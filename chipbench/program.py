"""What every driver does with the program: build its model configuration
from a configuration file, and hand the compared sequences to the check.

A configuration file holds the model's published ``config.json`` as it is
run (Hugging Face key names), with the benchmark's own keys beside it.
``model_sizes`` maps it to the sizes that the weights, the reference and
``flops.py`` read, in the program's field names."""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List

import numpy as np

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


@contextlib.contextmanager
def phase(name: str):
    """Prints the host seconds a set-up phase took."""
    t0 = time.perf_counter()
    yield
    print(f"[setup] {name}_s={time.perf_counter() - t0!r}", flush=True)


def model_sizes(config: dict, rehearse: bool) -> dict:
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

    from chipbench import weights as W

    cfg = ModelConfig(name=name, block_pattern=(LayerSpec(ATTN),), family="dense", **model)
    W.check_layout(model, P.abstract_params(cfg))
    return cfg


@dataclasses.dataclass
class Sample:
    """One compared sequence: its prompt and the tokens served."""
    prompt: np.ndarray
    served: List[int]


def reference_inputs(samples: List[Sample], max_out: int):
    """(tokens (B, T), rows (B, max_out)) for ``reference.logits``: each
    prompt and its served tokens but the last, padded on the right to one
    length, so that every run of a cell compiles the same shapes."""
    plen = len(samples[0].prompt)
    T = plen + max_out - 1
    tokens = np.zeros((len(samples), T), np.int32)
    for b, s in enumerate(samples):
        seq = np.concatenate([s.prompt, np.asarray(s.served[:-1], np.int32)])
        tokens[b, : len(seq)] = seq
    rows = np.minimum(plen - 1 + np.arange(max_out), T - 1)
    return tokens, np.tile(rows, (len(samples), 1)).astype(np.int32)
