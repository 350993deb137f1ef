"""What every driver does whatever the architecture: time its set-up
phases, and hand the compared sequences to the check.  What depends on the
architecture (sizes, the program's configuration, weights, reference,
operation counts, kernels) is the cell's family (``chipbench/families/``)."""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List

import numpy as np


@contextlib.contextmanager
def phase(name: str):
    """Prints the host seconds a set-up phase took."""
    t0 = time.perf_counter()
    yield
    print(f"[setup] {name}_s={time.perf_counter() - t0!r}", flush=True)


@dataclasses.dataclass
class Sample:
    """One compared sequence: its prompt and the tokens served."""
    prompt: np.ndarray
    served: List[int]


def reference_inputs(samples: List[Sample], max_out: int):
    """(tokens (B, T), rows (B, max_out)) for a family's
    ``reference_logits``: each prompt and its served tokens but the last,
    padded on the right to one length, so that every run of a cell compiles
    the same shapes."""
    plen = len(samples[0].prompt)
    T = plen + max_out - 1
    tokens = np.zeros((len(samples), T), np.int32)
    for b, s in enumerate(samples):
        seq = np.concatenate([s.prompt, np.asarray(s.served[:-1], np.int32)])
        tokens[b, : len(seq)] = seq
    rows = np.minimum(plen - 1 + np.arange(max_out), T - 1)
    return tokens, np.tile(rows, (len(samples), 1)).astype(np.int32)
