"""Serving engine: prefill + decode with continuous batching (slot-based).

A fixed grid of ``batch`` slots is decoded in lock-step (one jitted decode
step per token across all slots — the standard TPU serving shape).  Finished
sequences free their slot; queued requests are prefilled into free slots
between decode steps.  Per-slot position indices live in the engine; the
jitted step uses the MAXIMUM position for cache masking, which is correct
(slots are masked by their own valid lengths via the per-slot `stop` logic)
but admits some wasted attention span for ragged batches — the paper-style
time-series benchmark tracks exactly this kind of serving regression.

Between steps the decode state is held with each leaf's dimensions in the
order of the device layout that the decode step's compiler picks for it, and
each step consumes the state it is given (donation): prefill hands it over
in that order, and the step updates it in place.

Greedy and temperature sampling supported; everything is seeded and
deterministic (readiness L3).  Each wave records its spans and its
requests' token times in ``repro.serve.telemetry``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serve import telemetry as tel

Pytree = Any


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray          # (T,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0    # 0 = greedy
    eos_id: Optional[int] = None


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: List[int]
    prompt_len: int


def _reorder(state: Pytree, orders: List[Tuple[int, ...]], *, inverse: bool = False) -> Pytree:
    """Each leaf's dimensions put in its order (or back, with ``inverse``)."""
    leaves, tree = jax.tree.flatten(state)
    return tree.unflatten([jnp.transpose(a, np.argsort(o) if inverse else o)
                           for a, o in zip(leaves, orders)])


def compile_decode_step(cfg: ModelConfig, params: Pytree, *, batch: int,
                        max_len: int) -> Tuple[jax.stages.Compiled, List[Tuple[int, ...]]]:
    """``jit_decode_step`` for ``params`` (arrays, or shapes with their
    shardings), compiled ahead of time, and the order it keeps the decode
    state in: for each leaf, the model's dimensions from major to minor.

    The order is the device layout the compiler picks for each leaf when the
    state is donated and its layout left to it.  The step takes and returns
    every leaf transposed into that order, in the default layout, so the
    bytes sit as its loop wants them: the transposes are free, the donated
    state is updated in place, and no layout but the default crosses a
    program boundary.  Call it as ``(params, state, {"tokens": (batch, 1)
    int32}, idx int32)``; the state passed in is consumed."""
    placement = jax.tree.leaves(params)[0].sharding
    model_state = jax.eval_shape(lambda: T.init_decode_state(cfg, batch, max_len))
    inputs = ({"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=placement)},
              jax.ShapeDtypeStruct((), jnp.int32, sharding=placement))

    auto = jax.tree.map(lambda _: Format(Layout.AUTO, placement), model_state)
    probe = jax.jit(lambda p, s, b, i: T.decode_step(p, cfg, s, b, i),
                    in_shardings=(None, auto, None, None), out_shardings=(None, auto),
                    donate_argnums=(1,)).lower(params, model_state, *inputs).compile()
    orders = [f.layout.major_to_minor for f in jax.tree.leaves(probe.input_formats[0][1])]

    def decode_step(p, s, b, i):
        logits, s = T.decode_step(p, cfg, _reorder(s, orders, inverse=True), b, i)
        return logits, _reorder(s, orders)

    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=placement),
                         jax.eval_shape(lambda s: _reorder(s, orders), model_state))
    step = jax.jit(decode_step, donate_argnums=(1,)).lower(params, state, *inputs).compile()
    return step, orders


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Pytree,
        *,
        batch: int,
        max_len: int,
        seed: int = 0,
    ):
        assert cfg.input_mode == "tokens", "engine serves token LMs"
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.key = jax.random.key(seed)

        # Named functions, so that the device trace names the programs
        # ``jit_prefill`` and ``jit_decode_step``.  Prefill hands its state
        # over in the order the decode step keeps it in, and places its
        # outputs where the step's are, so that the host's ops on either
        # step's tokens compile once.
        self._decode, orders = compile_decode_step(cfg, params, batch=batch, max_len=max_len)

        def prefill(p, b):
            logits, state = T.prefill(p, cfg, b, max_len=max_len, remat="none")
            return logits, _reorder(state, orders)

        self._prefill = jax.jit(prefill, out_shardings=jax.tree.leaves(params)[0].sharding)

    # -- batched offline generation (all requests same length budget) --
    def generate(self, requests: List[Request]) -> List[Completion]:
        """Simple scheduler: admit in waves of ``batch``, decode lock-step."""
        out: List[Completion] = []
        with tel.span("engine.generate") as call:
            for i in range(0, len(requests), self.batch):
                out.extend(self._generate_wave(requests[i : i + self.batch], call.start_ns))
        return out

    def _generate_wave(self, wave: List[Request], start_ns: int) -> List[Completion]:
        n = len(wave)
        plen = max(len(r.prompt) for r in wave)
        budget = max(r.max_new_tokens for r in wave)
        with tel.span("engine.wave") as w:
            records = [tel.request(r.uid, w.seq, start_ns) for r in wave]
            toks = np.zeros((self.batch, plen), np.int32)
            for j, r in enumerate(wave):
                toks[j, plen - len(r.prompt):] = r.prompt  # left-pad
            batch = {"tokens": jnp.asarray(toks)}
            completions = [Completion(r.uid, [], len(r.prompt)) for r in wave]
            live = np.ones(self.batch, bool)
            live[n:] = False
            with tel.span("engine.prefill", wave=w.seq, step=0):
                logits, state = self._prefill(self.params, batch)
                cur = self._sample(logits[:, 0], wave)
                self._take_tokens(cur, wave, live, completions, records, stop=False)
            for t in range(1, budget):
                with tel.span("engine.step", wave=w.seq, step=t, pos=plen + t - 1,
                              live=int(live.sum())):
                    idx = jnp.asarray(plen + t - 1, jnp.int32)
                    logits, state = self._decode(
                        self.params, state, {"tokens": cur[:, None]}, idx
                    )
                    cur = self._sample(logits[:, 0], wave)
                    self._take_tokens(cur, wave, live, completions, records, stop=True)
                if not live.any():
                    break
        return completions

    def _take_tokens(self, cur: jax.Array, wave: List[Request], live: np.ndarray,
                     completions: List[Completion], records: List[dict], *,
                     stop: bool) -> None:
        """Waits for a step's sampled tokens ``cur`` and hands each live slot
        its own; with ``stop``, a slot whose request is done stops being live.

        The first live slot's slice is dispatched before the wait, so that it
        queues behind the step and the device does not idle while the host
        dispatches it."""
        j0 = int(np.argmax(live))
        head = cur[j0]
        with tel.span("engine.wait"):
            jax.block_until_ready(head)
        with tel.span("engine.read"):
            for j in np.flatnonzero(live).tolist():
                tok = int(head if j == j0 else cur[j])
                completions[j].tokens.append(tok)
                records[j]["token_ns"].append(time.perf_counter_ns())
                r = wave[j]
                if stop and (len(completions[j].tokens) >= r.max_new_tokens or (
                    r.eos_id is not None and tok == r.eos_id
                )):
                    live[j] = False

    def _sample(self, logits: jax.Array, wave: List[Request]) -> jnp.ndarray:
        temps = np.zeros(self.batch, np.float32)
        for j, r in enumerate(wave):
            temps[j] = r.temperature
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if float(np.max(temps)) == 0.0:
            return greedy
        self.key, sub = jax.random.split(self.key)
        t = jnp.asarray(np.maximum(temps, 1e-6))
        sampled = jax.random.categorical(sub, logits / t[:, None]).astype(jnp.int32)
        return jnp.where(jnp.asarray(temps) > 0, sampled, greedy)
