"""Serving engine: prefill + lock-step decode, admitted in waves.

``generate`` admits its requests in waves of ``batch``.  A wave's prompts
are left-padded to its longest and prefilled together, and then the whole
wave is decoded in lock-step, one jitted decode step per token, until its
longest request ends; a finished request's slot idles until then.  The wave
shares one decode position, so the padding of a shorter prompt is attended
to and ragged prompts are served wrongly.  Each decode step consumes the
state it is given (donation) and updates it in place.

Greedy and temperature sampling supported; everything is seeded and
deterministic (readiness L3).  Each wave records its spans and its
requests' token times in ``repro.serve.telemetry``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

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


def compile_decode_step(cfg: ModelConfig, params: Pytree, *, batch: int,
                        max_len: int) -> jax.stages.Compiled:
    """``jit_decode_step`` for ``params`` (arrays, or shapes with their
    shardings), compiled ahead of time on the params' placement.  Call it as
    ``(params, state, {"tokens": (batch, 1) int32}, idx int32)``; the state
    passed in is consumed and updated in place."""
    placement = jax.tree.leaves(params)[0].sharding
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=placement),
                         jax.eval_shape(lambda: T.init_decode_state(cfg, batch, max_len)))
    inputs = ({"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=placement)},
              jax.ShapeDtypeStruct((), jnp.int32, sharding=placement))

    def decode_step(p, s, b, i):
        return T.decode_step(p, cfg, s, b, i)

    return jax.jit(decode_step, donate_argnums=(1,)).lower(params, state, *inputs).compile()


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
        # ``jit_prefill`` and ``jit_decode_step``.  Prefill places its
        # outputs where the decode step's are, so that the host's ops on
        # either step's tokens compile once.
        self._decode = compile_decode_step(cfg, params, batch=batch, max_len=max_len)

        def prefill(p, b):
            return T.prefill(p, cfg, b, max_len=max_len, remat="none")

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
