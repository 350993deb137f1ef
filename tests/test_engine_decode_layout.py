"""The engine's decode step takes its state in the order of the device
layout its compiler picks for it, and consumes it (donation).  Its greedy
tokens are those of a plain loop of ``jax.jit(T.prefill)`` and
``jax.jit(T.decode_step)``, with no reordering and no donation, for a dense
cache and a ring cache, and stay so over consecutive ``generate`` calls: no
wave reads a donated state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import params as P
from repro.models import transformer as T
from repro.serve.engine import Engine, Request

BATCH, MAX_LEN = 3, 32


@pytest.fixture(scope="module", params=["qwen3-32b", "gemma3-4b"])
def model(request):
    """A dense decoder, and one whose local layers keep a ring cache of 8
    positions (with unstacked remainder layers), so that decoding wraps it."""
    cfg = configs.get_smoke(request.param)
    return cfg, P.init_params(cfg, jax.random.key(0))


def _requests(seed):
    """Two waves: mixed prompt lengths (left-padded) and budgets."""
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(1, 256, 5 + 2 * (i % 3)).astype(np.int32),
                    max_new_tokens=(9, 4, 12, 6, 10)[i])
            for i in range(5)]


def _plain_tokens(cfg, params, requests):
    """Greedy tokens of each request from plain jitted prefill and decode
    steps, in the engine's waves and with its left padding."""
    prefill = jax.jit(lambda p, b: T.prefill(p, cfg, b, max_len=MAX_LEN, remat="none"))
    decode = jax.jit(lambda p, s, b, i: T.decode_step(p, cfg, s, b, i))
    out = []
    for w in range(0, len(requests), BATCH):
        wave = requests[w: w + BATCH]
        plen = max(len(r.prompt) for r in wave)
        toks = np.zeros((BATCH, plen), np.int32)
        for j, r in enumerate(wave):
            toks[j, plen - len(r.prompt):] = r.prompt
        logits, state = prefill(params, {"tokens": jnp.asarray(toks)})
        cur = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        seqs = [np.asarray(cur)]
        for t in range(1, max(r.max_new_tokens for r in wave)):
            logits, state = decode(params, state, {"tokens": cur[:, None]},
                                   jnp.asarray(plen + t - 1, jnp.int32))
            cur = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            seqs.append(np.asarray(cur))
        seqs = np.stack(seqs, axis=1)
        out.extend(seqs[j, : r.max_new_tokens].tolist() for j, r in enumerate(wave))
    return out


def test_generate_matches_plain_steps(model):
    cfg, params = model
    reqs = _requests(1)
    got = [c.tokens for c in Engine(cfg, params, batch=BATCH, max_len=MAX_LEN).generate(reqs)]
    assert got == _plain_tokens(cfg, params, reqs)


def test_consecutive_generate_calls_match_plain_steps(model):
    cfg, params = model
    eng = Engine(cfg, params, batch=BATCH, max_len=MAX_LEN)
    for seed in (2, 3):
        reqs = _requests(seed)
        assert [c.tokens for c in eng.generate(reqs)] == _plain_tokens(cfg, params, reqs)


def test_decode_step_consumes_its_state(model):
    cfg, params = model
    eng = Engine(cfg, params, batch=BATCH, max_len=MAX_LEN)
    _, state = eng._prefill(params, {"tokens": jnp.ones((BATCH, 4), jnp.int32)})
    eng._decode(params, state, {"tokens": jnp.ones((BATCH, 1), jnp.int32)},
                jnp.asarray(4, jnp.int32))
    assert all(a.is_deleted() for a in jax.tree.leaves(state))
