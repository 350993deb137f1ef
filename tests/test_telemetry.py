"""The engine's recorder: spans nest at each boundary of a wave's work, the
records agree with what ``generate`` returns, the tokens are those of the
engine without spans, the ring drops and counts, and each span is in a
profiler trace with its ids as stats."""

import collections
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import serve as launch_serve
from repro.models import params as P
from repro.serve import telemetry as tel
from repro.serve.engine import Completion, Engine, Request

BATCH = 3


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(configs.get_smoke("glm4-9b"), d_model=64, n_layers=2, d_ff=128,
                              vocab_size=128, dtype="float32")
    return cfg, P.init_params(cfg, jax.random.key(0))


def _requests():
    """Two waves of ``BATCH``: mixed lengths and budgets, one sampled, one
    with an end token."""
    rng = np.random.default_rng(3)
    return [Request(uid=i, prompt=rng.integers(1, 128, 6 + (i % 2)).astype(np.int32),
                    max_new_tokens=(3, 6, 4, 5, 2)[i], temperature=0.7 if i == 3 else 0.0,
                    eos_id=7 if i == 0 else None)
            for i in range(5)]


def _plain_generate(eng, requests):
    """The engine's waves without spans or an explicit wait: the reference
    for the tokens."""
    out = []
    for i in range(0, len(requests), eng.batch):
        wave = requests[i: i + eng.batch]
        n = len(wave)
        plen = max(len(r.prompt) for r in wave)
        toks = np.zeros((eng.batch, plen), np.int32)
        for j, r in enumerate(wave):
            toks[j, plen - len(r.prompt):] = r.prompt
        logits, state = eng._prefill(eng.params, {"tokens": jnp.asarray(toks)})
        completions = [Completion(r.uid, [], len(r.prompt)) for r in wave]
        live = np.ones(eng.batch, bool)
        live[n:] = False
        budget = max(r.max_new_tokens for r in wave)
        cur = eng._sample(logits[:, 0], wave)
        for j, r in enumerate(wave):
            completions[j].tokens.append(int(cur[j]))
        for t in range(1, budget):
            idx = jnp.asarray(plen + t - 1, jnp.int32)
            logits, state = eng._decode(eng.params, state, {"tokens": cur[:, None]}, idx)
            cur = eng._sample(logits[:, 0], wave)
            for j, r in enumerate(wave):
                if not live[j]:
                    continue
                tok = int(cur[j])
                completions[j].tokens.append(tok)
                if len(completions[j].tokens) >= r.max_new_tokens or (
                        r.eos_id is not None and tok == r.eos_id):
                    live[j] = False
            if not live.any():
                break
        out.extend(completions)
    return out


@pytest.fixture(scope="module")
def served(model):
    """One ``generate`` call of two waves, and the recorder's snapshot of it."""
    cfg, params = model
    tel.reset()
    outs = Engine(cfg, params, batch=BATCH, max_len=32, seed=5).generate(_requests())
    return outs, tel.snapshot()


def test_spans_nest_per_wave(served):
    outs, snap = served
    spans = snap["spans"]
    by_seq = {s.seq: s for s in spans}
    kids = collections.defaultdict(list)
    for s in spans:
        kids[s.parent].append(s)
    (gen,) = [s for s in spans if s.name == "engine.generate"]
    assert gen.parent is None
    waves = [s for s in spans if s.name == "engine.wave"]
    assert [w.parent for w in waves] == [gen.seq, gen.seq]
    reqs = _requests()
    recs = {r["uid"]: r for r in snap["requests"]}
    for w, wave in zip(waves, (reqs[:BATCH], reqs[BATCH:])):
        assert w.ids == {} and [recs[r.uid]["wave"] for r in wave] == [w.seq] * len(wave)
        budget = max(r.max_new_tokens for r in wave)
        names = [s.name for s in kids[w.seq]]
        assert names == ["engine.prefill"] + ["engine.step"] * (budget - 1)
        for s in kids[w.seq]:
            assert s.ids["wave"] == w.seq
            assert [c.name for c in kids[s.seq]] == ["engine.wait", "engine.read"]
            assert w.start_ns <= s.start_ns <= s.end_ns <= w.end_ns
        assert [s.ids["step"] for s in kids[w.seq]] == list(range(budget))
    assert all(s.parent is None or s.parent in by_seq for s in spans)


def test_records_match_the_tokens(served):
    outs, snap = served
    tokens = sum(len(o.tokens) for o in outs)
    assert sum(len(r["token_ns"]) for r in snap["requests"]) == tokens
    live = [s.ids["live"] for s in snap["spans"] if s.name == "engine.step"]
    assert len(live) == (6 - 1) + (5 - 1)
    assert all(1 <= n <= BATCH for n in live) and sum(live) == tokens - len(outs)
    (gen,) = [s for s in snap["spans"] if s.name == "engine.generate"]
    recs = {r["uid"]: r for r in snap["requests"]}
    for o in outs:
        t = recs[o.uid]["token_ns"]
        assert recs[o.uid]["start_ns"] == gen.start_ns
        assert len(t) == len(o.tokens) and t == sorted(t) and gen.start_ns < t[0] < gen.end_ns
    assert snap["dropped"] == 0
    line = launch_serve.summary(snap, BATCH)
    assert f"5 requests, {tokens} tokens" in line and "ttft p50" in line and "itl p50" in line
    assert f"decode slot use {100 * sum(live) / (len(live) * BATCH):.1f}%" in line


def test_tokens_equal_the_engine_without_spans(model, served):
    cfg, params = model
    outs, _ = served
    want = _plain_generate(Engine(cfg, params, batch=BATCH, max_len=32, seed=5), _requests())
    assert [(o.uid, o.tokens) for o in outs] == [(o.uid, o.tokens) for o in want]


def test_ring_drops_and_counts():
    rec = tel.Recorder(capacity=3)
    for i in range(5):
        with rec.span("s", i=i):
            pass
        rec.request(i, wave=1, start_ns=0)
    snap = rec.snapshot()
    assert [s.ids["i"] for s in snap["spans"]] == [2, 3, 4]
    assert [r["uid"] for r in snap["requests"]] == [2, 3, 4]
    assert snap["dropped"] == 4
    rec.reset()
    assert rec.snapshot() == {"spans": [], "requests": [], "dropped": 0}


def test_steps_in_the_profiler_trace_carry_their_ids(model, tmp_path):
    from jax.profiler import ProfileData

    cfg, params = model
    eng = Engine(cfg, params, batch=BATCH, max_len=32)
    eng.generate(_requests()[:2])       # compiled before the trace
    tel.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate(_requests())
    finally:
        jax.profiler.stop_trace()
    want = sorted(tuple(s.ids[k] for k in ("wave", "step", "pos", "live"))
                  for s in tel.snapshot()["spans"] if s.name == "engine.step")
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    got = sorted(tuple(dict(e.stats)[k] for k in ("wave", "step", "pos", "live"))
                 for p in ProfileData.from_file(path).planes if p.name.startswith("/host:")
                 for line in p.lines for e in line.events if e.name == "engine.step")
    assert got == want and len(want) == (6 - 1) + (5 - 1)
