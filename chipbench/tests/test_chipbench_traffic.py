"""The traffic generator: one schedule of arrivals and sizes for every run
seed, prompts of the seed's own, and the compared sample."""

import numpy as np

from chipbench import traffic as TR

OPEN = {"arrivals": "poisson", "rate_rps": 2.0, "prompt_len": 8,
        "output": {"dist": "lognormal", "median": 32, "sigma": 0.7, "min": 8, "max": 128}}


def test_open_loop_schedule_is_the_same_for_every_seed():
    a = TR.open_loop(OPEN, seed=1, seconds=50, vocab=100)
    b = TR.open_loop(OPEN, seed=2**33 + 1, seconds=50, vocab=100)
    assert len(a) == len(b) == 100
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    arrivals = np.array([r.arrival_s for r in a])
    assert (np.diff(arrivals) > 0).all() and 0 < arrivals[0] and arrivals[-1] < 50
    assert all(1 <= t < 100 for r in a for t in r.prompt)
    assert sorted(r.max_new_tokens for r in a) == list(TR.output_lengths(OPEN["output"], 100))


def test_output_lengths_are_stratified_and_clipped():
    outs = TR.output_lengths(OPEN["output"], 1000)
    assert outs.min() >= 8 and outs.max() <= 128
    assert abs(np.median(outs) - 32) <= 1
    assert (np.diff(outs) >= 0).all()
    assert list(TR.output_lengths({"len": 5}, 3)) == [5, 5, 5]


def test_waves_and_check_sample():
    s = TR.check_sample(20, 5, seed=9, longest=13)
    assert 13 in s and len(set(s)) == 5 and s == sorted(s)
    assert s == TR.check_sample(20, 5, seed=9, longest=13)
    assert s != TR.check_sample(20, 5, seed=2**33 + 9, longest=13)
