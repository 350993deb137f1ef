"""The trace reduction on a hand-built trace: busy union, idle share,
kernel time and idle gaps named by host spans."""

import pytest

from chipbench import readings as R
from chipbench import trace as T

US = 1000.0   # ns


def _planes():
    host = T.Plane("/host:CPU", [T.Line("python", [
        (T.WINDOW_MARK, 0 * US, 100 * US),
        ("chipbench.generate", 0 * US, 60 * US),
        ("chipbench.wait_arrival", 60 * US, 40 * US),
        ("np.asarray(jax.Array)", 41 * US, 8 * US),
    ])])
    dev0 = T.Plane("/device:TPU:0", [
        T.Line("XLA Ops", [
            ("fusion.1", -5 * US, 15 * US),            # clipped to 0..10
            ("_flash_kernel", 5 * US, 10 * US),        # overlaps: busy 0..15
            ("all-reduce.3", 20 * US, 10 * US),        # 20..30
            ("fusion.1", 40 * US, 10 * US),            # 40..50
            ("%while.1 = (s32[]) while(...)", 88 * US, 30 * US),   # holds fusion.2
            ("%fusion.2 = bf16[2] fusion(...)", 90 * US, 20 * US),   # clipped to 90..100
        ]),
        T.Line("XLA Modules", [("jit_step", 0, 100 * US)]),
    ])
    dev1 = T.Plane("/device:TPU:1", [T.Line("XLA Ops", [
        ("all-gather.1", 0 * US, 50 * US),
        ("%async-collective-done = (bf16[2]) async-done(...)", 50 * US, 10 * US),
    ])])
    return [host, dev0, dev1]


def test_union_and_gaps():
    assert T.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert T.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_summary_by_hand():
    s = T.summarize(_planes(), kernel="flash")
    ns = 1e-9
    assert s["devices"] == 2
    assert s["window_s"] == pytest.approx(100 * US * ns)
    busy0 = (15 + 10 + 10 + 12) * US           # 0..15, 20..30, 40..50, 88..100
    busy1 = 60 * US
    assert s["busy_s"] == pytest.approx((busy0 + busy1) / 2 * ns)
    assert s["kernel_s"] == pytest.approx(10 * US / 2 * ns)
    assert s["kernel_events"] == 0.5
    # The idle gaps of device 0, longest first, named by the innermost span.
    # 50..88 wait_arrival, 30..40 (middle 35) generate, 15..20 generate
    assert [g[0] for g in s["idle_gaps"]] == ["wait_arrival", "generate", "generate"]
    assert s["idle_gaps"][0][1] == pytest.approx(38 * US * ns)
    ops = dict(s["device_ops"])
    assert "%while.1" not in ops and ops["%fusion.2"] == pytest.approx(10 * US / 2 * ns)
    assert s["device_ops"][0] == ["all-gather.1", pytest.approx(50 * US / 2 * ns)]


def test_idle_share_reader():
    s = T.summarize(_planes(), kernel="flash")

    class Run:
        trace = s
    want = 100 * (1 - s["busy_s"] / s["window_s"])
    assert R.idle_percent(Run) == pytest.approx(want)


def test_no_window_mark_or_device_gives_nothing():
    host, dev0, _ = _planes()
    assert T.summarize([dev0]) is None
    assert T.summarize([host]) is None
