"""Each cell end to end at rehearsal sizes on the CPU, and the control,
which has to fail the check."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import loader, run

RUN = str(loader.ROOT / "chipbench" / "run.py")
CALIBRATE = str(loader.ROOT / "chipbench" / "calibrate.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _last_json(args):
    proc = subprocess.run([sys.executable, *args], capture_output=True, text=True, env=ENV,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc, json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,trace", [("qwen3-4b-complete-poisson", 0),
                                        ("qwen3-4b-complete-poisson", 1)])
def test_rehearse_cell(cell, trace):
    c = loader.load_cell(cell, trace=bool(trace))
    proc, out = _last_json([RUN, "--workload", cell, "--seed", str(2**31 + 12345),
                            "--seconds", "1", "--trace", str(trace), "--rehearse"])
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == c.chips
    reported = set(out["metrics"])
    assert reported <= {m.name for m in c.metrics}
    if not trace:       # every end-to-end metric; device ones need the chip
        assert reported == {m.name for m in c.metrics}
    for name, chk in out["checks"].items():
        assert chk["value"] <= chk["limit"]
        assert f"[check] {name} " in proc.stderr


def test_control_fails_the_check():
    """The reference in float8 put in the program's place fails at least one
    number at its limit; the program itself passes every one."""
    cell = "qwen3-4b-complete-poisson"
    limits = run.check_limits(loader.load_cell(cell, trace=False).workload, rehearse=True)
    proc = subprocess.run([sys.executable, CALIBRATE, "--workload", cell, "--seconds", "1",
                           "--seeds", "3", "4", "--rehearse"],
                          capture_output=True, text=True, env=ENV, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines() if l.startswith('{"seed"')]
    assert len(rows) == 2
    for r in rows:
        assert all(r["program"][k] <= limits[k] for k in limits)
        assert any(r["control"][k] > limits[k] for k in limits)
