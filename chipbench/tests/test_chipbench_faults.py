"""With the timed path broken underneath, a run drives to its end and
``correct`` comes out false, once for each fault the cell can have."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


CASES = [
    ("qwen3-4b-complete-poisson", "state_unchanged", None),
    ("qwen3-4b-complete-poisson", "half_batch", "400"),     # full waves: every slot in use
    ("qwen3-4b-complete-poisson", "token_altered", None),
]


@pytest.mark.parametrize("cell,fault,rate", CASES)
def test_fault_makes_run_incorrect(cell, fault, rate):
    args = [sys.executable, str(HERE / "fault_child.py"), fault, cell, "11"]
    if rate:
        args.append(rate)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(args, capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False
    assert any(c["value"] is None or c["value"] > c["limit"] for c in result["checks"].values())
    assert list(result)[-1] == "checks"
    assert proc.stderr.strip().splitlines()[-1].startswith("[check]")
