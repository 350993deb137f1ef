"""``chip_smoke.py --rehearse``: the chip script's own code, end to end, on
the CPU at smoke size with the kernels in interpret mode."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("extra", [[], ["--four-chips"]])
def test_chip_smoke_rehearsal(tmp_path, extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR", "EXACB_AUTOTUNE_CACHE")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--rehearse", *extra],
        capture_output=True, text=True, timeout=600, env=env, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 4 if extra else 1}}
    last_phase = "cut_one_vs_four" if extra else "kernel_ssd"
    assert f"[phase] {last_phase} ok" in proc.stdout


def test_chip_smoke_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a 'tpu' device" in proc.stderr


@pytest.mark.parametrize("env_dir", ["/elsewhere/jax-cache", None])
def test_compile_cache_goes_where_the_environment_says(monkeypatch, env_dir):
    import jax

    from repro.launch import cache

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache.enable_compile_cache()
    # Set: JAX reads the variable itself.  Unset: a fixed path in the checkout.
    assert updates == ([] if env_dir else
                       [("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))])


def test_serve_launcher_serves_tokens_and_refuses_embeddings(capsys):
    from repro.launch import serve

    assert serve.main(["--arch", "starcoder2-3b", "--smoke", "--requests", "2",
                       "--prompt-len", "4", "--max-new-tokens", "2", "--batch", "2",
                       "--max-len", "16"]) == 0
    assert "2 requests, 4 tokens" in capsys.readouterr().out
    with pytest.raises(SystemExit) as e:
        serve.main(["--arch", "musicgen-medium", "--smoke"])
    assert e.value.code == 2
    assert "serves token LMs" in capsys.readouterr().err
