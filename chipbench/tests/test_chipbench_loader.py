"""The loader finds each piece of a cell by file name; a new architecture
family, configuration, traffic mix, cell, driver and metric are added by
adding files; a run refuses a host without the chips or peaks it needs;
``BENCHMARK.json`` keeps the benchmark's format."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import loader

ROOT = loader.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
@pytest.mark.parametrize("trace", [False, True])
def test_each_cell_loads(cell, trace):
    c = loader.load_cell(cell, trace=trace)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.chips == entry["chips"]
    assert c.config["name"] == entry["config"]
    assert hasattr(c.driver, "setup") and hasattr(c.driver, "run")
    kind = "per_layer" if trace else "end_to_end"
    want = [m["name"] for m in BENCH[kind] if cell in m.get("workloads", [cell])]
    assert [m.name for m in c.metrics] == want
    assert all(callable(m.read) for m in c.metrics)


def test_unknown_cell_and_device_kind_are_refused():
    with pytest.raises(KeyError):
        loader.load_cell("no-such-cell", trace=False)
    with pytest.raises(KeyError, match="peaks.json"):
        loader.peaks_for("TPU v9 imaginary")
    assert loader.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_config_without_known_family_is_refused(tmp_path, family):
    cfg = json.loads((ROOT / "chipbench" / "configs" / "qwen3-4b.json").read_text())
    cfg.pop("family")
    if family:
        cfg["family"] = family
    path = tmp_path / "chipbench" / "configs" / "toy.json"
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=re.escape(f"{path}: 'family'")):
        loader.load_family(path)


def test_run_refuses_host_without_tpu(capsys):
    from chipbench import run

    assert run.main(["--workload", "qwen3-4b-complete-poisson", "--seed", "1",
                     "--seconds", "1"]) == 2
    out, err = capsys.readouterr()
    assert "tpu" in err and "{" not in out


def test_run_refuses_device_kind_without_peaks(monkeypatch):
    import jax

    from chipbench import run

    class Fake:
        platform, device_kind = "tpu", "TPU v9 imaginary"
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    cell = loader.load_cell("qwen3-4b-complete-poisson", trace=False)
    with pytest.raises(run.NoDevice, match="peaks.json"):
        run.open_devices(cell, False)


def test_benchmark_json_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert all(not w.startswith("/") and ".." not in w for w in BENCH["command"])
    n = len(BENCH["workloads"])
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, n // 2)
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("chipbench/")
        assert all(NAME.match(k) for k in c["reduced"])
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
        assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").is_file()
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["name"] not in names
        names.add(m["name"])
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        moved = e2e[m["moves"]]
        assert all(w in moved.get("workloads", [w]) for w in m["workloads"])
        if m["name"].split(".")[0].endswith(("_roofline", "mfu")):
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        reported = [m for m in BENCH["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2
        assert any(w["name"] in m["workloads"] for m in BENCH["per_layer"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_new_cell_driver_metric_and_config_are_only_added_files(tmp_path):
    """Copies the benchmark, adds one file of each kind, a family among them,
    and runs the new cell at rehearsal sizes through the new family; no file
    that was there is edited."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    pkg = tmp_path / "chipbench"
    before = {p: p.read_bytes() for p in pkg.rglob("*") if p.is_file()}
    cfg = json.loads((pkg / "configs" / "qwen3-4b.json").read_text())
    cfg["name"], cfg["family"] = "toy-config", "toy_family"
    (pkg / "families" / "toy_family.py").write_text(
        (pkg / "families" / "dense_gqa.py").read_text()
        + "\n\n_sizes = sizes\n\n\ndef sizes(config, rehearse):\n"
          "    print('[family] toy_family', flush=True)\n"
          "    return _sizes(config, rehearse)\n")
    (pkg / "configs" / "toy-config.json").write_text(json.dumps(cfg))
    (pkg / "traffic" / "toy-traffic.json").write_text(json.dumps(
        {"arrivals": "poisson", "rate_rps": 10, "prompt_len": 16,
         "output": {"dist": "lognormal", "median": 4, "sigma": 0.5, "min": 2, "max": 6}}))
    wl = json.loads((pkg / "workloads" / "qwen3-4b-complete-poisson.json").read_text())
    wl["driver"] = "toy_driver"
    wl["rehearse"] = {"engine": {"batch": 2, "max_len": 24}}
    (pkg / "workloads" / "toy-cell.json").write_text(json.dumps(wl))
    shutil.copy(pkg / "drivers" / "open_loop_engine.py", pkg / "drivers" / "toy_driver.py")
    (pkg / "metrics" / "toy_calls.py").write_text(
        "def read(run):\n    return float(len(run.record['calls']))\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "toy-config",
                             "file": "chipbench/configs/toy-config.json"})
    bench["workloads"].append({"name": "toy-cell", "config": "toy-config",
                               "traffic": "toy-traffic", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "toy_calls", "unit": "calls", "better": "lower",
                                "bound": 0.25, "source": "host_clock",
                                "workloads": ["toy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = loader.load_cell("toy-cell", trace=False, root=tmp_path)
    assert c.config["name"] == "toy-config" and c.traffic["prompt_len"] == 16
    assert c.family.__file__ == str(pkg / "families" / "toy_family.py")
    assert [m.name for m in c.metrics] == ["setup_s", "toy_calls"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, str(pkg / "run.py"), "--workload", "toy-cell", "--seed", "5",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["toy_calls"]["value"] >= 1
    assert "[family] toy_family" in proc.stdout.splitlines()
    assert {p: p.read_bytes() for p in before} == before
