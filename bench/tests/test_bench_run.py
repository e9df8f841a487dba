"""``bench/run.py`` fails, with no result line, where it finds no TPU,
and in a directory that holds only the benchmark; and the benchmark
file meets its own layout: every cell, configuration, traffic mix and
per-layer metric has its file."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "netflix-train",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_every_entry_has_its_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert cfg["chips"] in (1, 4)
    for w in bench["workloads"]:
        tr = json.loads((ROOT / "bench" / "traffic" /
                         f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "drivers" / f"{tr['driver']}.py").is_file()
    for e in bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{e['name']}.py").is_file()
    names = {e["name"] for e in bench["end_to_end"]}
    assert "setup_s" in names
    assert all(e["moves"] in names for e in bench["per_layer"])


@pytest.mark.parametrize("kind", ["TPU v9 imaginary"])
def test_unknown_device_kind_is_an_error(kind, monkeypatch):
    from bench import run

    class Dev:
        platform, device_kind = "tpu", kind

    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        run.device_check(1, {"TPU v5 lite": {}})
