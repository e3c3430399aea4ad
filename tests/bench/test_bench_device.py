"""The peak table refuses a device it does not know; the harness refuses to
run without a TPU."""
import json
from pathlib import Path
import os
import shutil
import subprocess
import sys

import pytest

from bench.harness import device

REPO = Path(__file__).resolve().parents[2]



def test_known_kind():
    p = device.Peaks.of("TPU v5 lite")
    assert p.flops == 197e12 and p.hbm_bw == 819e9


def test_unknown_kind_refused():
    with pytest.raises(KeyError):
        device.Peaks.of("TPU v99")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "danube3.decode_heavy",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_no_tpu_no_result():
    r = _run(REPO)
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "needs a TPU" in r.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert _no_result(r.stdout)
