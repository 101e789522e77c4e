"""``run.py`` refuses to run where it cannot measure: without a TPU, and
in a directory that holds only the benchmark and not the program."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
ARGS = ["--workload", "cov3d-factor-left", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, str(script), *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT, BENCH / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, tmp_path / "benchmarks" / "chip" / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
