"""Without a TPU a run exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

CELL = "splitme-dnn10-m50.sweep30x2"


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_cpu_only_fails_without_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_fail_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
