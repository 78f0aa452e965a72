"""``bench/run.py`` refuses to run, and prints no result, off a TPU and in a
checkout that lacks the system under test."""

import os
import shutil
import subprocess
import sys

from bench_tiny import BENCH, ROOT


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cosmo_all", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_refuses_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not in this checkout" in p.stderr
