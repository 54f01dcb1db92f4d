"""The command refuses to run without a TPU, and without the program."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench_testutil import REPO


def _run(cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gru-xla.ac", "--seed", "2147483650",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_without_a_tpu():
    proc = _run(REPO)
    assert proc.returncode == 3, proc.stderr
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_fails_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    for path in ("bench", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(REPO, path), tmp_path / path, ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
