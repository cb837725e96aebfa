"""Off a TPU the command exits non-zero and prints no result."""

import os
import subprocess
import sys

from benchlib import manifest


def run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2-large.docs", "--seed", "7",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu():
    done = run(manifest.REPO)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "TPU" in done.stderr


def test_refuses_a_directory_without_the_program(tmp_path):
    import shutil

    shutil.copy(manifest.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(manifest.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    done = run(tmp_path)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
