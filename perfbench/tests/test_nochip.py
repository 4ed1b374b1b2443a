"""Without an accelerator, or without the program, a run prints no result."""

import os
import shutil
import subprocess
import sys

from perfbench import run

ARGS = ["--workload", "w8a-topk-solve", "--seed", "2147483713", "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join("perfbench", "run.py"), *ARGS],
                          cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    return p.returncode != 0 and '"correct"' not in p.stdout


def test_no_accelerator_no_result():
    p = _run(run.ROOT)
    assert _no_result(p), p.stdout
    assert "no accelerator" in p.stderr


def test_only_benchmark_files_no_result(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert _no_result(p)
    assert "No module named 'repro'" in p.stderr
