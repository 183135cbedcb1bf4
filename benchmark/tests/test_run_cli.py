"""run.py prints no result and exits non-zero without a TPU, and in a
directory that holds only BENCHMARK.json and the benchmark's files."""

import os
import shutil
import subprocess
import sys

from benchmark.tests.small import REPO

ARGS = ["--workload", "basic-saturated", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_device():
    p = _run(REPO)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(root)
    assert p.returncode != 0
    assert not p.stdout.strip()
