"""The harness refuses to measure anywhere but on the chip."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from chipbench_util import BENCH, REPO

ARGS = ["--workload", "granite-1chip-s4096", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    return any(line.strip().startswith("{") for line in stdout.splitlines())


def test_cpu_device_exits_nonzero_without_a_result(tmp_path):
    out = _run(REPO, tmp_path)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
    assert "no TPU" in out.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    root = tmp_path / "co"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    out = _run(root, tmp_path)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
