"""Without a TPU, or without the engine's sources, the command exits
non-zero and prints no result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ARGS = ["--workload", "lubm-uba-l.load", "--seed", str(2**34 + 1),
        "--seconds", "1", "--trace", "0"]


def run_in(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def assert_no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_cpu_only_exits_without_a_result():
    p = run_in(harness.ROOT)
    assert_no_result(p)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_without_a_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_in(tmp_path)
    assert_no_result(p)
    assert "no engine sources" in p.stderr
