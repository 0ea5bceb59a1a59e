"""Off a TPU the command exits non-zero before running anything, and
prints no result."""
import json
import os
import shutil
import subprocess
import sys

import _bench

cells = _bench.module("cells")

ROOT = cells.ROOT
ARGS = ["bench/run.py", "--workload", "gpt2-774m-24l.train-fp4", "--seed",
        "3000000001", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "metrics" in obj)


def test_exits_nonzero_off_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for rel in cells.load_benchmark()["paths"]:
        shutil.copytree(os.path.join(ROOT, rel), tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    _no_result(p.stdout)
