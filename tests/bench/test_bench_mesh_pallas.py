"""The harness on a four-chip mesh through the fused quantize pass, on four
virtual CPU devices: against the sharded plain reference a sound run is
correct, and one with the exchange between chips left out is not."""
import json
import os
import subprocess
import sys

import pytest

import _bench

cells = _bench.module("cells")

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("case,want", [("sound", True), ("fault", False)])
def test_mesh_pallas_run(case, want):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(cells.ROOT, "src"))
    p = subprocess.run([sys.executable,
                        os.path.join(HERE, "mesh_pallas_case.py"), case],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is want, out["checked"]
