"""The fused quantize pass under a mesh (``kernels/ops.py``): each quantized
operand's pass runs under ``shard_map`` over a (4, 1) FSDP mesh of four
virtual CPU devices, with a cross-chip ``pmax`` where an amax group spans
chips, and equals the one-device pass bit for bit; the mesh step's loss
and gradients agree with one device's; the routing census reads every
kernel role sharded.  One subprocess (``shard_qmm_case.py``) computes it
all: the device count must be set before JAX starts."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CROSS = ("wgrad_x_token", "wgrad_g_token", "wgrad_x_tensor")


@pytest.fixture(scope="module")
def case():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(HERE), "src"))
    p = subprocess.run([sys.executable, os.path.join(HERE,
                                                     "shard_qmm_case.py")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", [
    "fwd_x_token", "fwd_w_token", "fwd_x_block", "fwd_w_tile",
    "dgrad_g_token", "dgrad_w_token", "wgrad_x_token", "wgrad_g_token",
    "wgrad_x_block", "wgrad_g_block", "wgrad_x_tensor", "wgrad_x_token_640"])
def test_sharded_pass_equals_one_device_bitwise(case, name):
    assert case["passes"]["sound"][name]


def test_planted_fault_without_cross_chip_amax_fails_the_comparison(case):
    """With each chip's amax taken over its own shard, exactly the groups
    that span chips (per-token groups along the token axis, the whole
    tensor) quantize differently."""
    differ = sorted(k for k, ok in case["passes"]["fault"].items() if not ok)
    assert differ == sorted(CROSS)


def test_shard_plan(case):
    plans = case["passes"]["plans"]
    rows, rep = "PartitionSpec('data', None)", "PartitionSpec(None, None)"
    # token rows split like the batch; their groups lie inside a shard
    assert plans["fwd_x_block"] == [rows, rows, "()"]
    # a weight (d_model 384: a quarter, 96, would split its 128-groups) is
    # gathered whole before its pass, as the FSDP product needs it anyway
    assert plans["fwd_w_tile"] == [rep, rep, "()"]
    assert plans["dgrad_w_token"] == [rep, rep, "()"]
    # x^T's per-row groups span the token axis: a pmax over 'data', and
    # the output in the product's (K, M) layout
    assert plans["wgrad_x_token"] == [
        rows, "PartitionSpec(None, 'data')", "('data',)"]
    assert plans["wgrad_g_token"] == [rows, rows, "('data',)"]
    # 640 tokens over 4 chips is no whole number of 128-groups: gathered
    assert plans["wgrad_x_token_640"] == [rep, rep, "()"]


def test_mesh_step_loss_matches_one_device(case):
    """Every quantized operand is the one-device value, so the loss differs
    by the partitioned mean alone."""
    assert case["steps"]["pallas_sound"]["loss_gap"] < 1e-5


@pytest.mark.parametrize("kind,ok", [("sound", True), ("fault", False)])
def test_mesh_step_gradients_match_one_device(case, kind, ok):
    """Tolerance, with its reason: the dots' partitioned f32 accumulation
    order changes bf16 roundings, which the FP8 (E5M2) gradient quantizers
    amplify through the layers (up to 5% of a slice's norm here).  The QDQ
    path on the same mesh, partitioned by GSPMD, shows that same gap; the
    fused path may show no more than 1.25x it (plus 1e-3).  Leaving the
    cross-chip amax out exceeds it on the attention weights."""
    qdq = case["steps"]["qdq_sound"]["grad_gaps"]
    got = case["steps"][f"pallas_{kind}"]["grad_gaps"]
    assert set(got) == set(qdq)
    bad = {k: (got[k], qdq[k]) for k in qdq
           if got[k] > 1.25 * qdq[k] + 1e-3}
    assert (not bad) == ok, bad


def test_routing_census_reads_every_kernel_role_sharded(case):
    kernel = [c for c in case["census"] if c["route"] == "pallas"
              and (c["mode_a"] != "pass" or c["mode_b"] != "pass")]
    assert {(c["cls"], c["role"]) for c in kernel} == {
        (cls, role) for cls in ("attn", "ffn")
        for role in ("fwd", "dgrad", "wgrad")} - {("ffn", "dgrad")}
    assert all(c["pipeline"] == "two_pass" and c["sharded"] for c in kernel)
    assert not any(c["route"] == "qdq_fallback" for c in case["census"])
    # a cross-chip amax exactly where a per-token group spans the token
    # axis: the attention wgrad's FP8 operands
    assert {(c["cls"], c["role"]) for c in kernel if c["cross_amax"]} == {
        ("attn", "wgrad")}
    # the bf16 FFN dgrad runs no kernel, so nothing to shard
    dgrad = [c for c in case["census"] if c["role"] == "dgrad"
             and c["cls"] == "ffn"]
    assert dgrad and not any(c["sharded"] for c in dgrad)


def test_qlint_reads_the_mesh_census_clean(case):
    q = case["qlint"]
    assert q["n_violations"] == 0, q["violations"]
    assert q["n_fallbacks"] == 0
    pallas = [c for c in q["cells"] if c["route"] == "pallas"
              and (c["mode_a"] != "pass" or c["mode_b"] != "pass")]
    assert pallas and all(c["sharded"] for c in pallas)
