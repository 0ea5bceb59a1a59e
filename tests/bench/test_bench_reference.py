"""The plain reference against the program at a tiny size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _bench
from tiny import cell as tiny_cell

check = _bench.module("check")
R = _bench.module("reference")
weights = _bench.module("weights")
Program = _bench.module("program").Program

FMTS = ["fp4_e2m1", "fp8_e4m3", "fp8_e5m2"]


@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("gran", ["token", "block", "tile"])
@pytest.mark.parametrize("axis", [0, 1])
def test_quantizer_matches_the_program(fmt, gran, axis):
    from repro.core.quantize import QuantSpec, qdq
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (256, 384)) * jnp.exp(
        jax.random.normal(k2, (256, 1)))
    a = qdq(x, QuantSpec(fmt, gran, 128), axis)
    b = R._qdq(x, (fmt, gran), axis)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("cls", ["attn", "ffn"])
def test_quantized_matmul_and_its_gradients_match(cls):
    from repro.core.qlinear import qmatmul
    from repro.core.recipe import RECIPES
    mm = RECIPES["paper_fp4"].for_class(cls)
    roles = R._Roles(R.RECIPES["paper_fp4"][cls])
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (256, 384))
    w = 0.05 * jax.random.normal(ks[1], (384, 256))
    g = jax.random.normal(ks[2], (256, 256))

    def prog(x, w):
        return jnp.sum(qmatmul(x, w, jnp.zeros(2, jnp.uint32), mm) * g)

    def ref(x, w):
        return jnp.sum(R._qmm(x, w, roles) * g)
    for a, b in zip(jax.grad(prog, (0, 1))(x, w),
                    jax.grad(ref, (0, 1))(x, w)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _gaps(cell, seed):
    prog = Program(cell)
    got, batches, _ = prog.first_steps(seed)
    ref = R.readings(cell, seed, batches)
    return check.gaps(got, ref, weights.slice_names(
        weights.layout(cell.model)))


def test_reference_is_the_programs_float32_bf16_step():
    """With the program in float32 and no quantized operand, program and
    reference compute the same model, loss, clipping and AdamW."""
    g = _gaps(tiny_cell(recipe="bf16", dtype="float32"), 5)
    assert g["loss_gap"] < 1e-5
    assert g["grad_gap"] < 1e-5
    assert g["update_gap"] < 1e-5


def test_reference_follows_the_programs_float32_fp4_step():
    """Quantized in float32, only the sums' order differs; where it flips
    a rounding the steps drift apart a little."""
    g = _gaps(tiny_cell(dtype="float32"), 5)
    assert g["loss_gap"] < 5e-3
    assert g["grad_gap"] < 5e-3
    assert g["update_gap"] < 5e-3


def test_control_is_not_correct():
    """The reference one precision step down, in the program's place,
    fails the check at this size (the cells' control, run small)."""
    cell = tiny_cell()
    prog = Program(cell)
    _, batches, _ = prog.first_steps(11)
    ref = R.readings(cell, 11, batches)
    low = R.readings(cell, 11, batches, lower=True)
    g = check.gaps(low, ref, weights.slice_names(weights.layout(
        cell.model)))
    correct, _ = check.verdict(g, cell.workload["limits"])
    assert not correct
