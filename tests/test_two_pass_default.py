"""``fused_qmm`` with no pipeline given: quantize each operand once, then
XLA's dot.

For every granularity pair the training roles use, in every stored
layout, the default must resolve to ``two_pass``, quantize each operand
to exactly the bits ``quantize_panels`` gives, and meet the
``kernels/ref.py`` oracle (unfused QDQ of the effective operands, then
the dot).  All operands are bf16, the training dtype, so the product and
the oracle's dot see the same bf16 inputs: they agree bitwise where the
two dots also read them in the same layout, and to the f32 accumulation
order where the product reads a ``pass`` operand in its stored,
transposed layout.  Everything runs in interpret mode on the CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import QuantSpec
from repro.kernels.fp4_matmul import (fused_qmm, quantize_panels,
                                      resolve_pipeline)
from repro.kernels.ref import qmm_ref, quantize_panels_ref
from repro.kernels.rounding import hash_uniform

M, K, N = 256, 256, 128
SEED_A = jnp.asarray(7, jnp.int32)
SEED_B = jnp.asarray(11, jnp.int32)

# (a_mode, b_mode, a_fmt, b_fmt, sr): the FFN forward, the FP8 attention
# linears, the FP8 wgrad, the bf16 dgrad and packed-weight serving.
PAIRS = [
    ("block", "tile", "fp4_e2m1", "fp4_e2m1", False),
    ("token", "token", "fp8_e4m3", "fp8_e4m3", False),
    ("block", "block", "fp8_e4m3", "fp8_e5m2", False),
    ("pass", "pass", "bf16", "bf16", False),
    ("block", "pass", "fp4_e2m1", "bf16", False),
]
LAYOUTS = [(False, False), (True, False), (False, True), (True, True)]
CASES = [p + t for p in PAIRS for t in LAYOUTS]
# the full-FP4 wgrad: stochastic rounding on both operands, LHS transposed
CASES.append(("block", "block", "fp4_e2m1", "fp4_e2m1", True, True, False))


def _id(case):
    a_mode, b_mode, a_fmt, b_fmt, sr, ta, tb = case
    return (f"{a_mode}:{b_mode}-{a_fmt}:{b_fmt}{'-sr' if sr else ''}"
            f"-{'t' if ta else 'n'}{'t' if tb else 'n'}")


def _operands(trans_a, trans_b):
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (K, M) if trans_a else (M, K), jnp.float32)
    b = jax.random.normal(kb, (N, K) if trans_b else (K, N),
                          jnp.float32) * 0.05
    return a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)


def _bits(x):
    return np.asarray(x).view(np.uint16)


def _dot(a, b, trans_a=False, trans_b=False):
    dims = (((0 if trans_a else 1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32
                               ).astype(a.dtype)


@pytest.mark.parametrize("case", CASES, ids=[_id(c) for c in CASES])
def test_default_two_pass_quantizes_once(case):
    a_mode, b_mode, a_fmt, b_fmt, sr, trans_a, trans_b = case
    assert resolve_pipeline(None, a_mode, b_mode) == "two_pass"
    a, b = _operands(trans_a, trans_b)
    seeds = dict(seed_a=SEED_A, seed_b=SEED_B) if sr else {}
    y = fused_qmm(a, b, a_mode=a_mode, b_mode=b_mode, a_fmt=a_fmt,
                  b_fmt=b_fmt, a_sr=sr, b_sr=sr, trans_a=trans_a,
                  trans_b=trans_b, interpret=True, **seeds)
    assert y.shape == (M, N) and y.dtype == jnp.bfloat16

    # The quantize pass alone: A' in (M, K), B' read as (N, K) so its
    # groups reduce over K, then turned to (K, N); pass operands as stored.
    ta, tb = trans_a and a_mode == "pass", trans_b and b_mode == "pass"
    aq = a if a_mode == "pass" else quantize_panels(
        a, mode=a_mode, fmt_name=a_fmt, trans=trans_a, sr=sr,
        seed=SEED_A if sr else None)
    bq = b if b_mode == "pass" else quantize_panels(
        b, mode=b_mode, fmt_name=b_fmt, trans=not trans_b, sr=sr,
        seed=SEED_B if sr else None).T
    np.testing.assert_array_equal(_bits(y), _bits(_dot(aq, bq, ta, tb)),
                                  err_msg="operands differ from the pass")

    spec_a = QuantSpec(a_fmt) if a_mode == "pass" else QuantSpec(a_fmt,
                                                                  a_mode)
    spec_b = QuantSpec(b_fmt) if b_mode == "pass" else QuantSpec(b_fmt,
                                                                  b_mode)
    if sr:
        # The kernel's noise is keyed by each element's coordinate in the
        # quant orientation, so the oracle redraws it outside.
        ra = quantize_panels_ref(a, spec_a, trans=trans_a,
                                 noise=hash_uniform((M, K), SEED_A, 0, 0))
        rb = quantize_panels_ref(b, spec_b, trans=not trans_b,
                                 noise=hash_uniform((N, K), SEED_B, 0, 0))
        ref = _dot(ra, rb.T)
    else:
        ref = qmm_ref(a, b, spec_a, spec_b, trans_a=trans_a,
                      trans_b=trans_b)
    if ta or tb:
        y32, r32 = np.asarray(y, np.float32), np.asarray(ref, np.float32)
        np.testing.assert_allclose(
            y32, r32, rtol=2.0 ** -7,
            atol=1e-5 * max(float(np.abs(r32).max()), 1.0),
            err_msg="product differs from the oracle")
    else:
        np.testing.assert_array_equal(
            _bits(y), _bits(ref), err_msg="product differs from the oracle")
