"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps.

Assignment requirement: "For each Pallas kernel, sweep shapes/dtypes and
assert_allclose against the ref.py pure-jnp oracle."
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, fp4_matmul, quantize_blockwise
from repro.kernels.ref import (flash_attention_ref, fp4_matmul_ref,
                               quantize_blockwise_ref)

MM_SHAPES = [(128, 128, 128), (256, 384, 128), (200, 300, 260),
             (64, 500, 70), (128, 129, 127)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    # bf16 inputs land EXACTLY on RTN tie points (e.g. x/amax == 0.75), and
    # the in-Pallas division can differ by 1 ulp from the oracle's, flipping
    # a tie by one grid step (verified: xq grids agree everywhere except
    # exact ties).  Amax scales are tie-fragile by nature; pow2 scales are
    # exact.  Tolerance = a few flipped E2M1 ties per reduction.
    return dict(rtol=6e-2, atol=6e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fp4_matmul_sweep(m, k, n, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 7 + n))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.05
    y = fp4_matmul(x.astype(dtype), w.astype(dtype))
    ref = fp4_matmul_ref(x.astype(dtype), w.astype(dtype))
    scale = max(float(jnp.abs(ref.astype(jnp.float32)).max()), 1.0)
    np.testing.assert_allclose(np.asarray(y, np.float32) / scale,
                               np.asarray(ref, np.float32) / scale,
                               **_tol(dtype))


@pytest.mark.parametrize("fmt", ["fp4_e2m1", "fp8_e4m3", "fp8_e5m2"])
@pytest.mark.parametrize("shape", [(128, 128), (256, 384), (130, 70)])
def test_quantize_blockwise_sweep(fmt, shape):
    x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    y = quantize_blockwise(x, fmt, 128)
    ref = quantize_blockwise_ref(x, fmt, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-6)


def test_quantize_per_row_matches_block_spec():
    from repro.core.quantize import QuantSpec, qdq
    x = jax.random.normal(jax.random.PRNGKey(2), (256, 256), jnp.float32)
    y = quantize_blockwise(x, "fp4_e2m1", 128, per_row=True)
    ref = qdq(x, QuantSpec("fp4_e2m1", "block", 128), 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-6)


# kvh 4 of 4 heads is MHA, 2 GQA, 1 MQA; at 768 the tiles are 256, so the
# causal mask skips whole tiles and masks the diagonal ones in part.
@pytest.mark.parametrize("s,kvh", [(128, 4), (256, 2), (128, 1), (768, 2)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(s, kvh, causal):
    b, h, d = 2, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(s + kvh), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    o = flash_attention(q, k, v, causal=causal)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def test_flash_attention_bf16():
    b, s, h, d = 1, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)
    o = flash_attention(q, k, v)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32), rtol=3e-2,
                               atol=3e-2)


@pytest.mark.parametrize("h,kvh,causal", [(2, 2, True), (4, 1, True),
                                          (4, 2, False)])
def test_flash_attention_grads_match_ref(h, kvh, causal):
    """The dq and dkv kernels; with MQA and GQA, dk and dv sum over each
    KV head's group of query heads."""
    b, s, d = 1, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)

    def f(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                        argnums=(0, 1, 2))(q, k, v)

    g = f(lambda q, k, v: flash_attention(q, k, v, causal=causal))
    gr = f(lambda q, k, v: flash_attention_ref(q, k, v, causal=causal))
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("m,k,n", [(130, 129, 127), (1, 1, 1),
                                   (127, 257, 383)])
def test_fp4_matmul_padding_eps_floor_regression(m, k, n):
    """Non-multiple-of-128 shapes in ALL three dims: ops.py zero-pads, and
    the padded K-tail makes the weight's last (128 x 128) tile mostly (or,
    with a zeroed-out input region, entirely) zero — quantize_tile must take
    the _EPS-floor scale path and contribute exactly nothing, matching the
    oracle's identically-padded blocked view."""
    kx, kw = jax.random.split(jax.random.PRNGKey(k))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.05
    # Zero the real K-tail of w so the padded tile is ALL zero (pure
    # eps-floor path), not just zero-padded.
    if k > 128:
        w = w.at[128:].set(0.0)
        x = x.at[:, 128:].set(jnp.abs(x[:, 128:]) + 1.0)  # nonzero partner
    y = fp4_matmul(x, w)
    ref = fp4_matmul_ref(x, w)
    assert y.shape == (m, n)
    assert bool(jnp.isfinite(y).all())
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_fp4_matmul_mixed_formats():
    """x FP8 + w FP4 (the paper's wgrad setting) also matches ref."""
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 128),
                          jnp.float32) * 0.05
    y = fp4_matmul(x, w, x_fmt="fp8_e4m3", w_fmt="fp4_e2m1")
    ref = fp4_matmul_ref(x, w, x_fmt="fp8_e4m3", w_fmt="fp4_e2m1")
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
