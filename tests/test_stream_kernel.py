"""Single-pass streaming pipeline vs the two-pass default.

The contract (see ``fused_qmm``): the stream and two_pass pipelines
quantize every element to the same bits, so their telemetry stats are
**bit-identical** for every supported granularity pair, dtype, rounding
mode and trans layout.  The two-pass product is one XLA dot over the
whole reduction axis, so ``y`` is bit-identical where the streaming
kernel also takes a single K step (``bk == K``); with several K steps
only y's f32 accumulation order differs.

Everything runs in interpret mode on CPU (the fused_qmm default resolves
interpret from the backend inside ops.py; here we pass interpret=True
explicitly since we call the kernel module directly).
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qlinear import pallas_qmatmul, pallas_qmatmul_two_pass
from repro.core.recipe import MM_FFN_PAPER, MM_FP8
from repro.kernels.fp4_matmul import (default_pipeline, fused_qmm,
                                      stream_supported, use_pipeline)

# The module object (``repro.kernels.fp4_matmul`` the *package attribute*
# resolves to the re-exported function, not the module).
FM = importlib.import_module("repro.kernels.fp4_matmul")

M, N, K = 256, 256, 384
TILINGS = [(128, 128, 128), (256, 256, 384), (128, 256, 128)]
SEED_A = jnp.asarray(7, jnp.int32)
SEED_B = jnp.asarray(11, jnp.int32)


def _data(shape_a, shape_b, dtype=jnp.float32, seed=0):
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, shape_a, jnp.float32).astype(dtype)
    b = (jax.random.normal(kb, shape_b, jnp.float32) * 0.05).astype(dtype)
    return a, b


def _run_both(a, b, tiles=None, **kw):
    bm, bn, bk = tiles if tiles else (None, None, None)
    outs = {}
    for pipe in ("stream", "two_pass"):
        outs[pipe] = fused_qmm(a, b, bm=bm, bn=bn, bk=bk, pipeline=pipe,
                               interpret=True, **kw)
    return outs["stream"], outs["two_pass"]


def _assert_bits_equal(x, y, what=""):
    assert x.dtype == y.dtype, (x.dtype, y.dtype, what)
    np.testing.assert_array_equal(
        np.asarray(x).view(np.uint8), np.asarray(y).view(np.uint8),
        err_msg=f"bitwise mismatch: {what}")


def _assert_same_sums(x, y, what=""):
    """Equal up to the f32 accumulation order along K: a few f32 ulps of
    the largest |y|, plus one output ulp where the output is bf16."""
    assert x.dtype == y.dtype, (x.dtype, y.dtype, what)
    x32, y32 = np.asarray(x, np.float32), np.asarray(y, np.float32)
    scale = max(float(np.abs(y32).max()), 1.0)
    rtol = 2.0 ** -7 if x.dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(x32, y32, rtol=rtol, atol=1e-5 * scale,
                               err_msg=f"beyond accumulation order: {what}")


def _assert_y(ys, yt, one_k_step, what=""):
    """y bitwise where the streaming kernel took one K step, else equal
    up to the accumulation order."""
    if one_k_step:
        _assert_bits_equal(ys, yt, what)
    else:
        _assert_same_sums(ys, yt, what)


@pytest.mark.parametrize("tiles", TILINGS)
@pytest.mark.parametrize("dtype,sr", [
    (jnp.float32, False), (jnp.float32, True), (jnp.bfloat16, False),
], ids=["f32_rtn", "f32_sr", "bf16_rtn"])
def test_same_tiling_bit_exact(tiles, dtype, sr):
    """Stream vs two_pass: the full telemetry stats vectors bitwise, y
    bitwise at one K step and to accumulation order otherwise, with the
    stats epilogue not perturbing y."""
    a, b = _data((M, K), (K, N), dtype)
    kw = dict(a_mode="block", b_mode="tile", a_sr=sr, b_sr=sr,
              seed_a=SEED_A if sr else None, seed_b=SEED_B if sr else None)
    one_k_step = tiles[2] == K
    (ys, (sa_s, sb_s)), (yt, (sa_t, sb_t)) = _run_both(
        a, b, tiles, collect_stats=True, **kw)
    _assert_y(ys, yt, one_k_step, "y (stats on)")
    _assert_bits_equal(sa_s, sa_t, "stats_a")
    _assert_bits_equal(sb_s, sb_t, "stats_b")
    ys_off, yt_off = _run_both(a, b, tiles, **kw)
    _assert_y(ys_off, yt_off, one_k_step, "y (stats off)")
    _assert_bits_equal(ys_off, ys, "y stats-on vs stats-off")


def test_bf16_sr_bit_exact():
    a, b = _data((M, K), (K, N), jnp.bfloat16, seed=3)
    ys, yt = _run_both(a, b, (128, 128, 128), a_mode="block", b_mode="tile",
                       a_sr=True, b_sr=True, seed_a=SEED_A, seed_b=SEED_B)
    _assert_same_sums(ys, yt, "bf16 SR")


def test_pass_mode_dgrad_layout():
    """The dgrad role: both operands passthrough, RHS stored transposed."""
    g, w = _data((M, N), (K, N), jnp.bfloat16, seed=4)  # g @ w^T -> (M, K)
    ys, yt = _run_both(g, w, (128, 128, 128), a_mode="pass", b_mode="pass",
                       trans_b=True)
    _assert_same_sums(ys, yt, "pass/pass trans_b")


def test_wgrad_layout_fp8():
    """The wgrad role: LHS stored transposed, fp8 block pair."""
    x, g = _data((K, M), (K, N), seed=5)  # x^T @ g with trans_a
    ys, yt = _run_both(x, g, (128, 128, 128), a_mode="block", b_mode="block",
                       a_fmt="fp8_e4m3", b_fmt="fp8_e5m2", trans_a=True)
    _assert_same_sums(ys, yt, "block/block fp8 trans_a")


def test_token_granularity_falls_back_to_two_pass():
    """token/tensor need the whole reduction axis before scaling — stream
    auto-routes to two_pass, so pipeline="stream" must equal "two_pass"
    trivially (bitwise)."""
    assert not stream_supported("token", "tile")
    a, b = _data((256, 256), (256, 256), seed=6)
    ys, yt = _run_both(a, b, (128, 128, 128), a_mode="token",
                       b_mode="tensor", a_fmt="fp8_e4m3", b_fmt="fp8_e5m2")
    _assert_bits_equal(ys, yt, "token/tensor fallback")


def test_operand_cache_bit_exact(monkeypatch):
    """The VMEM operand caches (LHS row panel, full quantized RHS) are pure
    reuse optimizations: forcing either or both off (budget 0) must not
    change a single bit."""
    a, b = _data((M, K), (K, N), seed=7)
    kw = dict(a_mode="block", b_mode="tile", pipeline="stream",
              bm=128, bn=128, bk=128, interpret=True, collect_stats=True)
    y_ref, (sa_ref, sb_ref) = fused_qmm(a, b, **kw)
    for attrs in (("_AQ_CACHE_BYTES",), ("_BQ_CACHE_BYTES",),
                  ("_AQ_CACHE_BYTES", "_BQ_CACHE_BYTES")):
        with monkeypatch.context() as mp:
            for attr in attrs:
                mp.setattr(FM, attr, 0)
            # _fused_qmm's jit cache captured the cached kernel
            jax.clear_caches()
            y, (sa, sb) = fused_qmm(a, b, **kw)
            _assert_bits_equal(y_ref, y, f"y, cache off: {attrs}")
            _assert_bits_equal(sa_ref, sa, f"stats_a, cache off: {attrs}")
            _assert_bits_equal(sb_ref, sb, f"stats_b, cache off: {attrs}")
    jax.clear_caches()


@pytest.mark.parametrize("recipe,name", [(MM_FFN_PAPER, "ffn_paper"),
                                         (MM_FP8, "fp8")])
def test_qlinear_stream_vs_two_pass(recipe, name):
    """Through the training entry points: ``pallas_qmatmul`` under
    ``use_pipeline("stream")`` and ``pallas_qmatmul_two_pass`` agree
    bitwise on fwd AND the vjp (dgrad + wgrad): every dim is 128, one K
    step.  MM_FP8 exercises the token-granularity fallback."""
    key = jnp.zeros((2,), jnp.uint32)
    x, w = _data((128, 128), (128, 128), seed=8)
    c = jax.random.normal(jax.random.PRNGKey(9), (128, 128), jnp.float32)

    def run(f):
        y, vjp = jax.vjp(lambda p, q: f(p, q, key, recipe), x, w)
        dx, dw = vjp(c)
        return y, dx, dw

    with use_pipeline("stream"):
        ys, dxs, dws = run(pallas_qmatmul)
    yt, dxt, dwt = run(pallas_qmatmul_two_pass)
    _assert_bits_equal(ys, yt, f"{name} fwd")
    _assert_bits_equal(dxs, dxt, f"{name} dgrad")
    _assert_bits_equal(dws, dwt, f"{name} wgrad")


def test_use_pipeline_nesting():
    assert default_pipeline() == "two_pass"
    with use_pipeline("stream"):
        assert default_pipeline() == "stream"
        with use_pipeline("two_pass"):
            assert default_pipeline() == "two_pass"
        assert default_pipeline() == "stream"
    assert default_pipeline() == "two_pass"
    with pytest.raises(AssertionError):
        with use_pipeline("bogus"):
            pass


def test_stream_supported_matrix():
    for mode in ("pass", "block", "tile"):
        assert stream_supported(mode, "tile")
        assert stream_supported("block", mode)
    for mode in ("token", "tensor"):
        assert not stream_supported(mode, "tile")
        assert not stream_supported("block", mode)
