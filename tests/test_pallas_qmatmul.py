"""Fused-kernel training path: ``pallas_qmatmul`` (fwd + dgrad + wgrad)
vs the unfused ``qmatmul`` QDQ reference, across the paper's recipes.

All Pallas calls run in interpret mode on CPU (the ops.py default), so
these are exact-code-path parity tests against ``dot_qdq``: same amax
groups, same RTN grid, only f32 dot accumulation order differs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig, get_config
from repro.core.qlinear import (kernel_quant_mode, matmul_impl,
                                pallas_qmatmul, qmatmul)
from repro.core.quantize import QuantSpec
from repro.core.recipe import (MM_FP4_ALL, MM_FFN_PAPER, MM_FP8,
                               MatmulRecipe, RECIPES)
from repro.kernels.ops import pallas_qmm
from repro.kernels.ref import qmm_ref

KEY0 = jnp.zeros((2,), jnp.uint32)
RECIPE_CASES = [("fp8", MM_FP8), ("fp4_all", MM_FP4_ALL),
                ("ffn_paper", MM_FFN_PAPER)]
SHAPES = [(128, 128, 128), (200, 300, 260), (64, 500, 70)]


def _data(m, k, n, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.05
    return x, w


def _close(a, b, rtol=1e-5, atol=1e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = max(float(np.abs(b).max()), 1.0)
    np.testing.assert_allclose(a / scale, b / scale, rtol=rtol, atol=atol)


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("rname,recipe", RECIPE_CASES)
def test_forward_parity(rname, recipe, m, k, n):
    x, w = _data(m, k, n)
    _close(pallas_qmatmul(x, w, KEY0, recipe), qmatmul(x, w, KEY0, recipe))


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("rname,recipe", RECIPE_CASES)
def test_gradient_parity(rname, recipe, m, k, n):
    """fwd + dgrad + wgrad parity via jax.grad on a scalar loss.

    The loss is linear in y (sum(y * c)) so both implementations see the
    SAME backward cotangent; a nonlinear loss would feed each its own
    slightly-different y and FP4 rounding-tie flips would dominate.
    """
    x, w = _data(m, k, n, seed=1)
    c = jax.random.normal(jax.random.PRNGKey(2), (m, n), jnp.float32)

    def loss(f):
        return jax.grad(lambda a, b: jnp.sum(f(a, b, KEY0, recipe) * c),
                        argnums=(0, 1))(x, w)

    (dx_p, dw_p), (dx_q, dw_q) = loss(pallas_qmatmul), loss(qmatmul)
    _close(dx_p, dx_q)
    _close(dw_p, dw_q)


@pytest.mark.parametrize("rname,recipe", RECIPE_CASES)
def test_bf16_parity_one_ulp(rname, recipe):
    """In bf16 (the training dtype) the kernel quantizes in the input dtype
    exactly like the qdq path, so fwd/dgrad/wgrad agree to ~1 output ulp
    (dot accumulation order is the only remaining difference)."""
    kx, kw, kc = jax.random.split(jax.random.PRNGKey(8), 3)
    x = jax.random.normal(kx, (200, 260), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(kw, (260, 140), jnp.float32)
         * 0.05).astype(jnp.bfloat16)
    c = jax.random.normal(kc, (200, 140), jnp.float32).astype(jnp.bfloat16)

    def run(f):
        y, vjp = jax.vjp(lambda a, b: f(a, b, KEY0, recipe), x, w)
        return (y,) + vjp(c)

    for p, q in zip(run(pallas_qmatmul), run(qmatmul)):
        _close(p, q, rtol=2e-2, atol=2e-2)  # 1-2 bf16 ulps, normalized


def test_ffn_paper_dgrad_is_bf16_passthrough():
    """MM_FFN_PAPER: the dgrad role is unquantized — the fused path must
    produce the plain g @ w^T (f32-accumulated), not a quantized one."""
    x, w = _data(128, 256, 128)
    g = jax.random.normal(jax.random.PRNGKey(3), (128, 128), jnp.float32)
    _, vjp = jax.vjp(lambda a, b: pallas_qmatmul(a, b, KEY0, MM_FFN_PAPER),
                     x, w)
    dx, _ = vjp(g)
    _close(dx, g @ w.T)


@pytest.mark.parametrize("trans_a,trans_b", [(False, False), (False, True),
                                             (True, False)])
def test_transposed_operand_variants_match_oracle(trans_a, trans_b):
    """The kernel's in-VMEM transposition quantizes relative to the
    effective (post-transpose) reduction axis — exactly qmm_ref."""
    spec_a = QuantSpec("fp4_e2m1", "block")
    spec_b = QuantSpec("fp8_e5m2", "block")
    ka, kb = jax.random.split(jax.random.PRNGKey(4))
    a = jax.random.normal(ka, (200, 140) if trans_a else (140, 200),
                          jnp.float32)
    b = jax.random.normal(kb, (75, 200) if trans_b else (200, 75),
                          jnp.float32)
    y = pallas_qmm(a, b, spec_a, spec_b,
                   mode_a=kernel_quant_mode(spec_a),
                   mode_b=kernel_quant_mode(spec_b),
                   trans_a=trans_a, trans_b=trans_b)
    _close(y, qmm_ref(a, b, spec_a, spec_b, trans_a=trans_a,
                      trans_b=trans_b))


@pytest.mark.parametrize("gran_a,gran_b", [("token", "token"),
                                           ("tensor", "tile"),
                                           ("block", "token")])
def test_scaled_granularities_match_oracle(gran_a, gran_b):
    """token/tensor amax groups span the whole reduction axis; the quantize
    pass computes them in-kernel with a two-sweep grid (sweep 0 accumulates
    amax in scratch, sweep 1 quantizes)."""
    spec_a = QuantSpec("fp8_e4m3", gran_a)
    spec_b = QuantSpec("fp8_e4m3", gran_b)
    a, b = _data(130, 260, 70, seed=5)
    y = pallas_qmm(a, b, spec_a, spec_b,
                   mode_a=kernel_quant_mode(spec_a),
                   mode_b=kernel_quant_mode(spec_b))
    _close(y, qmm_ref(a, b, spec_a, spec_b))


def test_unsupported_spec_falls_back_to_qdq():
    """fp16 (clip-only codec) and non-128 blocks aren't kernel-realizable;
    those roles must fall back to dot_qdq (identical results)."""
    assert kernel_quant_mode(QuantSpec("fp16")) is None
    assert kernel_quant_mode(QuantSpec("fp4_e2m1", "block", block=64)) is None
    fb = MatmulRecipe(fwd_x=QuantSpec("fp16"),
                      fwd_w=QuantSpec("fp4_e2m1", "tile"))
    x, w = _data(128, 128, 128, seed=6)
    key = jax.random.key_data(jax.random.PRNGKey(7)).astype(jnp.uint32)
    np.testing.assert_allclose(np.asarray(pallas_qmatmul(x, w, key, fb)),
                               np.asarray(qmatmul(x, w, key, fb)),
                               rtol=1e-6, atol=1e-6)


def test_stochastic_specs_are_kernel_realizable():
    """Since the quantize-once rework, stochastic rounding runs in-kernel:
    kernel_quant_mode no longer disqualifies SR specs."""
    assert kernel_quant_mode(
        QuantSpec("fp4_e2m1", "block", stochastic=True)) == "block"
    assert kernel_quant_mode(
        QuantSpec("fp8_e5m2", "token", stochastic=True)) == "token"


def test_full_fp4_recipe_zero_qdq_fallbacks():
    """The full-FP4 recipe (stochastic wgrad_g) must run ALL THREE roles
    through the Pallas path: every operand spec is kernel-realizable."""
    recipe = RECIPES["fine_grained_fp4"].ffn_linear
    for slot in ("fwd_x", "fwd_w", "dgrad_g", "dgrad_w",
                 "wgrad_x", "wgrad_g"):
        assert kernel_quant_mode(getattr(recipe, slot)) is not None, slot
    assert recipe.wgrad_g.stochastic  # the role that used to fall back
    # And the whole fwd+bwd actually executes through the kernel pipeline.
    x, w = _data(128, 256, 128, seed=9)
    key = jax.random.key_data(jax.random.PRNGKey(11)).astype(jnp.uint32)
    y, vjp = jax.vjp(lambda a, b: pallas_qmatmul(a, b, key, recipe), x, w)
    dx, dw = vjp(jnp.ones_like(y))
    for t in (y, dx, dw):
        assert bool(jnp.isfinite(t).all())


def test_in_kernel_sr_mean_unbiased_vs_qdq_reference():
    """In-kernel stochastic rounding (counter-hash noise) must be mean-
    unbiased like the QDQ SR reference: averaging Q_sr(x) over seeds
    converges to x, and the two means agree within sampling error."""
    spec = QuantSpec("fp4_e2m1", "block", stochastic=True)
    recipe = MatmulRecipe(fwd_x=spec, fwd_w=QuantSpec("bf16"))
    x = jax.random.uniform(jax.random.PRNGKey(3), (128, 128), jnp.float32,
                           0.05, 4.0)
    w = jnp.eye(128, dtype=jnp.float32)  # y = Q_sr(x) @ I isolates Q_sr(x)
    n = 48
    acc_k = jnp.zeros_like(x)
    acc_q = jnp.zeros_like(x)
    for s in range(n):
        key = jax.random.key_data(
            jax.random.PRNGKey(1000 + s)).astype(jnp.uint32)
        acc_k = acc_k + pallas_qmatmul(x, w, key, recipe)
        acc_q = acc_q + qmatmul(x, w, key, recipe)
    mean_k, mean_q = np.asarray(acc_k) / n, np.asarray(acc_q) / n
    xs = np.asarray(x)
    # Per-element grid step bound: scale * 2 (top-binade step of E2M1 on a
    # per-row amax scale <= 4/6); CLT tolerance ~ step * 4 / sqrt(12 n).
    step = np.abs(xs).max(1, keepdims=True) / 6.0 * 2.0
    tol = step * 4.0 / np.sqrt(12.0 * n) + 1e-3
    assert np.abs(mean_k - xs).mean() < np.abs(step).mean() * 0.2
    assert (np.abs(mean_k - mean_q) < 2 * tol).mean() > 0.99
    # global bias averages out across 16k elements
    assert abs((mean_k - xs).mean()) < 5e-3


@pytest.mark.parametrize("trans_a,trans_b", [(False, True), (True, False)])
def test_bf16_transposed_fused_roles_parity(trans_a, trans_b):
    """bf16-dtype parity for the trans_a/trans_b fused roles (the dgrad /
    wgrad read patterns) vs qmm_ref — the quantize pass is bit-exact in
    bf16 too (ties included), so only dot accumulation order differs."""
    spec_a = QuantSpec("fp4_e2m1", "block")
    spec_b = QuantSpec("fp8_e5m2", "block")
    ka, kb = jax.random.split(jax.random.PRNGKey(13))
    a = jax.random.normal(ka, (200, 140) if trans_a else (140, 200),
                          jnp.float32).astype(jnp.bfloat16)
    b = jax.random.normal(kb, (75, 200) if trans_b else (200, 75),
                          jnp.float32).astype(jnp.bfloat16)
    y = pallas_qmm(a, b, spec_a, spec_b,
                   mode_a=kernel_quant_mode(spec_a),
                   mode_b=kernel_quant_mode(spec_b),
                   trans_a=trans_a, trans_b=trans_b)
    ref = qmm_ref(a, b, spec_a, spec_b, trans_a=trans_a, trans_b=trans_b)
    _close(y, ref, rtol=1e-2, atol=1e-2)  # ~1 bf16 output ulp


def test_quantize_pass_bit_exact_vs_oracle():
    """Phase 1 standalone (quantize_panels) is BIT-exact vs the shared-codec
    oracle in f32 and bf16 — RTN, and SR with the kernel's coordinate-keyed
    noise reconstructed outside (tiling-invariant, so the oracle needs no
    knowledge of panel sizes)."""
    from repro.kernels.fp4_matmul import quantize_panels
    from repro.kernels.ref import quantize_panels_ref
    from repro.kernels.rounding import hash_uniform
    x = jax.random.normal(jax.random.PRNGKey(21), (256, 384), jnp.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        xd = x.astype(dtype)
        for mode in ("block", "tile", "token", "tensor"):
            got = np.asarray(quantize_panels(
                xd, mode=mode, fmt_name="fp4_e2m1").astype(jnp.float32))
            ref = np.asarray(quantize_panels_ref(
                xd, QuantSpec("fp4_e2m1", mode)).astype(jnp.float32))
            np.testing.assert_array_equal(got, ref, err_msg=f"{dtype}/{mode}")
    # SR: same seed -> kernel noise is hash(seed, global coord), so the
    # oracle reproduces it bit-exactly with one full-array hash call.
    seed = jnp.asarray([1234], jnp.int32)
    got = np.asarray(quantize_panels(x, mode="block", fmt_name="fp4_e2m1",
                                     sr=True, seed=seed))
    noise = hash_uniform(x.shape, seed[0], 0, 0)
    ref = np.asarray(quantize_panels_ref(x, QuantSpec("fp4_e2m1", "block"),
                                         noise=noise))
    np.testing.assert_array_equal(got, ref)
    # transposed read (the wgrad x^T pattern): noise keys on the EFFECTIVE
    # orientation, so the oracle still reconstructs it exactly.
    gotT = np.asarray(quantize_panels(x.T, mode="block", sr=True, seed=seed,
                                      fmt_name="fp4_e2m1", trans=True))
    refT = np.asarray(quantize_panels_ref(x.T, QuantSpec("fp4_e2m1", "block"),
                                          trans=True, noise=noise))
    np.testing.assert_array_equal(gotT, refT)


def test_decoupled_mxu_tiling_matches_quant_grid():
    """The streaming kernel's tiling (bm, bn, bk), the only pipeline it
    steers, is independent of the 128-wide quant group: different tilings
    give the same result (the codec is tiling-invariant)."""
    from repro.kernels.fp4_matmul import fused_qmm
    x, w = _data(256, 512, 256, seed=14)
    outs = []
    for bm, bn, bk in [(128, 128, 128), (256, 256, 512), (256, 128, 256)]:
        outs.append(np.asarray(fused_qmm(
            x, w, a_mode="block", b_mode="tile", bm=bm, bn=bn, bk=bk,
            pipeline="stream", interpret=True)))
    spec_a, spec_b = QuantSpec("fp4_e2m1", "block"), QuantSpec("fp4_e2m1",
                                                               "tile")
    for o in outs:
        _close(o, qmm_ref(x, w, spec_a, spec_b))
    # same quantized operands -> only f32 dot order differs between tilings
    _close(outs[0], outs[1], rtol=1e-6, atol=1e-6)


def test_pallas_qmatmul_stats_bit_identical_y():
    """The telemetry-epilogue variant returns the same y and sensible
    finalized stats (matching a full-population operand_stats run)."""
    from repro.core.qlinear import pallas_qmatmul_stats
    from repro.kernels.fp4_matmul import finalize_quant_stats
    from repro.telemetry.collect import operand_stats
    x, w = _data(128, 256, 128, seed=15)
    recipe = MM_FFN_PAPER
    y0 = pallas_qmatmul(x, w, KEY0, recipe)
    y1, (sx, sw) = pallas_qmatmul_stats(x, w, KEY0, recipe)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    fx = {k: float(v) for k, v in finalize_quant_stats(sx).items()}
    # 128 rows -> no subsampling in operand_stats: exact same population
    ref = {k: float(v)
           for k, v in operand_stats(x, recipe.fwd_x, 1).items()}
    for k in ("clip", "underflow", "rel_err", "scale_spread"):
        np.testing.assert_allclose(fx[k], ref[k], rtol=1e-5, atol=1e-6)
    assert sw is not None
    # gradient flows exactly like the stats-free variant
    g = jax.grad(lambda a, b: jnp.sum(
        pallas_qmatmul_stats(a, b, KEY0, recipe)[0]), argnums=(0, 1))(x, w)
    g0 = jax.grad(lambda a, b: jnp.sum(
        pallas_qmatmul(a, b, KEY0, recipe)), argnums=(0, 1))(x, w)
    for a, b in zip(g, g0):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_matmul_impl_registry():
    assert matmul_impl("qdq") is qmatmul
    assert matmul_impl("pallas") is pallas_qmatmul
    with pytest.raises(ValueError):
        matmul_impl("nope")


def test_trainer_one_step_linear_impl_pallas():
    """One optimizer step on the tiny config with every model linear routed
    through the fused kernel (fwd+dgrad+wgrad in interpret mode)."""
    from repro.data import SyntheticLM
    from repro.models import build_model
    from repro.train.trainer import Trainer

    cfg = get_config("tiny").replace(linear_impl="pallas")
    model = build_model(cfg)
    pipe = SyntheticLM(cfg.vocab_size, 32, 2, seed=0)
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=1, global_batch=2,
                       seq_len=32, learning_rate=1e-3, log_every=0)
    tr = Trainer(model, tcfg, pipe)
    st = tr.train()
    assert st.step == 1
    assert np.isfinite(tr.history[-1]["loss"])
    for leaf in jax.tree.leaves(st.params):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


def test_trainer_pallas_with_telemetry_epilogue():
    """telemetry=True + linear_impl=pallas: the fwd_x/fwd_w stats come from
    the quantize pass's in-kernel epilogue (pallas_qmatmul_stats) inside
    the scanned, jitted train step — metrics present and finite."""
    from repro.data import SyntheticLM
    from repro.models import build_model
    from repro.train.trainer import Trainer

    cfg = get_config("tiny").replace(linear_impl="pallas")
    model = build_model(cfg)
    pipe = SyntheticLM(cfg.vocab_size, 32, 2, seed=0)
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=1, global_batch=2,
                       seq_len=32, learning_rate=1e-3, log_every=0,
                       telemetry=True)
    tr = Trainer(model, tcfg, pipe)
    tr.train()
    row = tr.history[-1]
    keys = [k for k in row if "/fwd_x/" in k or "/fwd_w/" in k]
    assert keys, sorted(row)
    for k in keys:
        assert np.isfinite(row[k]), (k, row[k])
    assert any(row[k] > 0 for k in keys if k.endswith("rel_err"))
