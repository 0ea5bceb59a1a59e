"""Compile the main-path kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib; ``get_topology_desc`` describes
a v5e it can target.  Nothing runs: each test lowers and compiles at
GPT-2-125M's training widths (8192 tokens, d_model 768, d_ff 3072) and
asserts that the compiled program still holds the Mosaic kernel
(``tpu_custom_call``).  This is what interpret-mode tests cannot see:
the compiler refuses unaligned slices, scoped-VMEM overruns and
operations Mosaic has no lowering for.

The attention core is compiled too: on a TPU, self-attention runs JAX's
splash flash kernels forward and backward (``kernels.ops.flash_attention``),
so the step holds them and no float32 (..., S, S) score block.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import functools
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import TrainConfig
from repro.configs.gpt2_125m import CONFIG as GPT2_125M
from repro.configs.gpt2_774m import CONFIG as GPT2_774M
from repro.core import routing
from repro.core.recipe import RECIPES, as_plan
from repro.distributed.mesh import make_mesh
from repro.distributed.sharding import default_rules
from repro.models import build_model
from repro.train.train_step import (make_optimizer, make_train_step,
                                    train_step_shardings)

FM = importlib.import_module("repro.kernels.fp4_matmul")
OPS = importlib.import_module("repro.kernels.ops")

KERNEL = 'custom_call_target="tpu_custom_call"'
SPLASH = re.compile(r"%splash_[\w.]+ = .*" + re.escape(KERNEL))
TOKENS, D, FF, QKV = 8192, 768, 3072, 3 * 768


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def on_chip(topo):
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)
    return shapes


def _bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def _seed():
    return jax.ShapeDtypeStruct((1,), jnp.int32)


# name -> (fused_qmm keywords, operand shapes as stored)
QMM_CASES = {
    # the paper's FFN forward: per-block FP4 activations, per-tile weights
    "stream_fwd_block_tile": (
        dict(a_mode="block", b_mode="tile", pipeline="stream"),
        (_bf16(TOKENS, D), _bf16(D, FF))),
    # the same role on the default pipeline: two quantize passes, XLA's dot
    "two_pass_fwd_block_tile": (
        dict(a_mode="block", b_mode="tile"), (_bf16(TOKENS, D),
                                              _bf16(D, FF))),
    # ... and at GPT-2-774M's widths (d_model 1280, d_ff 5120)
    "two_pass_fwd_block_tile_774m": (
        dict(a_mode="block", b_mode="tile"), (_bf16(TOKENS, 1280),
                                              _bf16(1280, 5120))),
    "attn_token_token_fp8_774m": (
        dict(a_mode="token", b_mode="token", a_fmt="fp8_e4m3",
             b_fmt="fp8_e4m3"), (_bf16(TOKENS, 1280), _bf16(1280, 3840))),
    # the paper's bf16 FFN dgrad: XLA's dot alone, no kernel
    "dgrad_pass_pass_trans_b": (
        dict(a_mode="pass", b_mode="pass", trans_b=True),
        (_bf16(TOKENS, FF), _bf16(D, FF))),
    # attention linears under paper_fp4: per-token FP8 (two-pass)
    "attn_token_token_fp8": (
        dict(a_mode="token", b_mode="token", a_fmt="fp8_e4m3",
             b_fmt="fp8_e4m3"), (_bf16(TOKENS, D), _bf16(D, QKV))),
    # FFN wgrad: x^T @ g, FP8 per-block along the token axis
    "wgrad_block_block_trans_a": (
        dict(a_mode="block", b_mode="block", a_fmt="fp8_e4m3",
             b_fmt="fp8_e5m2", trans_a=True), (_bf16(TOKENS, D),
                                               _bf16(TOKENS, FF))),
    # demoted FP4 wgrad with stochastic rounding (hardware PRNG noise)
    "wgrad_sr_fp4": (
        dict(a_mode="block", b_mode="block", trans_a=True, a_sr=True,
             b_sr=True), (_bf16(TOKENS, D), _bf16(TOKENS, FF), _seed(),
                          _seed())),
    # packed-weight decode: FP4 activations, pre-quantized panel passes
    "decode_block_pass": (
        dict(a_mode="block", b_mode="pass"), (_bf16(128, D), _bf16(D, FF))),
}


@pytest.mark.parametrize("case", sorted(QMM_CASES))
def test_fused_qmm_compiles_for_v5e(on_chip, case):
    kw, shapes = QMM_CASES[case]

    def f(a, b, *seeds):
        sk = dict(zip(("seed_a", "seed_b"), seeds))
        return FM.fused_qmm(a, b, interpret=False, **kw, **sk)

    compiled = jax.jit(f).lower(*on_chip(shapes)).compile()
    # Only a bf16 x bf16 role off the streaming pipeline runs no kernel.
    quantizes = kw["a_mode"] != "pass" or kw["b_mode"] != "pass"
    expect_kernel = quantizes or kw.get("pipeline") == "stream"
    assert (KERNEL in compiled.as_text()) == expect_kernel


def _scores_f32(text: str, seq: int) -> int:
    """float32 arrays in the HLO whose two minor dims are (seq, seq)."""
    return len(re.findall(rf"f32\[[\d,]*{seq},{seq}\]", text))


def test_flash_attention_fwd_compiles_for_v5e(on_chip, chip_precision):
    # (batch 16, seq 1024, 12 heads of 64): forward, dq and dkv kernels
    q = _bf16(16, 1024, 12, 64)

    def loss(q, k, v):
        o = OPS.flash_attention(q, k, v, interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *on_chip((q, q, q))).compile()
    text = compiled.as_text()
    names = {m.split(" = ")[0].rsplit(".", 1)[0]
             for m in SPLASH.findall(text)}
    assert names == {"%splash_mha_fwd_residuals",
                     "%splash_mha_dq_no_residuals",
                     "%splash_mha_dkv_no_residuals"}
    assert _scores_f32(text, 1024) == 0


def _sdpa_routes(log):
    """The (route, reasons) of the attention cores in a census."""
    return {(ev.route, ev.reasons) for ev in log.cells()
            if ev.role == "sdpa"}


def _step_args(model, tcfg):
    """Abstract arguments of the 6-arg train step."""
    params = model.abstract_params(jnp.float32)
    opt = jax.eval_shape(make_optimizer(model, tcfg).init, params)
    tokens = jax.ShapeDtypeStruct((tcfg.global_batch, tcfg.seq_len),
                                  jnp.int32)
    return (params, opt, jax.ShapeDtypeStruct((), jnp.float32),
            {"tokens": tokens, "targets": tokens},
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32))


def test_paper_fp4_train_step_compiles_for_v5e(on_chip, monkeypatch):
    """One whole fused-kernel paper_fp4 train step at GPT-2-125M widths,
    two layers deep; it must fit one 16 GB chip, and its Mosaic calls are
    the one-device path's: 36 at these widths.  At the ``highest`` matmul
    precision the test session sets, the splash kernels' bf16 dots would
    not compile, so attention keeps the chunked path and says why."""
    # Kernels decide interpret mode from the backend, which is the CPU
    # here: steer them to the chip's compiler.
    monkeypatch.setattr(FM, "interpret_mode", lambda: False)
    cfg = GPT2_125M.replace(n_layers=2, linear_impl="pallas")
    model = build_model(cfg)
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=20,
                       global_batch=TOKENS // 1024, seq_len=1024)
    step = make_train_step(model, tcfg,
                           as_plan(RECIPES["paper_fp4"], cfg.n_layers),
                           donate=False)
    with routing.capture() as log:
        compiled = step.lower(*on_chip(_step_args(model, tcfg))).compile()
    assert _sdpa_routes(log) == {("chunked", ("precision",))}
    assert compiled.as_text().count(KERNEL) == 36
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16e9


def test_paper_fp4_fsdp_step_compiles_for_v5e_2x2(topo, monkeypatch):
    """The same step on a described four-chip host, mesh (4, 1) with FSDP
    and 8 rows a chip: GSPMD cannot partition a Mosaic kernel, so each
    quantize pass runs under ``shard_map`` (``kernels/ops.py``); the
    kernels stay in, and the step fits each chip's 16 GB."""
    monkeypatch.setattr(FM, "interpret_mode", lambda: False)
    cfg = GPT2_125M.replace(n_layers=2, linear_impl="pallas")
    model = build_model(cfg)
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=20,
                       global_batch=4 * TOKENS // 1024, seq_len=1024,
                       mesh_shape=(4, 1), fsdp=True)
    rules = default_rules(make_mesh((4, 1), ("data", "model"),
                                    devices=topo.devices), cfg, fsdp=True)
    shardings = list(train_step_shardings(model, tcfg, rules)[0])
    shardings[3] = {"tokens": shardings[3], "targets": shardings[3]}
    args = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), _step_args(model, tcfg),
        tuple(shardings))
    step = make_train_step(model, tcfg,
                           as_plan(RECIPES["paper_fp4"], cfg.n_layers),
                           donate=False, rules=rules)
    with routing.capture() as log:
        compiled = step.lower(*args).compile()
    assert _sdpa_routes(log) == {("chunked", ("precision",))}
    assert KERNEL in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16e9


def _gpt2_774m_layer(tcfg_kw):
    cfg = GPT2_774M.replace(n_layers=1, linear_impl="pallas")
    model = build_model(cfg)
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=20, seq_len=1024,
                       **tcfg_kw)
    return cfg, model, tcfg


def test_gpt2_774m_layer_step_runs_flash_for_v5e(on_chip, monkeypatch,
                                                 chip_precision):
    """One GPT-2-774M-width layer's paper_fp4 train step (8 x 1024, 20
    heads of 64) on a described v5e: attention runs the splash kernels,
    and no float32 (..., 1024, 1024) score block is left in the step."""
    monkeypatch.setattr(FM, "interpret_mode", lambda: False)
    cfg, model, tcfg = _gpt2_774m_layer(dict(global_batch=8))
    step = make_train_step(model, tcfg,
                           as_plan(RECIPES["paper_fp4"], cfg.n_layers),
                           donate=False)
    with routing.capture() as log:
        lowered = step.lower(*on_chip(_step_args(model, tcfg)))
    assert _sdpa_routes(log) == {("flash", ())}
    text = lowered.compile().as_text()
    calls = SPLASH.findall(text)
    assert len(calls) == 4
    assert all("bf16[8,20,1024,64]" in c for c in calls)
    assert _scores_f32(text, 1024) == 0


def test_gpt2_774m_layer_fsdp_step_runs_flash_for_v5e_2x2(topo, monkeypatch,
                                                          chip_precision):
    """The same layer under the FSDP rules on a described v5e:2x2, mesh
    (4, 1), 8 rows a chip: GSPMD cannot partition a Mosaic call, so the
    splash kernels run inside ``shard_map``, each chip on its own rows."""
    monkeypatch.setattr(FM, "interpret_mode", lambda: False)
    cfg, model, tcfg = _gpt2_774m_layer(dict(global_batch=32,
                                             mesh_shape=(4, 1), fsdp=True))
    rules = default_rules(make_mesh((4, 1), ("data", "model"),
                                    devices=topo.devices), cfg, fsdp=True)
    shardings = list(train_step_shardings(model, tcfg, rules)[0])
    shardings[3] = {"tokens": shardings[3], "targets": shardings[3]}
    args = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), _step_args(model, tcfg),
        tuple(shardings))
    step = make_train_step(model, tcfg,
                           as_plan(RECIPES["paper_fp4"], cfg.n_layers),
                           donate=False, rules=rules)
    with routing.capture() as log:
        lowered = step.lower(*args)
    assert _sdpa_routes(log) == {("flash", ())}
    assert all(ev.sharded for ev in log.cells() if ev.role == "sdpa")
    text = lowered.compile().as_text()
    calls = SPLASH.findall(text)
    assert len(calls) == 4
    assert all("bf16[8,20,1024,64]" in c for c in calls)
    assert _scores_f32(text, 1024) == 0


def test_fp8_compressed_dp_step_keeps_chunked_attention_v5e_2x2(
        topo, monkeypatch, chip_precision):
    """The fp8-compressed data-parallel step computes each data shard's
    gradients in a ``vmap`` whose rules leave the batch unmapped: inside
    ``shard_map`` every chip would run every shard's attention, so the
    core keeps the chunked path, which GSPMD partitions, and says why."""
    from repro.optim.compression import init_compression_state
    monkeypatch.setattr(FM, "interpret_mode", lambda: False)
    cfg = GPT2_125M.replace(n_layers=1, linear_impl="pallas")
    model = build_model(cfg)
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=20, global_batch=32,
                       seq_len=1024, mesh_shape=(4, 1), fsdp=False,
                       grad_compression="fp8")
    rules = default_rules(make_mesh((4, 1), ("data", "model"),
                                    devices=topo.devices), cfg, fsdp=False)
    shardings = list(train_step_shardings(model, tcfg, rules)[0])
    shardings[3] = {"tokens": shardings[3], "targets": shardings[3]}
    args = list(_step_args(model, tcfg))
    args[2] = jax.eval_shape(functools.partial(
        init_compression_state, dp_size=rules.dp_size), args[0])
    args = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tuple(args), tuple(shardings))
    step = make_train_step(model, tcfg,
                           as_plan(RECIPES["paper_fp4"], cfg.n_layers),
                           donate=False, rules=rules)
    with routing.capture() as log:
        text = step.lower(*args).compile().as_text()
    assert _sdpa_routes(log) == {("chunked", ("mesh_axis_unused",))}
    assert not SPLASH.findall(text)
