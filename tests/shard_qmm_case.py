"""Run by ``test_shard_qmm.py`` in a process of its own with four virtual
CPU devices (the device count must be set before JAX starts).  Prints one
JSON object:

* ``passes``: for each quantized operand of the paper's roles, at d_model
  384 (a quarter is 96: a ``block``/``tile`` group of a weight split there
  would straddle two chips), whether the quantize pass under ``shard_map``
  on a (4, 1) mesh equals the one-device pass bit for bit, sound and with
  the cross-chip amax left out (``fault``), and the ``shard_plan`` of each;
* ``steps``: loss and per-parameter gradient gaps of a two-layer
  ``paper_fp4`` model at those widths, mesh (4, 1) with FSDP against one
  device, for the fused path (sound and fault) and for the QDQ path, whose
  gaps are the partitioned accumulation order alone;
* ``census``: the routing census of the fused mesh step;
* ``qlint``: ``analysis.qlint``'s audit of a mesh trainer's fused step.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import importlib  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis import qlint  # noqa: E402
from repro.configs.base import TrainConfig, get_config  # noqa: E402
from repro.core import routing  # noqa: E402
from repro.core.quantize import QuantSpec  # noqa: E402
from repro.core.recipe import RECIPES, as_plan  # noqa: E402
from repro.distributed.mesh import make_mesh  # noqa: E402
from repro.distributed.sharding import default_rules  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.nn import layers  # noqa: E402

FM = importlib.import_module("repro.kernels.fp4_matmul")
MESH = make_mesh((4, 1), ("data", "model"))
RULES = default_rules(MESH, None, fsdp=True)
TOK, D, F = 1024, 384, 1536


def _operand(rng, *shape):
    # columns of different magnitudes, so amax groups differ
    return jnp.asarray(rng.standard_normal(shape)
                       * rng.uniform(0.5, 4.0, shape[-1:]), jnp.bfloat16)


def pass_cases():
    rng = np.random.default_rng(0)
    x, g, w = _operand(rng, TOK, D), _operand(rng, TOK, F), _operand(rng, D, F)
    t8, g8 = QuantSpec("fp8_e4m3", "token"), QuantSpec("fp8_e5m2", "token")
    # name: (stored operand, spec, lhs, mode, trans, effective axes)
    return {
        "fwd_x_token": (x, t8, True, "token", False, ("tokens", "embed")),
        "fwd_w_token": (w, t8, False, "token", False, ("embed", "mlp")),
        "fwd_x_block": (x, QuantSpec("fp4_e2m1", "block"), True, "block",
                        False, ("tokens", "embed")),
        "fwd_w_tile": (w, QuantSpec("fp4_e2m1", "tile"), False, "tile",
                       False, ("embed", "mlp")),
        "dgrad_g_token": (g, g8, True, "token", False, ("tokens", "mlp")),
        "dgrad_w_token": (w, t8, False, "token", True, ("mlp", "embed")),
        "wgrad_x_token": (x, t8, True, "token", True, ("embed", "tokens")),
        "wgrad_g_token": (g, g8, False, "token", False, ("tokens", "mlp")),
        "wgrad_x_block": (x, QuantSpec("fp8_e4m3", "block"), True, "block",
                          True, ("embed", "tokens")),
        "wgrad_g_block": (g, QuantSpec("fp8_e5m2", "block"), False, "block",
                          False, ("tokens", "mlp")),
        "wgrad_x_tensor": (x, QuantSpec("fp8_e4m3", "tensor"), True,
                           "tensor", True, ("embed", "tokens")),
        # 640 tokens: a quarter (160) is no whole number of 128-groups
        "wgrad_x_token_640": (_operand(rng, 640, D), t8, True, "token", True,
                              ("embed", "tokens")),
    }


def one_device_pass(t, spec, lhs, mode, trans):
    red = ops._reduction_dim(lhs, trans)
    return jax.jit(lambda t: FM.quantize_for_dot(
        t, lhs=lhs, mode=mode, fmt_name=spec.fmt, pow2=spec.pow2_scale,
        sr=False, seed=None, trans=trans, block=128,
        rows_real=t.shape[1 - red], k_real=t.shape[red],
        collect_stats=False, interpret=True)[0])(t)


def sharded_pass(t, spec, lhs, mode, trans, axes):
    stored = list(axes[::-1] if trans else axes)
    token_dim = stored.index("tokens") if "tokens" in stored else None
    t = jax.device_put(t, NamedSharding(
        MESH, P(*["data" if d == token_dim else None for d in range(2)])))

    def f(t):
        return ops._sharded_pass(
            RULES, t, spec, lhs=lhs, mode=mode, trans=trans, seed=None,
            axes=axes, block=128, rows_real=0, k_real=0,
            collect_stats=False, interpret=True)[0]
    return jax.jit(f)(t)


def without_cross_amax():
    """The planted fault: every amax group takes its chip's shard alone."""
    orig = ops.shard_plan
    ops.shard_plan = lambda *a, **k: orig(*a, **k)[:2] + ((),)
    return lambda: setattr(ops, "shard_plan", orig)


def passes():
    cases = pass_cases()
    out = {"plans": {}, "sound": {}, "fault": {}}
    for name, (t, spec, lhs, mode, trans, axes) in cases.items():
        out["plans"][name] = [str(p) for p in ops.shard_plan(
            RULES, t.shape, axes, lhs=lhs, trans=trans, mode=mode,
            gather=False)]
    for kind in ("sound", "fault"):
        undo = without_cross_amax() if kind == "fault" else (lambda: None)
        for name, (t, spec, lhs, mode, trans, axes) in cases.items():
            a = np.asarray(one_device_pass(t, spec, lhs, mode, trans))
            b = np.asarray(sharded_pass(t, spec, lhs, mode, trans, axes))
            out[kind][name] = bool(a.shape == b.shape and np.array_equal(
                a.view(np.uint16), b.view(np.uint16)))
        undo()
    return out


def steps():
    rng = np.random.default_rng(1)
    tok = jnp.asarray(rng.integers(0, 512, (8, 128)), jnp.int32)
    batch = {"tokens": tok, "targets": jnp.roll(tok, -1, 1)}
    out, census = {}, None
    for impl in ("pallas", "qdq"):
        cfg = get_config("gpt2-774m").replace(
            n_layers=2, d_model=D, n_heads=6, n_kv_heads=6, head_dim=64,
            d_ff=F, vocab_size=512, max_seq_len=128, linear_impl=impl)
        model = build_model(cfg)
        plan = as_plan(RECIPES["paper_fp4"], cfg.n_layers)
        rules = default_rules(MESH, cfg, fsdp=True)
        params = model.init(jax.random.PRNGKey(0))

        def loss_grads(p, b):
            (loss, _), g = jax.value_and_grad(
                lambda p: model.loss(p, b, plan), has_aux=True)(p)
            return loss, g

        ref = jax.jit(loss_grads)(params, batch)
        b_sh = rules.batch_sharding(2)
        for kind in (("sound", "fault") if impl == "pallas" else ("sound",)):
            def on_mesh(p, b):
                with layers.sharding_context(rules):
                    return loss_grads(p, b)
            undo = without_cross_amax() if kind == "fault" else (
                lambda: None)
            with routing.capture() as log:
                got = jax.jit(on_mesh, in_shardings=(
                    rules.param_shardings(model.param_specs()),
                    {"tokens": b_sh, "targets": b_sh}))(params, batch)
            undo()
            if (impl, kind) == ("pallas", "sound"):
                census = [e.to_dict() for e in log.cells()]
            gaps = {}
            for (path, a), b in zip(
                    jax.tree_util.tree_flatten_with_path(ref[1])[0],
                    jax.tree.leaves(got[1])):
                a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
                gaps[jax.tree_util.keystr(path)] = float(
                    np.linalg.norm(a - b) / np.linalg.norm(a))
            out[f"{impl}_{kind}"] = {
                "loss_gap": abs(float(got[0]) - float(ref[0])),
                "grad_gaps": gaps}
    return out, census


def lint():
    cfg = get_config("tiny").replace(linear_impl="pallas", scan_layers=True)
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=8, global_batch=8,
                       seq_len=128, mesh_shape=(4, 1), fsdp=True)
    report = qlint.audit_train_graph(cfg, tcfg, label="train_fsdp4",
                                     compile_hlo=False)
    return {"n_violations": len(report.violations()),
            "n_fallbacks": len(report.fallbacks()),
            "violations": [f.to_dict() for f in report.violations()][:8],
            "cells": report.cells}


def main():
    step_gaps, census = steps()
    print(json.dumps({"passes": passes(), "steps": step_gaps,
                      "census": census, "qlint": lint()}))


if __name__ == "__main__":
    main()
