"""jit-able train/eval steps: loss, grad-accum, clip, compression, update.

The precision plan is baked into the compiled graph (it changes the math),
so the trainer holds one compiled step per active plan — switching at the
§3.3 schedule boundary or after a controller demotion is a Python-level
swap, not a recompile of anything else.  ``step`` is a traced scalar so the
LR schedule lives inside the graph; ``lr_scale`` is a traced scalar so the
controller's LR backoff does not recompile either.

Mesh-native mode: passing ``rules`` (a ``distributed.sharding.
ShardingRules``) makes the step mesh-first — the model body runs under the
rules' sharding context (every ``shard_hint`` / quantization-scale
placement hint becomes a real ``with_sharding_constraint``), jit gets
``NamedSharding`` in/out specs derived from the rules
(``train_step_shardings``), and with ``grad_compression='fp8'`` on a
multi-shard data axis the gradient reduction runs quantize-before-
communicate: per-data-shard gradients come from a ``vmap`` over batch
slices (the leading replica axis sharded over the data axes) and the fp8
sum over that axis lowers to a real ``float8_e4m3fn``-payload all-reduce
with per-shard error feedback
(``optim.compression.compressed_reduce_dp``).  Model axes keep their
ordinary GSPMD propagation — a shard_map manual over data was rejected
because ``lax.scan`` over model-sharded operands inside a partial-auto
region crashes XLA (jax 0.4.x) and the layer stack scans.  On a 1x1 mesh
every constraint is a no-op and the step is bit-exact with the rules-free
path.
"""
from __future__ import annotations

import contextlib
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import TrainConfig
from repro.core.qlinear import matmul_impl
from repro.core.recipe import as_plan
from repro.models.model import Model
from repro.nn import layers
from repro.optim import (clip_by_global_norm, compressed_reduce_dp,
                         fp8_compress_grads, get_optimizer, warmup_cosine)
from repro.telemetry import collect as telemetry
from repro.telemetry.profiler import graph_span

__all__ = ["make_train_step", "make_eval_step", "make_optimizer",
           "train_step_shardings"]


def make_optimizer(model: Model, tcfg: TrainConfig):
    return get_optimizer(
        model.cfg.optimizer, weight_decay=tcfg.weight_decay,
        beta1=tcfg.beta1, beta2=tcfg.beta2, eps=tcfg.eps)


def _split_microbatches(batch: Dict[str, jnp.ndarray], k: int):
    def sp(x):
        b = x.shape[0]
        assert b % k == 0, (b, k)
        return x.reshape(k, b // k, *x.shape[1:])
    return jax.tree.map(sp, batch)


# ---------------------------------------------------------------------------
# Mesh-native sharding derivation
# ---------------------------------------------------------------------------

def _uses_axes(sharding: NamedSharding, axes) -> bool:
    flat = []
    for e in sharding.spec:
        if e is None:
            continue
        flat.extend((e,) if isinstance(e, str) else e)
    return any(a in axes for a in flat)


def compression_state_sharding(rules, param_shardings):
    """Shardings for the error-feedback residuals.

    Under the manual-DP compressed path the residual tree carries a
    leading replica axis (``init_compression_state(dp_size=...)``) sharded
    over the data axes — each data shard owns its slice — while the
    per-parameter trailing dims keep the parameter's (model-axis) layout.
    Without a multi-shard data axis residuals mirror the params exactly.
    """
    dp = rules.dp_axes
    if rules.dp_size <= 1:
        return param_shardings

    def shift(sh: NamedSharding) -> NamedSharding:
        if _uses_axes(sh, dp):
            raise ValueError(
                "fp8 grad compression's per-shard residuals need params "
                "replicated over the data axes, but a param shards over "
                f"{sh.spec}.  Build rules with default_rules(..., "
                "fsdp=False) (TrainConfig.fsdp = False).")
        return NamedSharding(rules.mesh, P(dp, *sh.spec))

    return jax.tree.map(shift, param_shardings)


def train_step_shardings(model: Model, tcfg: TrainConfig, rules):
    """(in_shardings, out_shardings) for the 6-arg mesh-native train step
    ``(params, opt_state, comp_state, batch, step, lr_scale)``.

    Params/opt state follow ``rules.param_shardings`` /
    ``opt_state_shardings``; the batch shards its leading dim over the
    data axes (``rules.batch_sharding``, applied as a pytree prefix);
    step/lr_scale/metrics replicate.
    """
    from repro.distributed.sharding import opt_state_shardings

    params_abs = model.abstract_params(jnp.float32)
    p_shard = rules.param_shardings(model.param_specs())
    opt = make_optimizer(model, tcfg)
    opt_abs = jax.eval_shape(opt.init, params_abs)
    o_shard = opt_state_shardings(opt_abs, params_abs, p_shard, rules.mesh)
    if tcfg.grad_compression == "fp8":
        c_shard = compression_state_sharding(rules, p_shard)
    else:
        c_shard = rules.replicated()
    rep = rules.replicated()
    in_shardings = (p_shard, o_shard, c_shard, rules.batch_sharding(2),
                    rep, rep)
    out_shardings = (p_shard, o_shard, c_shard, rep)
    return in_shardings, out_shardings


def make_train_step(model: Model, tcfg: TrainConfig,
                    plan, *,
                    jit: bool = True,
                    donate: bool = True,
                    in_shardings=None, out_shardings=None,
                    rules=None):
    """Returns train_step(params, opt_state, comp_state, batch, step,
    lr_scale=1.0) -> (params, opt_state, comp_state, metrics).

    ``plan`` is a ``PrecisionPlan`` or a ``PrecisionRecipe`` template
    (coerced to the uniform plan).  The model's linear layers run through
    ``cfg.linear_impl`` ('qdq' unfused simulation | 'pallas' fused
    quantize+matmul kernel for fwd/dgrad/wgrad); validated here so a
    typo'd config fails at step-build time, not deep inside a jit trace.
    ``lr_scale`` multiplies the scheduled LR (the controller's rollback
    backoff); callers that never back off can omit it.

    ``rules`` (a ``ShardingRules``) turns on mesh-native mode: the step
    body traces under the rules' sharding context, jit derives
    ``NamedSharding`` in/out specs from them when the caller supplies none
    (callers then pass all six args, ``lr_scale`` included), and fp8
    gradient compression over a multi-shard data axis becomes the real
    quantize-before-communicate reduction (vmap over batch slices + an
    fp8-payload all-reduce).  ``rules=None`` is byte-for-byte the old
    single-device step.
    """
    matmul_impl(model.cfg.linear_impl)
    plan = as_plan(plan, model.cfg.n_layers)
    opt = make_optimizer(model, tcfg)
    lr_fn = warmup_cosine(tcfg.learning_rate, tcfg.total_steps,
                          tcfg.warmup_frac, tcfg.min_lr_frac)
    use_compression = tcfg.grad_compression == "fp8"
    spmd_dp = (rules is not None and use_compression and rules.dp_size > 1)
    if spmd_dp:
        p_shard = rules.param_shardings(model.param_specs())
        bad = [s.spec for s in jax.tree.leaves(p_shard)
               if _uses_axes(s, rules.dp_axes)]
        if bad:
            raise ValueError(
                "fp8 grad compression's manual-DP reduction needs params "
                "replicated over the data axes (each shard applies the "
                f"same compressed update), but these specs use them: "
                f"{bad[:3]}...  Build rules with default_rules(..., "
                "fsdp=False).")
    # Telemetry: when enabled, a trace-time collector is installed around
    # the loss (per-layer forward-side stats ride the loss aux; backward
    # cotangent stats arrive as gradients of zero-valued probes).  When
    # disabled, the code below is exactly the telemetry-free step — no
    # collector, no probes, bit-identical graph.
    collector = telemetry.TelemetryCollector() if tcfg.telemetry else None

    # Scopes (telemetry.profiler.graph_span = jax.named_scope) are pure
    # HLO metadata: the model's layers scope their own ops (embed, attn,
    # mlp, norm, head); here ``optim`` holds clipping and the update and
    # ``collective`` the fp8 gradient reduction.
    def loss_fn(params, batch):
        return model.loss(params, batch, plan)

    def loss_fn_tel(params, batch, probes):
        with telemetry.collecting(collector, probes):
            loss, metrics = model.loss(params, batch, plan)
            metrics = dict(metrics)
            metrics.update(collector.drain_root())
        return loss, metrics

    n_layers = model.cfg.n_layers

    def compute_grads(params, batch):
        probes = (telemetry.make_probes(n_layers)
                  if collector is not None else None)
        if collector is not None:
            vg = jax.value_and_grad(loss_fn_tel, argnums=(0, 2),
                                    has_aux=True)
        if tcfg.microbatch and tcfg.microbatch > 1:
            mbs = _split_microbatches(batch, tcfg.microbatch)

            def acc(carry, mb):
                g_acc, l_acc = carry
                (loss, metrics), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, l_acc + loss), metrics

            def acc_tel(carry, mb):
                (g_acc, pg_acc), l_acc = carry
                (loss, metrics), (g, pg) = vg(params, mb, probes)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                # probe stats are sums with a tap-count slot, so plain
                # accumulation keeps them self-normalizing
                pg_acc = jax.tree.map(jnp.add, pg_acc, pg)
                return ((g_acc, pg_acc), l_acc + loss), metrics

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            if collector is None:
                (g, loss_sum), metrics = jax.lax.scan(
                    acc, (g0, jnp.zeros((), jnp.float32)), mbs)
            else:
                ((g, pg), loss_sum), metrics = jax.lax.scan(
                    acc_tel, ((g0, telemetry.make_probes(n_layers)),
                              jnp.zeros((), jnp.float32)), mbs)
            k = tcfg.microbatch
            grads = jax.tree.map(lambda x: x / k, g)
            metrics = jax.tree.map(lambda m: m.mean(), metrics)
            metrics["loss"] = loss_sum / k
            if collector is not None:
                metrics.update(telemetry.probe_metrics(pg))
            return grads, metrics
        if collector is not None:
            (loss, metrics), (grads, pg) = vg(params, batch, probes)
            metrics.update(telemetry.probe_metrics(pg))
            return grads, metrics
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, metrics

    # Quantize-before-communicate: per-data-shard gradients via a vmap
    # over batch slices (leading replica axis sharded over the data
    # axes); the fp8 sum over that axis IS the gradient all-reduce, with
    # a real float8_e4m3fn payload and per-shard error feedback.  Model
    # (TP) axes keep their ordinary GSPMD propagation throughout — a
    # shard_map manual over data was rejected because lax.scan over
    # model-sharded operands inside a partial-auto region crashes XLA
    # (jax 0.4.x) and the layer stack scans.
    if spmd_dp:
        dp_axes = rules.dp_axes
        dp = rules.dp_size
        batch_dp_sharding = NamedSharding(rules.mesh, P(dp_axes))
        c_shards = compression_state_sharding(rules, p_shard)

        def _reduce_metric(m):
            m = jnp.asarray(m)
            if jnp.issubdtype(m.dtype, jnp.integer):
                return jnp.sum(m, axis=0)    # counts sum globally
            return jnp.mean(m, axis=0)

        def _split_dp(a):
            if a.shape[0] % dp:
                raise ValueError(
                    f"batch dim {a.shape[0]} not divisible by the "
                    f"data-parallel degree {dp}")
            a = a.reshape((dp, a.shape[0] // dp) + a.shape[1:])
            return jax.lax.with_sharding_constraint(a, batch_dp_sharding)

        # Inside the vmapped body the per-slice batch dim must NOT carry
        # the data axes (dim 0 of the stacked view already does), so the
        # slice traces under rules with the dp axes stripped — model (TP)
        # hints survive, data hints become no-ops.
        inner_rules = rules.manual_over(dp_axes)

        def sharded_grads(params, comp_state, batch):
            batch_dp = jax.tree.map(_split_dp, batch)
            with layers.sharding_context(inner_rules):
                grads_dp, metrics_dp = jax.vmap(
                    compute_grads, in_axes=(None, 0))(params, batch_dp)
            with graph_span("collective"):
                # pin the replica axis to the data shards so quantization
                # and error feedback stay local (one slice per shard)
                grads_dp = jax.tree.map(jax.lax.with_sharding_constraint,
                                        grads_dp, c_shards)
                grads, comp_state = compressed_reduce_dp(grads_dp,
                                                         comp_state)
            return grads, comp_state, jax.tree.map(_reduce_metric,
                                                   metrics_dp)

    def train_step(params, opt_state, comp_state, batch, step,
                   lr_scale=1.0):
        ctx = (contextlib.nullcontext() if rules is None
               else layers.sharding_context(rules))
        with ctx:
            if spmd_dp:
                # Reduction (fp8, error-fed) happens where the physical
                # all-reduce is — before clipping, as on a real system.
                grads, comp_state, metrics = sharded_grads(
                    params, comp_state, batch)
                if collector is not None:
                    metrics.update(telemetry.grad_norm_metrics(grads))
                with graph_span("optim"):
                    grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
            else:
                grads, metrics = compute_grads(params, batch)
                if collector is not None:
                    metrics.update(telemetry.grad_norm_metrics(grads))
                with graph_span("optim"):
                    grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
                if use_compression:
                    with graph_span("collective"):
                        grads, comp_state = fp8_compress_grads(grads,
                                                               comp_state)
            with graph_span("optim"):
                lr = lr_fn(step) * lr_scale
                params, opt_state = opt.update(grads, opt_state, params, lr)
            metrics = dict(metrics)
            metrics["grad_norm"] = gnorm
            metrics["lr"] = lr
            return params, opt_state, comp_state, metrics

    if not jit:
        return train_step
    if rules is not None and in_shardings is None and out_shardings is None:
        in_shardings, out_shardings = train_step_shardings(model, tcfg,
                                                           rules)
    kw = {}
    if in_shardings is not None:
        kw["in_shardings"] = in_shardings
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    return jax.jit(train_step,
                   donate_argnums=(0, 1, 2) if donate else (), **kw)


def make_eval_step(model: Model, plan, *, jit=True, rules=None):
    plan = as_plan(plan, model.cfg.n_layers)

    def eval_step(params, batch):
        ctx = (contextlib.nullcontext() if rules is None
               else layers.sharding_context(rules))
        with ctx:
            loss, metrics = model.loss(params, batch, plan)
            return metrics
    return jax.jit(eval_step) if jit else eval_step
