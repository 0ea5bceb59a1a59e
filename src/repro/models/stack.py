"""Unified decoder-layer stack: attn/mamba mixers, dense/MoE FFNs, cross-attn.

One ``Layer`` = pre-norm mixer sublayer (+ optional cross-attn sublayer)
(+ optional FFN sublayer), covering every assigned architecture:

  dense LMs        : attn + dense FFN
  MoE LMs          : attn + MoE FFN
  VLM              : attn (+ cross every k) + dense FFN
  whisper decoder  : attn + cross + dense FFN
  mamba2           : mamba (no FFN)
  jamba            : {attn|mamba by period} + {dense|MoE alternating}

Stacking strategies:
  * ``scan_layers=True``  : lax.scan over repeating groups (small HLO, used
    by the multi-pod dry-run and training);
  * ``scan_layers=False`` : python-loop unroll (exact cost_analysis for the
    roofline pass).

Precision enters as a layer-resolved ``PrecisionPlan`` (``core.recipe``),
resolved here at trace time: unroll mode indexes the plan row per layer;
scan mode partitions the scan groups into maximal contiguous runs whose
layers share a plan signature and emits one ``lax.scan`` per run (a
uniform plan is a single run, reproducing the pre-plan single-scan graph
bit-identically).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp


def _checkpoint(fn, cfg):
    """cfg.remat_policy: 'full' (recompute everything), 'dots' (save matmul
    outputs — trades activation memory for the remat FLOPs), 'none'."""
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)

from repro.configs.base import LayerSpec, ModelConfig
from repro.core import routing
from repro.core.recipe import LayerRecipe, PrecisionPlan
from repro.models import attention as attn_lib
from repro.models import mlp as mlp_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.nn.layers import apply_norm, shard_hint
from repro.nn.params import ParamSpec
from repro.telemetry import collect as telemetry
from repro.telemetry.profiler import graph_span

__all__ = ["layer_param_specs", "stack_param_specs", "run_stack",
           "stack_cache_spec", "init_stack_cache"]


def _norm_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = {"scale": ParamSpec((cfg.d_model,), ("embed",), init="zeros")}
    if cfg.norm == "layernorm":
        d["bias"] = ParamSpec((cfg.d_model,), ("embed",), init="zeros")
    return d


def layer_param_specs(cfg: ModelConfig, spec: LayerSpec,
                      *, causal: bool = True,
                      kv_dim: Optional[int] = None) -> Dict[str, Any]:
    p: Dict[str, Any] = {}
    if spec.mixer == "attn":
        p["mixer_norm"] = _norm_specs(cfg)
        p["mixer"] = attn_lib.attn_param_specs(cfg)
    else:
        p["mixer_norm"] = _norm_specs(cfg)
        p["mixer"] = ssm_lib.mamba_param_specs(cfg)
    if spec.cross:
        p["cross_norm"] = _norm_specs(cfg)
        p["cross"] = attn_lib.cross_attn_param_specs(cfg, kv_dim)
        # learned gate (llama-3.2-vision style): cross output ramps in from 0
        p["cross_gate"] = ParamSpec((1,), (None,), init="zeros",
                                    dtype=jnp.float32)
    if spec.ffn == "dense":
        p["ffn_norm"] = _norm_specs(cfg)
        p["ffn"] = mlp_lib.mlp_param_specs(cfg)
    elif spec.ffn == "moe":
        p["ffn_norm"] = _norm_specs(cfg)
        p["ffn"] = moe_lib.moe_param_specs(cfg)
    return p


def _stack_specs(tree, n: int, axis_name: Optional[str] = "layers"):
    """Add a leading (n, ...) dim to every ParamSpec in the tree."""
    def bump(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.init,
                         s.scale, s.dtype)
    return jax.tree.map(bump, tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def stack_param_specs(cfg: ModelConfig, *, causal: bool = True,
                      kv_dim: Optional[int] = None,
                      specs: Optional[List[LayerSpec]] = None
                      ) -> Dict[str, Any]:
    """Specs for the whole stack.

    scan mode:   {'groups': stacked specs of one period-group}
    unroll mode: {'layers': [per-layer specs]}
    """
    specs = specs if specs is not None else cfg.layer_specs()
    if not cfg.scan_layers:
        return {"layers": [layer_param_specs(cfg, s, causal=causal,
                                             kv_dim=kv_dim) for s in specs]}
    period = _period(specs)
    n_groups = len(specs) // period
    group = {f"l{i:02d}": layer_param_specs(cfg, specs[i], causal=causal,
                                            kv_dim=kv_dim)
             for i in range(period)}
    return {"groups": _stack_specs(group, n_groups)}


def _period(specs: List[LayerSpec]) -> int:
    n = len(specs)
    for p in range(1, n + 1):
        if n % p == 0 and all(specs[i] == specs[i % p] for i in range(n)):
            return p
    return n


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _layer_cache_spec(cfg: ModelConfig, spec: LayerSpec, batch: int,
                      max_len: int, dtype, per_slot: bool = False):
    c: Dict[str, Any] = {}
    if spec.mixer == "attn":
        c["self"] = attn_lib.attn_cache_spec(cfg, batch, max_len, dtype,
                                             per_slot=per_slot)
    else:
        c["self"] = ssm_lib.mamba_cache_spec(cfg, batch, dtype)
    if spec.cross:
        hd = cfg.resolved_head_dim
        n_kv = cfg.n_kv_heads
        n_cross = (cfg.n_patches if cfg.family == "vlm" else cfg.n_frames)
        c["cross"] = {
            "k": jax.ShapeDtypeStruct((batch, n_cross, n_kv, hd), dtype),
            "v": jax.ShapeDtypeStruct((batch, n_cross, n_kv, hd), dtype),
        }
    return c


def stack_cache_spec(cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16,
                     specs: Optional[List[LayerSpec]] = None,
                     per_slot: bool = False):
    """ShapeDtypeStruct cache pytree matching run_stack's cache layout."""
    specs = specs if specs is not None else cfg.layer_specs()
    if not cfg.scan_layers:
        return {"layers": [_layer_cache_spec(cfg, s, batch, max_len, dtype,
                                             per_slot)
                           for s in specs]}
    period = _period(specs)
    n_groups = len(specs) // period

    def bump(s):
        return jax.ShapeDtypeStruct((n_groups,) + s.shape, s.dtype)

    group = {f"l{i:02d}": _layer_cache_spec(cfg, specs[i], batch, max_len,
                                            dtype, per_slot)
             for i in range(period)}
    return {"groups": jax.tree.map(bump, group)}


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16,
                     specs: Optional[List[LayerSpec]] = None,
                     per_slot: bool = False):
    spec_tree = stack_cache_spec(cfg, batch, max_len, dtype, specs, per_slot)

    def mk(s: jax.ShapeDtypeStruct):
        return jnp.zeros(s.shape, s.dtype)

    cache = jax.tree.map(mk, spec_tree)
    # attention position slots start at -1 (= unwritten)
    def fix_pos(path, leaf):
        if path[-1].key == "pos":
            return jnp.full(leaf.shape, -1, jnp.int32)
        return leaf
    return jax.tree_util.tree_map_with_path(fix_pos, cache)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _run_layer(params, cfg: ModelConfig, spec: LayerSpec, row:
               LayerRecipe, x, *, positions, cross_states, cache,
               cache_len, decode, causal=True, layer_idx=None,
               audit_label=None):
    """One layer, precision-resolved by its plan ``row``.
    Returns (x, new_cache).

    With telemetry enabled, a collection frame is opened around the whole
    layer: the quantized linears inside push per-operand quant-health stats
    into it, and the drained frame rides out through the ``_telemetry``
    cache slot (same channel as ``_moe_aux``) so per-layer stats survive
    both the scan and the unroll stacking strategies.  ``layer_idx`` (int
    in unroll mode, traced scalar in a scan body) routes backward-side
    probe stats into the layer's row.  ``audit_label`` is the STATIC layer
    label for the routing census (``"L3"`` unrolled, ``"L1:8:4"`` for a
    scan-body position standing for ``range(1, 8, 4)``) — usable where
    ``layer_idx`` may be traced.
    """
    new_cache: Dict[str, Any] = {}
    with routing.layer_scope(audit_label), \
            telemetry.layer_frame(layer_idx) as tel_frame:
        # Pre-norm outputs re-enter TP matmuls replicated on embed; the
        # hints pin each sublayer input so GSPMD gathers exactly once here
        # instead of propagating a model-sharded layout into the norm.
        h = shard_hint(apply_norm(params["mixer_norm"], x, cfg.norm),
                       ("batch", "seq", "embed"))
        if spec.mixer == "attn":
            with telemetry.module_scope("attn"):
                out, c = attn_lib.attention(
                    params["mixer"], cfg, h, row.attn_linear,
                    positions=positions,
                    cache=None if cache is None else cache["self"],
                    cache_len=cache_len, causal=causal)
        else:
            with telemetry.module_scope("ssm"):
                out, c = ssm_lib.mamba_mixer(
                    params["mixer"], cfg, h, row.ffn_linear,
                    cache=None if cache is None else cache["self"],
                    decode=decode, unroll=not cfg.scan_layers)
        if cache is not None:
            new_cache["self"] = c if c is not None else cache["self"]
        # each residual add belongs to its sublayer's scope
        with (graph_span("attn") if spec.mixer == "attn"
              else contextlib.nullcontext()):
            x = x + out

        if spec.cross:
            h = shard_hint(apply_norm(params["cross_norm"], x, cfg.norm),
                           ("batch", "seq", "embed"))
            cc = cache.get("cross") if (cache is not None and decode) \
                else None
            with telemetry.module_scope("cross"):
                out, ccache = attn_lib.cross_attention(
                    params["cross"], cfg, h, row.attn_linear,
                    kv_states=cross_states, cache=cc)
            with graph_span("attn"):
                gate = jnp.tanh(params["cross_gate"].astype(jnp.float32))
                x = x + (out.astype(jnp.float32) * gate).astype(x.dtype)
            if cache is not None:
                new_cache["cross"] = ccache

        if spec.ffn == "dense":
            h = shard_hint(apply_norm(params["ffn_norm"], x, cfg.norm),
                           ("batch", "seq", "embed"))
            with telemetry.module_scope("ffn"), graph_span("mlp"):
                x = x + mlp_lib.mlp(params["ffn"], cfg, h, row.ffn_linear)
        elif spec.ffn == "moe":
            h = shard_hint(apply_norm(params["ffn_norm"], x, cfg.norm),
                           ("batch", "seq", "embed"))
            with telemetry.module_scope("moe"):
                out, aux = moe_lib.moe(params["ffn"], cfg, h,
                                       row.ffn_linear)
            x = x + out
            new_cache["_moe_aux"] = aux  # surfaced via cache slot in unroll
        x = shard_hint(x, ("batch", "seq", "embed"))
    if tel_frame is not None and tel_frame.stats:
        new_cache["_telemetry"] = tel_frame.stats
    return x, new_cache


def run_stack(params, cfg: ModelConfig, plan: PrecisionPlan,
              x: jnp.ndarray, *,
              positions: Optional[jnp.ndarray] = None,
              cross_states: Optional[jnp.ndarray] = None,
              cache=None, cache_len=None, decode: bool = False,
              specs: Optional[List[LayerSpec]] = None,
              causal: bool = True, indexed_probes: bool = True):
    """Run the full layer stack under a layer-resolved ``PrecisionPlan``.

    Returns (x, new_cache_or_None, aux_losses: dict of scalars).

    ``indexed_probes=False`` disables per-layer backward-probe indexing
    (taps fold into the class-aggregate trailing row).  The audio encoder
    stack uses this: its layer indices would otherwise collide with the
    decoder's rows in the shared probe arrays and could mis-drive the
    controller's per-layer demotion.
    """
    specs = specs if specs is not None else cfg.layer_specs()
    assert plan.n_layers == len(specs), (plan.n_layers, len(specs))
    aux_total: Dict[str, jnp.ndarray] = {}

    def add_aux(aux):
        for k, v in aux.items():
            aux_total[k] = aux_total.get(k, 0.0) + v

    if not cfg.scan_layers:
        layer_params = params["layers"]
        layer_caches = (cache["layers"] if cache is not None
                        else [None] * len(specs))
        new_caches = []
        for i, spec in enumerate(specs):
            fn = functools.partial(
                _run_layer, cfg=cfg, spec=spec, row=plan.layers[i],
                positions=positions, cross_states=cross_states,
                cache_len=cache_len, decode=decode, causal=causal,
                layer_idx=i if indexed_probes else None,
                audit_label=f"L{i}")
            if cfg.remat and cfg.remat_policy != "none" and cache is None:
                ckpt = _checkpoint(
                    lambda p, y, _fn=fn: _fn(p, x=y, cache=None), cfg)
                x, c = ckpt(layer_params[i], x)
            else:
                x, c = fn(layer_params[i], x=x, cache=layer_caches[i])
            if isinstance(c, dict) and "_moe_aux" in c:
                add_aux(c.pop("_moe_aux"))
            if isinstance(c, dict) and "_telemetry" in c:
                for k, v in c.pop("_telemetry").items():
                    aux_total[f"tel/l{i:02d}/{k}"] = v
            new_caches.append(c)
        new_cache = ({"layers": new_caches} if cache is not None else None)
        return x, new_cache, aux_total

    # --- scan mode ---
    #
    # The plan partitions the scan groups into maximal contiguous runs of
    # identical layer rows (``plan.scan_runs``); each run is one lax.scan
    # over its slice of the stacked params/cache.  A uniform plan is a
    # single run over the unsliced trees — the same traced graph as the
    # pre-plan single scan.  When a telemetry collector is installed, the
    # group index rides along as an extra scanned input so backward-side
    # probe stats resolve to absolute layers (the graph with telemetry off
    # carries no such input and stays bit-identical).
    period = _period(specs)
    n_groups = len(specs) // period
    gparams = params["groups"]
    gcache = cache["groups"] if cache is not None else None
    runs = plan.scan_runs(period)
    col_on = telemetry.active() is not None and indexed_probes

    new_gcache_runs = []
    carry = (x, cache_len)
    for g0, g1 in runs:
        rows = plan.layers[g0 * period:(g0 + 1) * period]
        whole = (g0, g1) == (0, n_groups)

        def sl(t):
            return t if whole else jax.tree.map(lambda a: a[g0:g1], t)

        def group_body(carry, xs, rows=rows):
            h, clen = carry
            if col_on:
                p_g, c_g, g_idx = xs
            else:
                p_g, c_g = xs
                g_idx = None
            new_c_g = {} if c_g is not None else None
            aux_g = []
            tel_g = {}
            for i in range(period):
                spec = specs[i]
                pos = positions  # absolute positions already supplied
                lidx = None if g_idx is None else g_idx * period + i
                h, c_i = _run_layer(
                    p_g[f"l{i:02d}"], cfg, spec, rows[i], h,
                    positions=pos, cross_states=cross_states,
                    cache=None if c_g is None else c_g[f"l{i:02d}"],
                    cache_len=clen, decode=decode, causal=causal,
                    layer_idx=lidx,
                    audit_label=(f"L{g0 * period + i}:"
                                 f"{g1 * period}:{period}"))
                if isinstance(c_i, dict) and "_moe_aux" in c_i:
                    aux_g.append(c_i.pop("_moe_aux"))
                if isinstance(c_i, dict) and "_telemetry" in c_i:
                    for k, v in c_i.pop("_telemetry").items():
                        tel_g[f"{i:02d}/{k}"] = v
                if new_c_g is not None:
                    new_c_g[f"l{i:02d}"] = c_i
            aux_stacked = jax.tree.map(lambda *xs: sum(xs), *aux_g) \
                if aux_g else {}
            return (h, clen), (new_c_g, aux_stacked, tel_g)

        body = group_body
        if cache is None:
            body = _checkpoint(group_body, cfg)

        g_ids = (jnp.arange(g0, g1),) if col_on else ()
        if gcache is not None:
            carry, (new_c_g, aux_scan, tel_scan) = jax.lax.scan(
                body, carry, (sl(gparams), sl(gcache)) + g_ids)
            new_gcache_runs.append(new_c_g)
        else:
            if col_on:
                def body_nocache(carry, xs):
                    p_g, g_idx = xs
                    return body(carry, (p_g, None, g_idx))
                scan_xs = (sl(gparams), g_ids[0])
            else:
                def body_nocache(carry, p_g):
                    return body(carry, (p_g, None))
                scan_xs = sl(gparams)
            carry, (_, aux_scan, tel_scan) = jax.lax.scan(
                body_nocache, carry, scan_xs)
        if aux_scan:
            add_aux({k: jnp.sum(v) for k, v in aux_scan.items()})
        # Per-layer telemetry: each scanned value is (g1 - g0,); unstack
        # into absolute layer indices (layer = group*period + position).
        for key, v in tel_scan.items():
            i, rest = int(key[:2]), key[3:]
            for g in range(g1 - g0):
                aux_total[f"tel/l{(g0 + g) * period + i:02d}/{rest}"] = v[g]

    x, _ = carry
    if gcache is not None:
        if len(new_gcache_runs) == 1:
            new_cache = {"groups": new_gcache_runs[0]}
        else:
            new_cache = {"groups": jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0),
                *new_gcache_runs)}
    else:
        new_cache = None
    return x, new_cache, aux_total
