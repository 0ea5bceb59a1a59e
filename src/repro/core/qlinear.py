"""Quantized linear primitive with per-role precision (the paper's §3 core).

``qmatmul(x2d, w, key, recipe)`` computes a matmul whose forward and two
backward matmuls each quantize their operands according to an independent
``QuantSpec`` (see ``core.recipe``).  The ``MatmulRecipe`` argument is one
resolved cell of a layer-resolved ``PrecisionPlan`` (layer x class), so
this primitive is depth-agnostic; per-layer precision is decided one level
up, in ``models.stack``.  Gradients flow by straight-through estimation
(App. B: the gradient of the quantized weight is passed to the master
weight unchanged).

Two implementations share the same recipe semantics:

  * ``qmatmul``        — unfused QDQ + ``lax.dot`` (simulation reference);
  * ``pallas_qmatmul`` — fwd, dgrad and wgrad each run through the fused
    Pallas pipeline (``kernels.fp4_matmul.fused_qmm``; by default one
    Pallas quantize pass per operand, then XLA's dot), with
    transposed-operand variants so the backward matmuls quantize
    relative to their own reduction axes without materializing
    ``w^T``/``x^T`` in HBM.  Stochastic-rounding specs are
    kernel-realizable (in-kernel PRNG noise seeded from ``key_data``);
    roles the kernel cannot realize (fp16 clipping, non-128 blocks) fall
    back to the QDQ path for that role only.
  * ``pallas_qmatmul_two_pass`` — the same contract pinned to the
    two-pass pipeline, whatever the process default.

The public entry point ``qlinear`` folds arbitrary leading batch dims and
selects the implementation via ``impl`` ('qdq' | 'pallas' |
'pallas_two_pass', threaded from ``ModelConfig.linear_impl``).  Stochastic rounding (beyond-paper option)
consumes the ``key`` argument; RTN recipes ignore it, and passthrough (bf16)
recipes lower to a single dot — important for clean roofline baselines.

Notes on backward quantization orientation: each backward matmul is treated
as a first-class matmul with its own reduction axis, and operand scales are
grouped relative to *that* matmul (per-token = per non-reduction vector;
per-block = (1 x 128) along the reduction axis; per-tile = 128x128).  These
are exactly the groupings an FP4/FP8 tensor-core epilogue can rescale.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import routing
from repro.core.packed import PackedTensor
from repro.core.quantize import BF16_SPEC, QuantSpec, qdq
from repro.core.recipe import MatmulRecipe
from repro.telemetry import collect as telemetry

__all__ = ["qmatmul", "pallas_qmatmul", "pallas_qmatmul_two_pass",
           "pallas_qmatmul_stats", "qlinear", "packed_linear", "dot_qdq",
           "kernel_quant_mode", "kernel_unsupported_reason", "matmul_impl"]


def _role_scope(role: Optional[str]):
    """``jax.named_scope`` marker attributing ops to a matmul role in the
    jaxpr/HLO (``qrole_fwd`` / ``qrole_dgrad`` / ``qrole_wgrad``).  Pure
    metadata: the computation is bit-identical with or without it."""
    if role is None:
        return contextlib.nullcontext()
    return jax.named_scope(f"qrole_{role}")


def _maybe_key(key_data: Optional[jnp.ndarray], spec: QuantSpec,
               salt: int) -> Optional[jax.Array]:
    if key_data is None or not spec.stochastic:
        return None
    key = jax.random.wrap_key_data(key_data.astype(jnp.uint32))
    return jax.random.fold_in(key, salt)


def dot_qdq(a: jnp.ndarray, b: jnp.ndarray,
            spec_a: QuantSpec, spec_b: QuantSpec,
            *, key_data: Optional[jnp.ndarray] = None,
            salt: int = 0, precision=None,
            axes_a=None, axes_b=None,
            role: Optional[str] = None, route: str = "qdq",
            reasons: Tuple[str, ...] = (), cell=None) -> jnp.ndarray:
    """QDQ both operands of ``a @ b`` then run the dot in the input dtype.

    ``a``: (M, K), ``b``: (K, N).  Reduction axes: 1 for a, 0 for b.
    ``axes_a``/``axes_b``: optional logical (row, col) names for SPMD scale
    placement (see ``quantize.scale_logical_axes``).

    ``role``/``route``/``reasons``/``cell`` are static observability
    metadata: when a routing census is active (``core.routing.capture``)
    the call records one event, and the whole dot is wrapped in a
    ``qrole_<role>`` named scope for jaxpr/HLO attribution.  ``route`` is
    ``"qdq"`` for a configured QDQ impl and ``"qdq_fallback"`` (with
    structured ``reasons``) when a pallas impl could not realize the
    specs; ``cell`` carries the (layer, class) labels captured in scope
    by ``qlinear`` (custom_vjp rules trace out of scope).
    """
    if role is not None and routing.active() is not None:
        routing.record(
            role, route, spec_a.to_str(), spec_b.to_str(), reasons=reasons,
            sr_a=bool(spec_a.stochastic) and key_data is not None,
            sr_b=bool(spec_b.stochastic) and key_data is not None,
            cell=cell)
    with _role_scope(role):
        aq = qdq(a, spec_a, reduction_axis=1,
                 stochastic_key=_maybe_key(key_data, spec_a, salt),
                 axes=axes_a)
        bq = qdq(b, spec_b, reduction_axis=0,
                 stochastic_key=_maybe_key(key_data, spec_b, salt + 1),
                 axes=axes_b)
        return jax.lax.dot(aq, bq, precision=precision)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def qmatmul(x: jnp.ndarray, w: jnp.ndarray, key_data: jnp.ndarray,
            recipe: MatmulRecipe, axes=None, cell=None) -> jnp.ndarray:
    """y = Q(x) @ Q(w) with recipe-defined backward quantization.

    x: (M, K) activations, w: (K, N) weights, key_data: uint32[2] raw PRNG
    key material (only consumed by stochastic QuantSpecs), y: (M, N).
    ``axes``: optional logical names ``(row, k, n)`` of the matmul dims —
    static metadata steering operand/scale sharding in all three matmuls
    (fwd here, dgrad/wgrad in the vjp, each in its own orientation).
    ``cell``: optional static (layer, class) labels for the routing
    census (``core.routing``) — metadata only, no effect on the graph.
    """
    ax = axes or (None, None, None)
    return dot_qdq(x, w, recipe.fwd_x, recipe.fwd_w, key_data=key_data,
                   salt=0, axes_a=(ax[0], ax[1]), axes_b=(ax[1], ax[2]),
                   role="fwd", cell=cell)


def _qmatmul_fwd(x, w, key_data, recipe, axes, cell):
    y = qmatmul(x, w, key_data, recipe, axes, cell)
    return y, (x, w, key_data)


def _qmatmul_bwd(recipe, axes, cell, res, g):
    x, w, key_data = res
    row, k, n = axes or (None, None, None)
    # dgrad: dx = Q(g) @ Q(w^T); reduction over N.
    dx = dot_qdq(g, w.T, recipe.dgrad_g, recipe.dgrad_w, key_data=key_data,
                 salt=2, axes_a=(row, n), axes_b=(n, k), role="dgrad",
                 cell=cell)
    # wgrad: dw = Q(x^T) @ Q(g); reduction over M (tokens).
    dw = dot_qdq(x.T, g, recipe.wgrad_x, recipe.wgrad_g, key_data=key_data,
                 salt=4, axes_a=(k, row), axes_b=(row, n), role="wgrad",
                 cell=cell)
    return (dx.astype(x.dtype), dw.astype(w.dtype),
            jnp.zeros_like(key_data))


qmatmul.defvjp(_qmatmul_fwd, _qmatmul_bwd)


# ---------------------------------------------------------------------------
# Fused-kernel implementation (pallas_qmatmul)
# ---------------------------------------------------------------------------

_KERNEL_BLOCK = 128


def kernel_unsupported_reason(spec: QuantSpec) -> Optional[str]:
    """Why the fused pipeline cannot realize ``spec``, or None if it can.

    Returns a structured ``"<code>: <detail>"`` string — the vocabulary
    the routing census records for QDQ fallbacks and ``analysis.qlint``
    surfaces (and tests assert on):

      ``unsupported_dtype``        fp16 is a clip-only codec (no grid the
                                   integer-RTN kernel can round to);
      ``unsupported_block``        block/tile granularity with a group
                                   size other than the kernel's 128;
      ``unsupported_granularity``  a granularity the kernel has no
                                   quantize mode for.
    """
    if spec.is_passthrough:
        return None
    if spec.fmt == "fp16":
        return ("unsupported_dtype: fp16 is clip-only (no kernel "
                "rounding grid)")
    if spec.granularity in ("block", "tile"):
        if spec.block != _KERNEL_BLOCK:
            return (f"unsupported_block: {spec.granularity}{spec.block} "
                    f"(kernel group size is {_KERNEL_BLOCK})")
        return None
    if spec.granularity in ("token", "tensor"):
        return None
    return f"unsupported_granularity: {spec.granularity!r}"


def kernel_quant_mode(spec: QuantSpec) -> Optional[str]:
    """The fused pipeline's quantization mode realizing ``spec``, or None.

    ``pass``            bf16/fp32 passthrough roles;
    ``block``           per-(1 x 128) groups along the reduction axis;
    ``tile``            per-(128 x 128) tiles;
    ``token``/``tensor`` amax group spans the full reduction axis — the
                        quantize pass computes it with a two-sweep grid
                        (no external scale precompute).

    Stochastic rounding is kernel-realizable since the quantize-once
    rework (in-kernel PRNG noise).  None means unrealizable — the caller
    falls back to QDQ for that role, and
    :func:`kernel_unsupported_reason` says why (the structured reason the
    routing census records).
    """
    if kernel_unsupported_reason(spec) is not None:
        return None
    if spec.is_passthrough:
        return "pass"
    return spec.granularity


def _dot_fused(a: jnp.ndarray, b: jnp.ndarray,
               spec_a: QuantSpec, spec_b: QuantSpec,
               *, trans_a: bool = False, trans_b: bool = False,
               key_data: Optional[jnp.ndarray] = None,
               salt: int = 0, collect_stats: bool = False,
               pipeline: Optional[str] = None,
               axes_a=None, axes_b=None,
               role: Optional[str] = None, cell=None):
    """One matmul role through the fused Pallas pipeline when its specs are
    kernel-realizable, else through ``dot_qdq`` (transposes materialized).

    ``a``/``b`` are the STORED arrays; the effective operands are
    ``a^T``/``b^T`` under the trans flags, and quantization granularities
    apply in effective orientation (reduction-relative).  Stochastic specs
    consume ``key_data`` through the kernel's in-kernel PRNG (different
    stream than the QDQ path's ``jax.random`` — statistically equivalent,
    not bit-equal).  ``pipeline``: None = the process default (two-pass
    unless overridden via ``use_pipeline``, resolved at trace
    time), or an explicit ``kernels.fp4_matmul.PIPELINES`` name.  With
    ``collect_stats`` returns ``(y, (sa, sb))`` raw quantize stat vectors
    (None for pass/fallback operands).
    """
    mode_a, mode_b = kernel_quant_mode(spec_a), kernel_quant_mode(spec_b)
    if mode_a is not None and mode_b is not None:
        # Deferred import: kernels.ops pulls in models.attention (cycle via
        # this module at import time).
        from repro.kernels.ops import pallas_qmm
        from repro.nn.layers import get_sharding_context
        with _role_scope(role):
            return pallas_qmm(a, b, spec_a, spec_b,
                              mode_a=mode_a, mode_b=mode_b,
                              trans_a=trans_a, trans_b=trans_b,
                              key_data=key_data, salt=salt,
                              pipeline=pipeline,
                              collect_stats=collect_stats, role=role,
                              cell=cell, axes_a=axes_a, axes_b=axes_b,
                              rules=get_sharding_context())
    reasons = tuple(
        f"{operand}: {why}"
        for operand, spec in (("lhs", spec_a), ("rhs", spec_b))
        for why in (kernel_unsupported_reason(spec),) if why is not None)
    ae = a.T if trans_a else a
    be = b.T if trans_b else b
    y = dot_qdq(ae, be, spec_a, spec_b, key_data=key_data, salt=salt,
                axes_a=axes_a, axes_b=axes_b,
                role=role, route="qdq_fallback", reasons=reasons, cell=cell)
    return (y, (None, None)) if collect_stats else y


def _make_pallas_qmatmul(pipeline: Optional[str]):
    """Build a ``qmatmul``-shaped custom_vjp whose three roles all run
    through the fused kernel with a fixed ``pipeline`` choice (None = the
    process default).  Returns ``(qmatmul_fn, bwd_fn)`` — the bwd is shared
    with the stats variant below."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
    def _pqm(x: jnp.ndarray, w: jnp.ndarray, key_data: jnp.ndarray,
             recipe: MatmulRecipe, axes=None, cell=None) -> jnp.ndarray:
        ax = axes or (None, None, None)
        return _dot_fused(x, w, recipe.fwd_x, recipe.fwd_w,
                          key_data=key_data, salt=0, pipeline=pipeline,
                          axes_a=(ax[0], ax[1]), axes_b=(ax[1], ax[2]),
                          role="fwd", cell=cell)

    def _fwd(x, w, key_data, recipe, axes, cell):
        return _pqm(x, w, key_data, recipe, axes, cell), (x, w, key_data)

    def _bwd(recipe, axes, cell, res, g):
        x, w, key_data = res
        row, k, n = axes or (None, None, None)
        # dgrad: dx = Q(g) @ Q(w^T); reduction over N (w read transposed
        # in-kernel via the BlockSpec index map).
        dx = _dot_fused(g, w, recipe.dgrad_g, recipe.dgrad_w, trans_b=True,
                        key_data=key_data, salt=2, pipeline=pipeline,
                        axes_a=(row, n), axes_b=(n, k), role="dgrad",
                        cell=cell)
        # wgrad: dw = Q(x^T) @ Q(g); reduction over M (tokens).
        dw = _dot_fused(x, g, recipe.wgrad_x, recipe.wgrad_g, trans_a=True,
                        key_data=key_data, salt=4, pipeline=pipeline,
                        axes_a=(k, row), axes_b=(row, n), role="wgrad",
                        cell=cell)
        return (dx.astype(x.dtype), dw.astype(w.dtype),
                jnp.zeros_like(key_data))

    _pqm.defvjp(_fwd, _bwd)
    return _pqm, _bwd


pallas_qmatmul, _pallas_qmatmul_bwd = _make_pallas_qmatmul(None)
pallas_qmatmul.__doc__ = (
    """``qmatmul`` with all three matmuls (fwd/dgrad/wgrad) running through
    the fused quantize+matmul pipeline (default: a quantize pass per
    operand, then XLA's dot; see ``kernels.fp4_matmul``).  Same
    signature/semantics.
    ``axes`` only steers the QDQ-fallback roles (kernel scales live in
    kernel-private buffers and need no placement).""")

pallas_qmatmul_two_pass, _ = _make_pallas_qmatmul("two_pass")
pallas_qmatmul_two_pass.__doc__ = (
    """``pallas_qmatmul`` pinned to the two-pass pipeline (quantize pass
    + XLA's dot) whatever the process default; the streaming pipeline
    differs from it only in the f32 accumulation order along K.""")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def pallas_qmatmul_stats(x: jnp.ndarray, w: jnp.ndarray,
                         key_data: jnp.ndarray, recipe: MatmulRecipe,
                         cell=None):
    """``pallas_qmatmul`` that additionally returns the forward quantize
    pass's telemetry-epilogue vectors ``(y, (stats_x, stats_w))``.

    The stats come from the SAME kernel invocation that quantizes the
    operands for the dot (no second QDQ pass); ``y`` is bit-identical to
    ``pallas_qmatmul``.  Pass/fallback slots are None.  Gradients match
    ``pallas_qmatmul`` (stat outputs carry no cotangent).
    """
    return _dot_fused(x, w, recipe.fwd_x, recipe.fwd_w, key_data=key_data,
                      salt=0, collect_stats=True, role="fwd", cell=cell)


def _pallas_qmatmul_stats_fwd(x, w, key_data, recipe, cell):
    out = pallas_qmatmul_stats(x, w, key_data, recipe, cell)
    return out, (x, w, key_data)


def _pallas_qmatmul_stats_bwd(recipe, cell, res, ct):
    g = ct[0]
    return _pallas_qmatmul_bwd(recipe, None, cell, res, g)


pallas_qmatmul_stats.defvjp(_pallas_qmatmul_stats_fwd,
                            _pallas_qmatmul_stats_bwd)

_IMPLS = {"qdq": qmatmul, "pallas": pallas_qmatmul,
          "pallas_two_pass": pallas_qmatmul_two_pass}


def matmul_impl(impl: str):
    """Resolve a ``linear_impl`` config value to its qmatmul function."""
    try:
        return _IMPLS[impl]
    except KeyError:
        raise ValueError(
            f"unknown linear_impl {impl!r}; have {sorted(_IMPLS)}") from None


def _zero_key() -> jnp.ndarray:
    # NOTE: must be constructed fresh per trace (a cached global would leak
    # tracers out of scan/remat scopes); XLA constant-folds it anyway.
    return jnp.zeros((2,), jnp.uint32)


def _hint2d(arr: jnp.ndarray, axes) -> jnp.ndarray:
    """Sharding hint by logical axis names (lazy import: nn.layers imports
    this module at load time; no context or no names -> no-op)."""
    if axes is None or all(a is None for a in axes):
        return arr
    from repro.nn.layers import shard_hint
    return shard_hint(arr, axes)


def packed_linear(x: jnp.ndarray, w: PackedTensor, recipe: MatmulRecipe,
                  *, bias: Optional[jnp.ndarray] = None,
                  key_data: Optional[jnp.ndarray] = None,
                  impl: str = "qdq",
                  axes: Optional[Tuple[Optional[str], Optional[str],
                                       Optional[str]]] = None
                  ) -> jnp.ndarray:
    """Serving-side linear over a quantize-once ``PackedTensor`` panel.

    The RHS was quantized exactly once at load time (payload + per-tile
    scales); here it is expanded by a table gather — bitwise identical to
    the training QDQ of the same spec — and fed to the matmul as a
    PASSTHROUGH operand, so no per-token weight re-quantization happens:

      * passthrough activation spec -> plain dot (weight-only serving);
      * pallas impls with a kernel-realizable activation spec -> the
        fused pipeline via ``kernels.ops``, RHS in mode ``pass`` (the
        quantize pass covers only the activations; the pre-quantized
        weight goes to the dot as it is);
      * otherwise -> QDQ fallback for the activation side only.

    Forward-only by design (serving): gradients, telemetry taps and the
    custom_vjp STE machinery of the training path do not apply here.
    """
    lead: Tuple[int, ...] = x.shape[:-1]
    k = x.shape[-1]
    w_dq = w.dequantize().astype(x.dtype)
    spec_x = recipe.fwd_x
    x2d = _hint2d(x.reshape(-1, k), axes and axes[:2])
    if spec_x.is_passthrough:
        if routing.active() is not None:
            routing.record("fwd", "packed_dot", spec_x.to_str(),
                           recipe.fwd_w.to_str())
        y = x2d @ w_dq
    else:
        if key_data is None:
            key_data = _zero_key()
        cell = routing.current_cell()
        if (impl in ("pallas", "pallas_two_pass")
                and kernel_quant_mode(spec_x) is not None):
            pipeline = "two_pass" if impl == "pallas_two_pass" else None
            ax = axes or (None, None, None)
            y = _dot_fused(x2d, w_dq, spec_x, BF16_SPEC, key_data=key_data,
                           salt=0, pipeline=pipeline,
                           axes_a=(ax[0], ax[1]), axes_b=(ax[1], ax[2]),
                           role="fwd", cell=cell)
        else:
            route, reasons = "qdq", ()
            if impl in ("pallas", "pallas_two_pass"):
                route = "qdq_fallback"
                reasons = (f"lhs: {kernel_unsupported_reason(spec_x)}",)
            ax = axes or (None, None, None)
            y = dot_qdq(x2d, w_dq, spec_x, BF16_SPEC, key_data=key_data,
                        salt=0, axes_a=(ax[0], ax[1]),
                        axes_b=(ax[1], ax[2]),
                        role="fwd", route=route, reasons=reasons,
                        cell=cell)
    y = _hint2d(y, axes and (axes[0], axes[2]))
    y = y.reshape(*lead, w_dq.shape[-1])
    if bias is not None:
        y = y + bias
    return y


def qlinear(x: jnp.ndarray, w: jnp.ndarray, recipe: MatmulRecipe,
            *, bias: Optional[jnp.ndarray] = None,
            key_data: Optional[jnp.ndarray] = None,
            impl: str = "qdq",
            axes: Optional[Tuple[Optional[str], Optional[str],
                                 Optional[str]]] = None) -> jnp.ndarray:
    """Linear layer over the last axis of ``x`` with per-role quantization.

    ``x``: (..., K), ``w``: (K, N) -> (..., N).  ``impl`` selects the
    matmul implementation ('qdq' unfused simulation | 'pallas' fused
    kernel); passthrough recipes lower to a plain dot either way.
    ``axes`` optionally names the logical matmul dims ``(tokens, K, N)``:
    when a sharding context is installed the flattened activation view and
    every per-granularity scale tensor (fwd, dgrad, wgrad — each in its own
    orientation) get ``with_sharding_constraint`` hints so the quantize-once
    K-panels partition cleanly under GSPMD.
    """
    if isinstance(w, PackedTensor):
        # quantize-once serving panels take the forward-only packed path
        return packed_linear(x, w, recipe, bias=bias, key_data=key_data,
                             impl=impl, axes=axes)
    lead: Tuple[int, ...] = x.shape[:-1]
    k = x.shape[-1]
    if recipe.is_passthrough:
        if routing.active() is not None:
            routing.record("fwd", "dot", recipe.fwd_x.to_str(),
                           recipe.fwd_w.to_str())
        y = _hint2d(x.reshape(-1, k), axes and axes[:2]) @ w
    else:
        if key_data is None:
            key_data = _zero_key()
        x2d = _hint2d(x.reshape(-1, k), axes and axes[:2])
        # Telemetry taps (no-ops unless a collector is installed).
        # fwd-computable operand stats go to the active collection frame;
        # grad_tap transports dgrad_g/wgrad_g cotangent stats out via the
        # layer-indexed probe-gradient channel (the collector knows the
        # current layer, so nothing layer-shaped threads through here).  On the pallas impl the fwd_x/fwd_w slots
        # come from the quantize pass's telemetry EPILOGUE — the very kernel
        # that feeds the dot — instead of tap_matmul re-running QDQ math;
        # the remaining fwd-side slots (wgrad_x, dgrad_w: different
        # orientation, only quantized in the backward) keep the tap path.
        fused_fwd = None
        y = None
        cell = routing.current_cell()
        if impl == "pallas" and telemetry.active() is not None:
            ma = kernel_quant_mode(recipe.fwd_x)
            mb = kernel_quant_mode(recipe.fwd_w)
            if (ma is not None and mb is not None
                    and (ma != "pass" or mb != "pass")):
                from repro.kernels.fp4_matmul import finalize_quant_stats
                y, (sa, sb) = pallas_qmatmul_stats(x2d, w, key_data, recipe,
                                                   cell)
                fused_fwd = {
                    "fwd_x": finalize_quant_stats(sa) if sa is not None
                    else None,
                    "fwd_w": finalize_quant_stats(sb) if sb is not None
                    else None,
                }
        telemetry.tap_matmul(x2d, w, recipe, fused_fwd=fused_fwd)
        if y is None:
            y = matmul_impl(impl)(x2d, w, key_data, recipe, axes, cell)
        y = telemetry.grad_tap(y, recipe)
    y = _hint2d(y, axes and (axes[0], axes[2]))
    y = y.reshape(*lead, w.shape[-1])
    if bias is not None:
        y = y + bias
    return y
