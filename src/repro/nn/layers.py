"""Stateless layer math: linears, activations, norms, RoPE, sharding hints."""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qlinear import qlinear
from repro.core.recipe import MatmulRecipe
from repro.telemetry.profiler import graph_span

__all__ = ["linear", "gelu", "silu", "relu2", "layer_norm", "rms_norm",
           "apply_norm", "rope", "sincos_positions", "shard_hint",
           "set_sharding_context", "get_sharding_context"]


def linear(x: jnp.ndarray, w: jnp.ndarray, recipe: MatmulRecipe, cfg,
           *, bias: Optional[jnp.ndarray] = None,
           key_data: Optional[jnp.ndarray] = None,
           axes: Optional[Tuple[Optional[str], Optional[str],
                                Optional[str]]] = None) -> jnp.ndarray:
    """Quantized linear over the last axis of ``x``, selecting the matmul
    implementation from ``cfg.linear_impl`` ('qdq' | 'pallas').

    The single call site models use for every recipe-carrying linear, so the
    config knob reaches fwd, dgrad and wgrad of all of them.  ``recipe`` is
    one cell of the active ``PrecisionPlan`` — the layer-resolved row the
    stack looked up for this layer and module class — so per-layer
    precision requires no plumbing below this point.  ``cfg`` is required:
    a call site that forgot it would otherwise silently ignore the user's
    ``linear_impl`` setting.  ``axes`` names the logical matmul dims
    ``(tokens, K, N)`` for SPMD activation/scale placement (see qlinear).
    """
    return qlinear(x, w, recipe, bias=bias, key_data=key_data,
                   impl=cfg.linear_impl, axes=axes)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def silu(x):
    return jax.nn.silu(x)


def relu2(x):
    """Squared ReLU (nemotron-4)."""
    r = jax.nn.relu(x)
    return r * r


ACTIVATIONS = {"gelu": gelu, "silu": silu, "relu2": relu2}


def rms_norm(x, scale, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * (1.0 + scale.astype(jnp.float32)) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


@graph_span("norm")
def apply_norm(params: dict, x, kind: str):
    if kind == "rmsnorm":
        return rms_norm(x, params["scale"])
    if kind == "layernorm":
        return layer_norm(x, params["scale"], params["bias"])
    raise ValueError(kind)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary position embedding.

    x: (..., seq, heads, head_dim), positions: (seq,) or (batch, seq).
    """
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., seq, half)
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    if positions.ndim == 1:
        cos, sin = cos[None], sin[None]  # add batch dim
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sincos_positions(seq_len: int, dim: int) -> jnp.ndarray:
    """Fixed sinusoidal position embeddings (whisper encoder)."""
    pos = np.arange(seq_len, dtype=np.float32)[:, None]
    i = np.arange(dim // 2, dtype=np.float32)[None, :]
    ang = pos / (10000.0 ** (2 * i / dim))
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb)


# ---------------------------------------------------------------------------
# Activation sharding hints.
#
# Model code calls ``shard_hint(x, ('batch', 'seq', 'embed'))``; the launcher
# installs a context mapping logical activation axes to mesh axes.  Without a
# context (unit tests, CPU) this is a no-op, so model code stays portable.
# ---------------------------------------------------------------------------

_CTX = threading.local()


def set_sharding_context(ctx) -> None:
    """Install a sharding context (see distributed.sharding.ShardingRules)."""
    _CTX.value = ctx


def get_sharding_context():
    return getattr(_CTX, "value", None)


@contextlib.contextmanager
def sharding_context(ctx):
    prev = get_sharding_context()
    set_sharding_context(ctx)
    try:
        yield
    finally:
        set_sharding_context(prev)


def shard_hint(x: jnp.ndarray,
               axes: Sequence[Optional[str]]) -> jnp.ndarray:
    """Constrain ``x``'s sharding by logical activation axis names (no-op
    when no sharding context is installed)."""
    ctx = get_sharding_context()
    if ctx is None:
        return x
    sharding = ctx.activation_sharding(tuple(axes), x.shape)
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)
