"""Dense FFN sublayer — the paper's FP4 target (§3.2 Gradient-sensitive)."""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.recipe import MatmulRecipe
from repro.nn.layers import ACTIVATIONS, linear, shard_hint
from repro.nn.params import ParamSpec
from repro.telemetry.profiler import graph_span

__all__ = ["mlp_param_specs", "mlp"]


def mlp_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    down_scale = 1.0 / np.sqrt(f * max(cfg.n_layers, 1))
    if cfg.activation == "swiglu":
        return {
            "w_gate": ParamSpec((d, f), ("embed", "mlp")),
            "w_up": ParamSpec((d, f), ("embed", "mlp")),
            "w_down": ParamSpec((f, d), ("mlp", "embed"), scale=down_scale),
        }
    return {
        "w_up": ParamSpec((d, f), ("embed", "mlp")),
        "w_down": ParamSpec((f, d), ("mlp", "embed"), scale=down_scale),
    }


@graph_span("mlp")
def mlp(params: Dict[str, jnp.ndarray], cfg: ModelConfig, x: jnp.ndarray,
        recipe: MatmulRecipe) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D).  All matmuls quantized per ``recipe`` —
    this layer's ffn cell of the active ``PrecisionPlan``;
    the nonlinearity stays in the compute dtype (§3.2: there is always a
    nonlinear op between linear layers that needs precise representation)."""
    up_axes = ("tokens", "embed", "mlp")
    if cfg.activation == "swiglu":
        g = linear(x, params["w_gate"], recipe, cfg, axes=up_axes)
        u = linear(x, params["w_up"], recipe, cfg, axes=up_axes)
        h = ACTIVATIONS["silu"](g) * u
    else:
        h = ACTIVATIONS[cfg.activation](
            linear(x, params["w_up"], recipe, cfg, axes=up_axes))
    h = shard_hint(h, ("batch", "seq", "mlp"))
    return linear(h, params["w_down"], recipe, cfg,
                  axes=("tokens", "mlp", "embed"))
