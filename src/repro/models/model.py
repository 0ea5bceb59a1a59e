"""Unified model API: build_model(cfg) -> Model with init/loss/prefill/decode.

Families: dense | moe | vlm | audio (enc-dec) | ssm | hybrid — all assembled
from the unified stack (models.stack).  Precision enters exclusively through
the ``plan`` argument: a layer-resolved ``PrecisionPlan`` (or a
``PrecisionRecipe`` class template, coerced to the uniform plan via
``core.recipe.as_plan``) threaded to every linear.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.packed import PackedTensor
from repro.core.recipe import PrecisionPlan, as_plan
from repro.models import stack as stack_lib
from repro.nn.layers import (apply_norm, linear, shard_hint,
                             sincos_positions)
from repro.nn.params import ParamSpec, init_params, param_count, spec_shapes
from repro.telemetry import collect as telemetry
from repro.telemetry.profiler import graph_span

__all__ = ["Model", "build_model"]


_PARAM_SCOPES = {"embed": "embed", "pos_embed": "embed", "head": "head",
                 "wq": "attn", "wk": "attn", "wv": "attn", "wo": "attn",
                 "w_gate": "mlp", "w_up": "mlp", "w_down": "mlp"}


def _param_scope(keys) -> Optional[str]:
    """The layer scope of the ops that use the parameter at ``keys`` (its
    path's dict keys; None for a list index)."""
    if any(isinstance(k, str) and k.endswith("norm") for k in keys[:-1]):
        return "norm"
    return next((_PARAM_SCOPES[k] for k in reversed(keys)
                 if k in _PARAM_SCOPES), None)


def _encoder_cfg(cfg: ModelConfig) -> ModelConfig:
    return cfg.replace(n_layers=cfg.n_encoder_layers, family="dense",
                       cross_attn_period=0, attn_layer_period=0, moe=None,
                       sliding_window=0)


class Model:
    """Functional model wrapper (all methods pure; params passed in)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.is_encdec = cfg.family == "audio"
        self.has_cross_inputs = cfg.family in ("vlm", "audio")

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        d = cfg.d_model
        specs: Dict[str, Any] = {
            "embed": ParamSpec((cfg.vocab_size, d), ("vocab", "embed"),
                               init="embed"),
            "final_norm": stack_lib._norm_specs(cfg),
            "stack": stack_lib.stack_param_specs(cfg),
        }
        if cfg.pos_emb == "learned":
            specs["pos_embed"] = ParamSpec((cfg.max_seq_len, d),
                                           (None, "embed"), init="embed")
        if not cfg.tie_embeddings:
            specs["head"] = ParamSpec((d, cfg.vocab_size),
                                      ("embed", "vocab"),
                                      scale=1.0 / np.sqrt(d))
        if self.is_encdec:
            enc = _encoder_cfg(cfg)
            specs["encoder"] = {
                "stack": stack_lib.stack_param_specs(enc),
                "final_norm": stack_lib._norm_specs(enc),
            }
        return specs

    def init(self, key: jax.Array, dtype=jnp.float32):
        return init_params(key, self.param_specs(), dtype)

    def cast_params(self, params):
        """FP32 master -> compute-dtype copy (explicit-dtype specs, e.g. the
        FP32 router / mamba dt/A params, keep their dtype).  PackedTensor
        leaves (quantize-once serving panels) pass through unchanged — they
        are expanded to the compute dtype at their consuming matmul."""
        specs = self.param_specs()

        def cast(path, p, s):
            if isinstance(p, PackedTensor):
                return p
            if s.dtype is None and jnp.issubdtype(p.dtype, jnp.floating):
                # the cast is the work of the layer that uses the weight
                scope = _param_scope([getattr(k, "key", None)
                                      for k in path])
                with (graph_span(scope) if scope
                      else contextlib.nullcontext()):
                    return p.astype(self._dt)
            return p

        return jax.tree_util.tree_map_with_path(
            cast, params, specs,
            is_leaf=lambda x: isinstance(x, PackedTensor))

    def abstract_params(self, dtype=jnp.float32):
        return spec_shapes(self.param_specs(), dtype)

    def param_count(self) -> int:
        return param_count(self.param_specs())

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k of num_experts)."""
        cfg = self.cfg
        total = self.param_count()
        if cfg.moe is None:
            return total
        st = cfg.moe
        expert_leaves = 0
        specs = self.param_specs()
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, ParamSpec))[0]:
            if "experts" in (leaf.axes or ()):
                expert_leaves += int(np.prod(leaf.shape))
        inactive = expert_leaves * (1.0 - st.top_k / st.num_experts)
        return int(total - inactive)

    # ------------------------------------------------------------------
    # Embedding / head
    # ------------------------------------------------------------------

    @graph_span("embed")
    def _embed(self, params, tokens: jnp.ndarray,
               positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.cfg
        x = params["embed"].astype(self._dt)[tokens]
        if cfg.pos_emb == "learned":
            pos = (jnp.arange(tokens.shape[1], dtype=jnp.int32)
                   if positions is None else positions)
            pe = params["pos_embed"].astype(self._dt)[pos]
            # (Sq,) positions broadcast over batch; (B, Sq) per-slot
            # positions (batched decode engine) index per row directly
            x = x + (pe if pos.ndim == tokens.ndim else pe[None])
        return shard_hint(x, ("batch", "seq", "embed"))

    @graph_span("head")
    def _head(self, params, x: jnp.ndarray,
              plan: PrecisionPlan) -> jnp.ndarray:
        cfg = self.cfg
        x = apply_norm(params["final_norm"], x, cfg.norm)
        if cfg.tie_embeddings:
            w = params["embed"].astype(self._dt).T
        else:
            w = params["head"].astype(self._dt)
        with telemetry.module_scope("head"):
            logits = linear(x, w, plan.head_linear, cfg,
                            axes=("tokens", "embed", "vocab"))
        return shard_hint(logits, ("batch", "seq", "vocab"))

    def _plan(self, p) -> PrecisionPlan:
        """Coerce a recipe (class template) or plan to this model's
        depth-resolved plan (see ``core.recipe.as_plan``)."""
        return as_plan(p, self.cfg.n_layers)

    @property
    def _dt(self):
        return jnp.dtype(self.cfg.dtype)

    # ------------------------------------------------------------------
    # Encoder (audio enc-dec)
    # ------------------------------------------------------------------

    def _encode(self, params, frames: jnp.ndarray,
                plan: PrecisionPlan) -> jnp.ndarray:
        """frames: precomputed conv-frontend embeddings (B, F, D) — stub per
        assignment; adds sinusoidal positions and runs the encoder stack.
        The decoder's plan is depth-resized onto the encoder stack
        (proportional row mapping; exact for uniform plans)."""
        enc = _encoder_cfg(self.cfg)
        x = frames.astype(self._dt)
        x = x + sincos_positions(x.shape[1], enc.d_model).astype(self._dt)
        x, _, _ = stack_lib.run_stack(
            params["encoder"]["stack"], enc, plan.resize(enc.n_layers), x,
            causal=False, indexed_probes=False)
        return apply_norm(params["encoder"]["final_norm"], x, enc.norm)

    def _cross_states(self, params, batch, plan) -> Optional[jnp.ndarray]:
        if self.cfg.family == "vlm":
            return batch["vision"].astype(self._dt)
        if self.cfg.family == "audio":
            return self._encode(params, batch["frames"], plan)
        return None

    # ------------------------------------------------------------------
    # Training forward / loss
    # ------------------------------------------------------------------

    def forward(self, params, batch: Dict[str, jnp.ndarray],
                plan) -> Tuple[jnp.ndarray, Dict]:
        """Full training-mode forward.  batch['tokens']: (B, S) int32."""
        cfg = self.cfg
        plan = self._plan(plan)
        params = self.cast_params(params)
        tokens = batch["tokens"]
        x = self._embed(params, tokens)
        cross = self._cross_states(params, batch, plan)
        x, _, aux = stack_lib.run_stack(
            params["stack"], cfg, plan, x, cross_states=cross)
        logits = self._head(params, x, plan)
        return logits, aux

    def hidden(self, params, batch: Dict[str, jnp.ndarray],
               plan) -> Tuple[jnp.ndarray, Dict]:
        """Training-mode forward up to (but excluding) the LM head."""
        cfg = self.cfg
        plan = self._plan(plan)
        params = self.cast_params(params)
        x = self._embed(params, batch["tokens"])
        cross = self._cross_states(params, batch, plan)
        x, _, aux = stack_lib.run_stack(
            params["stack"], cfg, plan, x, cross_states=cross)
        return apply_norm(params["final_norm"], x, cfg.norm), aux

    def _head_weight(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].astype(self._dt).T
        return params["head"].astype(self._dt)

    @staticmethod
    @graph_span("head")
    def _xent_terms(logits: jnp.ndarray, targets: jnp.ndarray):
        """Returns (sum nll, sum lse^2, n_tokens) over masked positions."""
        mask = (targets >= 0)
        lt = jnp.where(mask, targets, 0)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lt[..., None],
                                   axis=-1).squeeze(-1)
        nll = jnp.sum((lse - gold) * mask)
        z2 = jnp.sum((lse * mask) ** 2)
        return nll, z2, mask.sum()

    @graph_span("head")
    def _chunked_xent(self, params, h: jnp.ndarray, targets: jnp.ndarray,
                      plan: PrecisionPlan):
        """``_xent_terms`` of the head's logits, seq-chunked under remat."""
        cfg = self.cfg
        w = self._head_weight(self.cast_params(params))
        c = cfg.loss_chunk
        s = h.shape[1]
        assert s % c == 0, (s, c)
        hc = h.reshape(h.shape[0], s // c, c, -1).transpose(1, 0, 2, 3)
        tc = targets.reshape(targets.shape[0], s // c, c).transpose(1, 0, 2)

        @jax.checkpoint
        def chunk_terms(h_c, t_c):
            # telemetry stays off in here: stats pushed from inside the
            # chunk scan could not legally escape its trace scope.
            with telemetry.suppressed():
                logits = linear(h_c, w, plan.head_linear, cfg,
                                axes=("tokens", "embed", "vocab"))
            return self._xent_terms(logits, t_c)

        def body(carry, xs):
            nll, z2, n = carry
            d_nll, d_z2, d_n = chunk_terms(*xs)
            return (nll + d_nll, z2 + d_z2, n + d_n), None

        return jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                   jnp.zeros((), jnp.int32)), (hc, tc))[0]

    def loss(self, params, batch: Dict[str, jnp.ndarray],
             plan) -> Tuple[jnp.ndarray, Dict]:
        """Next-token cross-entropy (fp32).  targets==-1 masks a position.

        With ``cfg.loss_chunk > 0`` the head matmul + xent run seq-chunked
        under remat, so the (B, S, vocab) logits are never materialized —
        required for the 128k-256k-vocab configs at train_4k scale.
        """
        cfg = self.cfg
        plan = self._plan(plan)
        targets = batch["targets"]
        if not cfg.loss_chunk:
            logits, aux = self.forward(params, batch, plan)
            nll, z2, n = self._xent_terms(logits, targets)
        else:
            h, aux = self.hidden(params, batch, plan)
            nll, z2, n = self._chunked_xent(params, h, targets, plan)
        denom = jnp.maximum(n, 1)
        loss = nll / denom
        metrics = {"loss": loss, "tokens": denom}
        if self.cfg.z_loss:
            zl = self.cfg.z_loss * z2 / denom
            loss = loss + zl
            metrics["z_loss"] = zl
        for k, v in aux.items():
            metrics[k] = v
            if k in ("moe_load_balance", "moe_router_z"):
                loss = loss + v
        metrics["total_loss"] = loss
        return loss, metrics

    # ------------------------------------------------------------------
    # Serving: prefill + decode
    # ------------------------------------------------------------------

    def cache_spec(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   per_slot: bool = False):
        """``per_slot=True`` gives every batch row its own length/position
        tracking (the batched continuous-decode engine's slot cache):
        ``length`` becomes ``(batch,)`` and attention ``pos`` buffers gain a
        leading batch dim, so rows can sit at different decode depths."""
        spec = {
            "stack": stack_lib.stack_cache_spec(self.cfg, batch, max_len,
                                                dtype, per_slot=per_slot),
            "length": jax.ShapeDtypeStruct((batch,) if per_slot else (),
                                           jnp.int32),
        }
        return spec

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   per_slot: bool = False):
        return {
            "stack": stack_lib.init_stack_cache(self.cfg, batch, max_len,
                                                dtype, per_slot=per_slot),
            "length": jnp.zeros((batch,) if per_slot else (), jnp.int32),
        }

    def prefill(self, params, batch: Dict[str, jnp.ndarray], cache,
                plan, *, true_length=None) -> Tuple[jnp.ndarray, Any]:
        """Process the prompt; returns (last-position logits, filled cache).

        ``true_length`` (traced scalar) supports bucket-padded prompts: the
        returned logits come from position ``true_length - 1`` instead of
        the last padded column, and the cache length advances by
        ``true_length``.  The padded tail still writes K/V, but at positions
        ``>= true_length`` — causally masked for every later query until a
        real decode step overwrites them, so full-attention logits are
        unchanged.  (Not valid for SSM recurrences or ring-buffer windows —
        the decode engine falls back to exact-length prefill there.)
        """
        cfg = self.cfg
        plan = self._plan(plan)
        params = self.cast_params(params)
        tokens = batch["tokens"]
        sq = tokens.shape[1]
        # absolute positions continue from whatever is already cached
        # (segmented/streaming prefill passes partially-filled caches)
        length = cache["length"].astype(jnp.int32)
        arange = jnp.arange(sq, dtype=jnp.int32)
        positions = (length[:, None] + arange[None] if length.ndim
                     else length + arange)
        x = self._embed(params, tokens, positions=positions)
        cross = self._cross_states(params, batch, plan)
        x, new_stack, _ = stack_lib.run_stack(
            params["stack"], cfg, plan, x, positions=positions,
            cross_states=cross, cache=cache["stack"],
            cache_len=cache["length"], decode=False)
        if true_length is None:
            x_last = x[:, -1:]
            advance = sq
        else:
            tl = jnp.asarray(true_length, jnp.int32)
            x_last = jax.lax.dynamic_slice_in_dim(x, tl - 1, 1, axis=1)
            advance = tl
        logits = self._head(params, x_last, plan)
        return logits, {"stack": new_stack,
                        "length": cache["length"] + advance}

    def decode_step(self, params, token: jnp.ndarray, cache,
                    plan) -> Tuple[jnp.ndarray, Any]:
        """One decode step.  token: (B, 1) int32 -> logits (B, 1, V).

        A per-slot cache (vector ``length``) decodes all rows batched, each
        at its own position — the batched-engine hot path."""
        cfg = self.cfg
        plan = self._plan(plan)
        params = self.cast_params(params)
        pos = cache["length"]
        positions = (pos[:, None] if pos.ndim else pos[None]
                     ).astype(jnp.int32)
        x = self._embed(params, token, positions=positions)
        x, new_stack, _ = stack_lib.run_stack(
            params["stack"], cfg, plan, x, positions=positions,
            cross_states=None, cache=cache["stack"], cache_len=pos,
            decode=True)
        logits = self._head(params, x, plan)
        return logits, {"stack": new_stack, "length": pos + 1}


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
