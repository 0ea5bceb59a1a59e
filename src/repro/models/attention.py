"""Attention: GQA/MQA, causal/sliding-window/cross, chunked flash, KV caches.

Per the paper (§3.1 + App. B): the QKV and output projections are
"attention-protected" linears (FP8 under the paper recipe; the
``MatmulRecipe`` argument is the layer's attn cell of the active
``PrecisionPlan``), while the attention math itself (softmax(QK^T)V) always
runs in the compute dtype via FlashAttention: on a TPU, self-attention with
no cache and no window runs the splash flash kernels forward and backward
(``kernels.ops.flash_attention``); everything else — the CPU, decode and
prefill with a cache, sliding windows, cross-attention — runs a chunked
online-softmax over KV blocks (O(S * chunk) memory).  Each call's route is
recorded in the routing census as role ``sdpa``.

Cache variants:
  * full ring-less cache  (decode with full attention)
  * ring buffer           (sliding-window attention; the sub-quadratic
                           mechanism for the long_500k cells)
  * cross cache           (K/V precomputed once from encoder/vision states)
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import routing
from repro.core.recipe import MatmulRecipe
from repro.kernels import ops as _kops
from repro.nn.layers import get_sharding_context, linear, rope, shard_hint
from repro.nn.params import ParamSpec
from repro.telemetry.profiler import graph_span

__all__ = ["attn_param_specs", "cross_attn_param_specs", "attention",
           "cross_attention", "attn_cache_spec", "init_attn_cache",
           "chunked_attention", "NEG_INF"]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def attn_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    return {
        "wq": ParamSpec((d, nq * hd), ("embed", "heads")),
        "wk": ParamSpec((d, nkv * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((d, nkv * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((nq * hd, d), ("heads", "embed"),
                        scale=1.0 / np.sqrt(nq * hd * max(cfg.n_layers, 1))),
    }


def cross_attn_param_specs(cfg: ModelConfig,
                           kv_dim: Optional[int] = None) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    kv_dim = kv_dim or d
    return {
        "wq": ParamSpec((d, nq * hd), ("embed", "heads")),
        "wk": ParamSpec((kv_dim, nkv * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((kv_dim, nkv * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((nq * hd, d), ("heads", "embed"),
                        scale=1.0 / np.sqrt(nq * hd * max(cfg.n_layers, 1))),
    }


# ---------------------------------------------------------------------------
# Chunked flash attention (pure-jnp FlashAttention equivalent)
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, causal: bool, window: int):
    """(..., Sq, Sk) additive mask from absolute positions.

    ``k_pos`` entries < 0 denote unwritten cache slots (always masked).
    """
    valid = (k_pos >= 0)[..., None, :]
    if causal:
        valid &= k_pos[..., None, :] <= q_pos[..., :, None]
    if window:
        valid &= k_pos[..., None, :] > q_pos[..., :, None] - window
    return jnp.where(valid, 0.0, NEG_INF)


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, KVH, D) -> (B, S, KVH*n_rep, D)."""
    if n_rep == 1:
        return x
    b, s, kvh, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kvh, n_rep, d))
    return x.reshape(b, s, kvh * n_rep, d)


@graph_span("attn")
def chunked_attention(
    q: jnp.ndarray,           # (B, Sq, H, D)
    k: jnp.ndarray,           # (B, Sk, KVH, D)
    v: jnp.ndarray,           # (B, Sk, KVH, D)
    q_pos: jnp.ndarray,       # (Sq,) or (B, Sq) absolute positions
    k_pos: jnp.ndarray,       # (Sk,) or (B, Sk) absolute (-1 = invalid)
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 1024,
    unroll: bool = False,
) -> jnp.ndarray:
    """Online-softmax attention over KV chunks; O(Sq * chunk) live scores.

    2-D positions (per-slot decode: each batch row at its own offset)
    broadcast into the mask as (B, 1, S) against the (B, H, Sq, Sk) scores.
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    if q_pos.ndim == 2:
        q_pos = q_pos[:, None]            # (B, 1, Sq)
    if k_pos.ndim == 2:
        k_pos = k_pos[:, None]            # (B, 1, Sk)
    n_rep = h // kvh
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = 1.0 / np.sqrt(d)

    chunk = min(chunk, sk)
    n_chunks = sk // chunk
    rem = sk - n_chunks * chunk

    # Operands stay in the compute dtype; dots accumulate in f32 via
    # preferred_element_type (flash-style — avoids live f32 K/V copies).
    qf = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)  # (B,H,Sq,D)
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    qf = shard_hint(qf, ("batch", "heads", "seq_q", None))

    def one_chunk(carry, kc, vc, kpos_c):
        m, l, acc = carry
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kc,
                       preferred_element_type=jnp.float32)
        s = s + _mask_bias(q_pos, kpos_c, causal, window)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # Guard fully-masked rows: exp(-inf - (-inf)) must be 0, not 1.
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        corr = jnp.exp(m - safe_m) * (m > NEG_INF / 2)
        p = jnp.exp(s - safe_m[..., None])
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v.dtype), vc,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # Carry inits must match qf's sharding: lax.scan unifies the carry
    # sharding across iterations, so replicated inits would force GSPMD to
    # re-gather the q-sequence dim inside every chunk step (defeats
    # context-parallel attention).
    m0 = shard_hint(jnp.full((b, h, sq), NEG_INF, jnp.float32),
                    ("batch", "heads", "seq_q"))
    l0 = shard_hint(jnp.zeros((b, h, sq), jnp.float32),
                    ("batch", "heads", "seq_q"))
    a0 = shard_hint(jnp.zeros((b, h, sq, d), jnp.float32),
                    ("batch", "heads", "seq_q", None))
    carry = (m0, l0, a0)

    if n_chunks > 0:
        if unroll:
            for i in range(n_chunks):
                sl = slice(i * chunk, (i + 1) * chunk)
                carry = one_chunk(carry, kf[:, :, sl], vf[:, :, sl],
                                  k_pos[..., sl])
        else:
            kc = kf[:, :, :n_chunks * chunk].reshape(
                b, h, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)
            vc = vf[:, :, :n_chunks * chunk].reshape(
                b, h, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)
            pc = k_pos[..., :n_chunks * chunk]
            pc = jnp.moveaxis(
                pc.reshape(pc.shape[:-1] + (n_chunks, chunk)), -2, 0)

            def body(c, xs):
                return one_chunk(c, *xs), None

            carry, _ = jax.lax.scan(body, carry, (kc, vc, pc))
    if rem:
        carry = one_chunk(carry, kf[:, :, n_chunks * chunk:],
                          vf[:, :, n_chunks * chunk:],
                          k_pos[..., n_chunks * chunk:])

    m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,Sq,H,D)


# ---------------------------------------------------------------------------
# Full attention sublayer (projections + SDPA [+ cache update])
# ---------------------------------------------------------------------------

def _flash_route(q, k, rules, *, cache, window: int,
                default_positions: bool):
    """``(reasons, specs)``: why self-attention cannot take the flash
    kernels here (empty: it can), and their ``shard_map`` specs under a
    mesh.  The model's conditions are no cache, no window and positions
    that are the plain ``arange``; the kernel's are
    ``kernels.ops.flash_route``'s."""
    reasons = tuple(r for r, hit in (("cache", cache is not None),
                                     ("window", bool(window)),
                                     ("positions", not default_positions))
                    if hit)
    kernel_reasons, specs = _kops.flash_route(q.shape, k.shape, rules)
    reasons += kernel_reasons
    return reasons, (None if reasons else specs)


def _record_sdpa(q, k, reasons: Tuple[str, ...], sharded: bool) -> None:
    """One routing-census event for an attention core: role ``sdpa``,
    route ``flash`` or ``chunked`` with the ``reasons`` that kept it
    chunked."""
    routing.record(
        "sdpa", "chunked" if reasons else "flash", jnp.dtype(q.dtype).name,
        jnp.dtype(k.dtype).name, reasons=reasons, sharded=sharded)


@graph_span("attn")
def attention(
    params: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    x: jnp.ndarray,                      # (B, Sq, D)
    recipe: MatmulRecipe,
    *,
    positions: Optional[jnp.ndarray] = None,   # (Sq,) or (B, Sq) absolute
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    cache_len: Optional[jnp.ndarray] = None,   # int32 scalar or (B,) cached
    causal: bool = True,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Self-attention sublayer.  Returns (out, updated_cache)."""
    b, sq, _ = x.shape
    hd = cfg.resolved_head_dim
    default_positions = positions is None
    if default_positions:
        positions = jnp.arange(sq, dtype=jnp.int32)

    q = linear(x, params["wq"], recipe, cfg,
               axes=("tokens", "embed", "heads")
               ).reshape(b, sq, cfg.n_heads, hd)
    k = linear(x, params["wk"], recipe, cfg,
               axes=("tokens", "embed", "kv_heads")).reshape(
        b, sq, cfg.n_kv_heads, hd)
    v = linear(x, params["wv"], recipe, cfg,
               axes=("tokens", "embed", "kv_heads")).reshape(
        b, sq, cfg.n_kv_heads, hd)
    if cfg.pos_emb == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    # 'seq_q' is None by default; mapping it to the TP axis enables
    # context-parallel attention (q-sequence sharding) — the fallback when
    # head counts don't divide TP (e.g. llama3.2-3b 24H on model=16).
    q = shard_hint(q, ("batch", "seq_q", "heads", None))
    k = shard_hint(k, ("batch", "seq", "kv_heads", None))
    v = shard_hint(v, ("batch", "seq", "kv_heads", None))

    window = cfg.sliding_window
    reasons, specs = _flash_route(q, k, get_sharding_context(),
                                  cache=cache, window=window,
                                  default_positions=default_positions)
    _record_sdpa(q, k, reasons, specs is not None)
    new_cache = None
    if not reasons:
        out = _kops.flash_attention(q, k, v, causal=causal, specs=specs)
    elif cache is None:
        out = chunked_attention(
            q, k, v, positions, positions, causal=causal, window=window,
            chunk=cfg.attention_chunk, unroll=cfg.unroll_attention)
    else:
        new_cache, k_all, v_all, k_pos = _update_cache(
            cache, k, v, cache_len, window, cfg.kv_cache_format)
        out = chunked_attention(
            q, k_all, v_all, positions, k_pos, causal=causal, window=window,
            chunk=cfg.attention_chunk, unroll=cfg.unroll_attention)
    out = out.reshape(b, sq, cfg.n_heads * hd)
    return linear(out, params["wo"], recipe, cfg,
                  axes=("tokens", "heads", "embed")), new_cache


@graph_span("attn")
def cross_attention(
    params: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    x: jnp.ndarray,                      # (B, Sq, D)
    recipe: MatmulRecipe,
    *,
    kv_states: Optional[jnp.ndarray] = None,   # (B, Skv, Dkv)
    cache: Optional[Dict[str, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Cross-attention over encoder/vision states (non-causal).

    Either ``kv_states`` (training/prefill; K/V computed here and returned as
    a cache) or ``cache`` (decode; K/V reused) must be provided.
    """
    b, sq, _ = x.shape
    hd = cfg.resolved_head_dim
    q = linear(x, params["wq"], recipe, cfg,
               axes=("tokens", "embed", "heads")
               ).reshape(b, sq, cfg.n_heads, hd)
    if cache is None:
        skv = kv_states.shape[1]
        k = linear(kv_states, params["wk"], recipe, cfg,
                   axes=("tokens", None, "kv_heads")).reshape(
            b, skv, cfg.n_kv_heads, hd)
        v = linear(kv_states, params["wv"], recipe, cfg,
                   axes=("tokens", None, "kv_heads")).reshape(
            b, skv, cfg.n_kv_heads, hd)
        new_cache = {"k": k, "v": v}
    else:
        k, v = cache["k"], cache["v"]
        new_cache = cache
    skv = k.shape[1]
    kpos = jnp.arange(skv, dtype=jnp.int32)
    qpos = jnp.zeros((sq,), jnp.int32)
    _record_sdpa(q, k, ("cross",), False)
    out = chunked_attention(q, k, v, qpos, kpos, causal=False, window=0,
                            chunk=cfg.attention_chunk,
                            unroll=cfg.unroll_attention)
    out = out.reshape(b, sq, cfg.n_heads * hd)
    return linear(out, params["wo"], recipe, cfg,
                  axes=("tokens", "heads", "embed")), new_cache


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------

def attn_cache_spec(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16, per_slot: bool = False
                    ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Cache spec for ONE attention layer.

    Sliding-window configs get a ring buffer bounded by the window size —
    this is what makes long_500k decode sub-quadratic (and sub-linear in
    memory) for SWA archs.

    ``per_slot`` gives every batch row its own position track
    (pos (batch, size) instead of (size,)) so a continuous-batching engine
    can hold slots at different sequence offsets in one cache.

    ``cfg.kv_cache_format`` (serving-side, 8-bit) swaps the K/V leaves for
    uint8 codes plus per-(token, kv-head) f32 scales — quantize on append,
    dequantize on read (see ``core.packed.kv_quantize``).
    """
    size = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    hd = cfg.resolved_head_dim
    kvh = cfg.n_kv_heads
    pos_shape = (batch, size) if per_slot else (size,)
    spec: Dict[str, jax.ShapeDtypeStruct] = {
        "pos": jax.ShapeDtypeStruct(pos_shape, jnp.int32),
    }
    if cfg.kv_cache_format:
        spec["k"] = jax.ShapeDtypeStruct((batch, size, kvh, hd), jnp.uint8)
        spec["v"] = jax.ShapeDtypeStruct((batch, size, kvh, hd), jnp.uint8)
        spec["k_scale"] = jax.ShapeDtypeStruct((batch, size, kvh),
                                               jnp.float32)
        spec["v_scale"] = jax.ShapeDtypeStruct((batch, size, kvh),
                                               jnp.float32)
    else:
        spec["k"] = jax.ShapeDtypeStruct((batch, size, kvh, hd), dtype)
        spec["v"] = jax.ShapeDtypeStruct((batch, size, kvh, hd), dtype)
    return spec


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16,
                    per_slot: bool = False) -> Dict[str, jnp.ndarray]:
    spec = attn_cache_spec(cfg, batch, max_len, dtype, per_slot)
    cache = {k: jnp.zeros(v.shape, v.dtype) for k, v in spec.items()}
    cache["pos"] = jnp.full(spec["pos"].shape, -1, jnp.int32)
    return cache


def _update_cache(cache, k, v, cache_len, window, kv_format=None):
    """Write new K/V at [cache_len, cache_len+sq) (mod ring size).

    ``cache_len`` is either a scalar (whole batch at one offset) or a
    ``(B,)`` vector (per-slot decode: every batch row advances from its own
    length).  Quantized caches store uint8 codes + f32 scales; the read
    side dequantizes the whole cache back into the compute dtype, so the
    attention math itself is unchanged.
    """
    sq = k.shape[1]
    size = cache["k"].shape[1]
    start = cache_len.astype(jnp.int32)
    if start.ndim:                      # per-slot (B,) lengths
        new_pos = start[:, None] + jnp.arange(sq, dtype=jnp.int32)[None]
        # Ring indexing for windowed caches; identity otherwise.
        idx = new_pos % size if window else new_pos
        bidx = jnp.arange(k.shape[0], dtype=jnp.int32)[:, None]

        def put(dst, src):
            return dst.at[bidx, idx].set(src.astype(dst.dtype))
    else:
        new_pos = start + jnp.arange(sq, dtype=jnp.int32)
        idx = new_pos % size if window else new_pos

        def put(dst, src):
            return dst.at[:, idx].set(src.astype(dst.dtype))

    if kv_format is not None and "k_scale" in cache:
        from repro.core.packed import kv_dequantize, kv_quantize
        kc, ks = kv_quantize(k, kv_format)
        vc, vs = kv_quantize(v, kv_format)
        new_cache = {"k": put(cache["k"], kc), "v": put(cache["v"], vc),
                     "k_scale": put(cache["k_scale"], ks),
                     "v_scale": put(cache["v_scale"], vs)}
        k_all = kv_dequantize(new_cache["k"], new_cache["k_scale"],
                              kv_format, k.dtype)
        v_all = kv_dequantize(new_cache["v"], new_cache["v_scale"],
                              kv_format, v.dtype)
    else:
        new_cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
        k_all, v_all = new_cache["k"], new_cache["v"]
    pos_new = (put(cache["pos"], new_pos) if cache["pos"].ndim == 2
               else cache["pos"].at[idx].set(new_pos))
    new_cache["pos"] = pos_new
    return new_cache, k_all, v_all, pos_new
