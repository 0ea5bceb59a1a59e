"""Pallas TPU kernel: causal flash attention forward (online softmax).

The paper keeps the attention math in high precision via FlashAttention
(App. B); this is its TPU-native form.  Tiling:

  grid = (batch*heads, Sq/bq, Sk/bk) with the KV dim innermost; the
  (m, l, acc) running statistics live in VMEM scratch and are revisited
  across KV steps, so each Q tile makes exactly one HBM pass over K/V.
  Causal masking is positional; fully-masked KV tiles are skipped at trace
  time via the grid (bk tiles beyond the causal frontier are not visited
  thanks to the index_map clamping).

Backward runs through the pure-jnp chunked implementation (custom_vjp in
ops.py) — identical math, so gradients are exact w.r.t. this forward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale, bq, bk, causal):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    corr = jnp.exp(m_prev - safe_m) * (m_prev > NEG_INF / 2)
    p = jnp.exp(s - safe_m)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, bq: int = 128, bk: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """q/k/v: (BH, S, D) flattened batch*heads (GQA repeat done by ops.py).
    S must be a multiple of bq/bk; D MXU-aligned (128 ideally)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % bq == 0 and sk % bk == 0
    scale = 1.0 / np.sqrt(d)
    kernel = functools.partial(_fa_kernel, scale=scale, bq=bq, bk=bk,
                               causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
