"""jit'd public wrappers for the Pallas kernels: padding, GQA handling,
custom_vjp glue, and interpret-mode fallback for CPU.

Off a TPU every entry point runs with ``interpret=True`` (decided by
``fp4_matmul.interpret_mode``) — the kernel body executes in Python,
validating the exact TPU code path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import routing
from repro.core.quantize import QuantSpec
from repro.kernels import fp4_matmul as _mm
from repro.kernels import quantize as _q
from repro.kernels import flash_attention as _fa
from repro.models.attention import chunked_attention

__all__ = ["fp4_matmul", "pallas_qmm", "quantize_blockwise",
           "flash_attention"]


def _pad2d(x, block):
    m, n = x.shape
    pm, pn = (-m) % block, (-n) % block
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x, m, n


def fp4_matmul(x: jnp.ndarray, w: jnp.ndarray, *,
               x_fmt: str = "fp4_e2m1", w_fmt: str = "fp4_e2m1",
               block: int = 128,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused block-quantized matmul; pads to tile multiples.

    NOTE on padding semantics: zero-padding K changes nothing (zeros add
    nothing and per-row amax over the padded segment is unchanged for the
    rows that exist); padding M/N rows/cols are sliced away.
    """
    interpret = _mm.interpret_mode() if interpret is None else interpret
    xp, m, k = _pad2d(x, block)
    wp, _, n = _pad2d(w, block)
    y = _mm.fp4_matmul(xp, wp, x_fmt=x_fmt, w_fmt=w_fmt, block=block,
                       interpret=interpret)
    return y[:m, :n]


def pallas_qmm(a: jnp.ndarray, b: jnp.ndarray,
               spec_a: QuantSpec, spec_b: QuantSpec, *,
               mode_a: str, mode_b: str,
               trans_a: bool = False, trans_b: bool = False,
               block: int = 128,
               key_data: Optional[jnp.ndarray] = None, salt: int = 0,
               pipeline: Optional[str] = None,
               bm: Optional[int] = None, bn: Optional[int] = None,
               bk: Optional[int] = None,
               collect_stats: bool = False,
               interpret: Optional[bool] = None,
               role: Optional[str] = None, cell=None):
    """Per-role quantized matmul ``Q(A') @ Q(B')`` through the fused
    pipeline (a quantize pass per operand, then XLA's dot, by default —
    see ``kernels.fp4_matmul``), with padding.

    ``a``/``b`` are stored arrays; ``A' = a^T`` under ``trans_a`` (same for
    B') — the kernels read the stored layout via their index maps and
    quantize in effective orientation.  Quantization (``mode_*`` from
    ``core.qlinear.kernel_quant_mode``) is relative to the *effective*
    orientation, i.e. each backward matmul's own reduction axis; ``token``/
    ``tensor`` amax needs its whole-axis sweep, so such roles take the
    two-pass pipeline even where ``pipeline="stream"`` is asked for.
    Stochastic specs draw in-kernel noise seeded from ``key_data`` +
    ``salt``.  ``pipeline``/``bm``/``bn``/``bk`` pass straight through to
    ``fused_qmm`` (None = the default pipeline; the tiles steer only the
    streaming kernel).
    Padding semantics: zero K-padding adds nothing to the dot and leaves
    real rows' amax groups unchanged; padded M/N rows/cols quantize on the
    eps-floor scale path and are sliced away.  With ``collect_stats``
    returns ``(y, (stats_a, stats_b))`` raw telemetry-epilogue vectors
    (``kernels.fp4_matmul.finalize_quant_stats`` reduces them).
    """
    interpret = _mm.interpret_mode() if interpret is None else interpret
    ap, _, _ = _pad2d(a, block)
    bp, _, _ = _pad2d(b, block)
    m = a.shape[1] if trans_a else a.shape[0]
    k = a.shape[0] if trans_a else a.shape[1]
    n = b.shape[0] if trans_b else b.shape[1]
    a_sr = bool(spec_a.stochastic) and mode_a != "pass"
    b_sr = bool(spec_b.stochastic) and mode_b != "pass"
    if routing.active() is not None:
        routing.record(
            role or "?", "pallas", spec_a.to_str(), spec_b.to_str(),
            mode_a=mode_a, mode_b=mode_b,
            pipeline=_mm.resolve_pipeline(pipeline, mode_a, mode_b),
            sr_a=a_sr and key_data is not None,
            sr_b=b_sr and key_data is not None, cell=cell)
    seed_a = seed_b = None
    if a_sr or b_sr:
        assert key_data is not None, "stochastic spec needs key_data"
        from repro.kernels.rounding import fold_seed
        seed_a = fold_seed(key_data, salt, 0) if a_sr else None
        seed_b = fold_seed(key_data, salt, 1) if b_sr else None
    out = _mm.fused_qmm(
        ap, bp, a_mode=mode_a, b_mode=mode_b,
        a_fmt=spec_a.fmt, b_fmt=spec_b.fmt,
        a_pow2=spec_a.pow2_scale, b_pow2=spec_b.pow2_scale,
        a_sr=a_sr, b_sr=b_sr, seed_a=seed_a, seed_b=seed_b,
        trans_a=trans_a, trans_b=trans_b, block=block,
        bm=bm, bn=bn, bk=bk, pipeline=pipeline,
        real_dims=(m, k, n), collect_stats=collect_stats,
        interpret=interpret)
    if collect_stats:
        y, stats = out
        return y[:m, :n], stats
    return out[:m, :n]


def quantize_blockwise(x: jnp.ndarray, fmt_name: str = "fp4_e2m1",
                       block: int = 128, *, per_row: bool = False,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    interpret = _mm.interpret_mode() if interpret is None else interpret
    xp, m, n = _pad2d(x, block)
    y = _q.quantize_blockwise(xp, fmt_name, block, per_row=per_row,
                              interpret=interpret)
    return y[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, chunk, interpret):
    """(B, S, H, D) attention; Pallas fwd, chunked-jnp bwd."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    bq = min(128, sq)
    bk = min(128, kf.shape[1])
    o = _fa.flash_attention_fwd(qf, kf, vf, causal=causal, bq=bq, bk=bk,
                                interpret=interpret)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, causal, chunk, interpret):
    return _flash(q, k, v, causal, chunk, interpret), (q, k, v)


def _flash_bwd(causal, chunk, interpret, res, g):
    q, k, v = res

    def ref_fn(q, k, v):
        sq = q.shape[1]
        pos = jnp.arange(sq, dtype=jnp.int32)
        kpos = jnp.arange(k.shape[1], dtype=jnp.int32)
        return chunked_attention(q, k, v, pos, kpos, causal=causal,
                                 chunk=chunk)

    _, vjp = jax.vjp(ref_fn, q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, chunk: int = 1024,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Differentiable flash attention: Pallas forward (TPU target),
    chunked-jnp backward.  q/k/v: (B, S, H|KVH, D)."""
    interpret = _mm.interpret_mode() if interpret is None else interpret
    return _flash(q, k, v, causal, chunk, interpret)
