"""jit'd public wrappers for the Pallas kernels: padding, GQA handling,
custom_vjp glue, the mesh split of the quantize pass, and interpret-mode
fallback for CPU.

Off a TPU every entry point runs with ``interpret=True`` (decided by
``fp4_matmul.interpret_mode``) — the kernel body executes in Python,
validating the exact TPU code path.

Under a mesh of more than one device (``rules``, the sharding rules of
the mesh-native train step, passed down by ``core.qlinear``) GSPMD cannot
partition a Mosaic kernel, so ``pallas_qmm`` runs each operand's quantize
pass under ``shard_map`` over the whole mesh (``shard_plan`` says how each
operand splits) and leaves the product to XLA's dot, which GSPMD
partitions.  Without a mesh, or on one device, the call is the one-device
pipeline unchanged.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

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
               role: Optional[str] = None, cell=None,
               axes_a=None, axes_b=None, rules=None):
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

    ``axes_a``/``axes_b`` name the effective operands' dims logically
    (``core.qlinear``'s ``(tokens, K, N)`` in each role's orientation).
    Under ``rules`` (``distributed.sharding.ShardingRules``) whose mesh has
    more than one device they say how each quantize pass splits over it
    (``_sharded_qmm``); a ``pass:pass`` role quantizes nothing and takes
    the one-device call, whose dot GSPMD partitions.  The values
    are the one-device pass's bit for bit, and ``y`` differs only in the
    partitioned dot's f32 accumulation order.
    """
    interpret = _mm.interpret_mode() if interpret is None else interpret
    ap, _, _ = _pad2d(a, block)
    bp, _, _ = _pad2d(b, block)
    m = a.shape[1] if trans_a else a.shape[0]
    k = a.shape[0] if trans_a else a.shape[1]
    n = b.shape[0] if trans_b else b.shape[1]
    a_sr = bool(spec_a.stochastic) and mode_a != "pass"
    b_sr = bool(spec_b.stochastic) and mode_b != "pass"
    seed_a = seed_b = None
    if a_sr or b_sr:
        assert key_data is not None, "stochastic spec needs key_data"
        from repro.kernels.rounding import fold_seed
        seed_a = fold_seed(key_data, salt, 0) if a_sr else None
        seed_b = fold_seed(key_data, salt, 1) if b_sr else None
    pipeline = _mm.resolve_pipeline(pipeline, mode_a, mode_b)
    if (rules is not None and rules.mesh.size > 1
            and (mode_a != "pass" or mode_b != "pass")):
        return _sharded_qmm(
            rules, ap, bp, spec_a, spec_b, mode_a=mode_a, mode_b=mode_b,
            trans_a=trans_a, trans_b=trans_b, block=block, seeds=(seed_a,
                                                                  seed_b),
            real_dims=(m, k, n), axes=(axes_a, axes_b),
            collect_stats=collect_stats, interpret=interpret, role=role,
            cell=cell)
    if routing.active() is not None:
        routing.record(
            role or "?", "pallas", spec_a.to_str(), spec_b.to_str(),
            mode_a=mode_a, mode_b=mode_b, pipeline=pipeline,
            sr_a=a_sr and key_data is not None,
            sr_b=b_sr and key_data is not None, cell=cell)
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


def _spec_entry(axes: Tuple[str, ...]):
    return None if not axes else (axes[0] if len(axes) == 1 else axes)


def _reduction_dim(lhs: bool, trans: bool) -> int:
    """The stored dim the quant groups reduce over (the product's K): the
    LHS is stored (M, K), or (K, M) transposed; the RHS (K, N), or
    (N, K)."""
    return 1 - int(trans) if lhs else int(trans)


def shard_plan(rules, shape: Tuple[int, int], eff_axes, *, lhs: bool,
               trans: bool, mode: str, gather: bool, block: int = 128):
    """How one stored operand's quantize pass is split over the mesh:
    ``(in_spec, out_spec, amax_axes)``.

    Each stored dim takes the mesh axes its logical name has under the
    rules' activation mapping (``eff_axes`` names the effective
    orientation's dims), so token rows split like the batch and a weight,
    whose ``embed`` dim maps to no axis there, is gathered whole before
    its pass (the FSDP gather the product needs anyway).  A dim whose
    shard would not be a whole number of 128-groups is gathered too:
    ``block``/``tile`` groups then never straddle two chips.  ``gather``
    (stochastic rounding, telemetry stats) gathers the whole operand, so
    its noise and stats are the one-device pass's.  ``amax_axes``: the
    mesh axes a ``token``/``tensor`` amax group spans, over which the
    pass takes a ``pmax`` first.  ``out_spec`` is the product's read
    layout: the LHS as (M, K), the RHS as (K, N).
    """
    names = tuple(eff_axes) if eff_axes is not None else (None, None)
    stored = names[::-1] if trans else names
    spec = tuple(rules.activation_sharding(stored, shape).spec)
    parts = []
    for d, entry in enumerate((spec + (None, None))[:2]):
        axes = () if entry is None else (
            (entry,) if isinstance(entry, str) else tuple(entry))
        axes = tuple(x for x in axes if rules.mesh.shape[x] > 1)
        size = math.prod(rules.mesh.shape[x] for x in axes)
        if gather or (shape[d] // size) % block:
            axes = ()
        parts.append(axes)
    red = _reduction_dim(lhs, trans)
    amax_axes = {"token": parts[red],
                 "tensor": parts[0] + parts[1]}.get(mode, ())
    out = parts[::-1] if trans else parts
    return (P(*map(_spec_entry, parts)), P(*map(_spec_entry, out)),
            amax_axes)


def _sharded_pass(rules, t, spec: QuantSpec, *, lhs: bool, mode: str,
                  trans: bool, seed, axes, block: int, rows_real: int,
                  k_real: int, collect_stats: bool, interpret: bool):
    """One operand's quantize pass inside ``shard_map`` over the whole mesh
    (a Mosaic kernel cannot be partitioned by GSPMD): each chip quantizes
    its shard, a cross-chip amax group first takes its ``pmax``.  Returns
    ``(values, stats, needed a cross-chip amax)``."""
    gather = seed is not None or collect_stats
    in_spec, out_spec, amax_axes = shard_plan(
        rules, t.shape, axes, lhs=lhs, trans=trans, mode=mode,
        gather=gather, block=block)
    red = _reduction_dim(lhs, trans)

    def body(x, *seed_arg):
        amax = None
        if amax_axes:
            mag = jnp.abs(x)
            amax = (jnp.max(mag, axis=red) if mode == "token"
                    else jnp.max(mag).astype(jnp.float32).reshape(1))
            amax = jax.lax.pmax(amax, amax_axes)
        rows, kk = (x.shape[1 - red], x.shape[red])
        return jax.named_call(_mm.quantize_for_dot, name="_fused_qmm")(
            x, lhs=lhs, mode=mode, fmt_name=spec.fmt, pow2=spec.pow2_scale,
            sr=seed is not None, seed=seed_arg[0] if seed_arg else None,
            trans=trans, block=block,
            rows_real=rows_real if gather else rows,
            k_real=k_real if gather else kk, collect_stats=collect_stats,
            interpret=interpret, amax=amax)

    args = (t,) if seed is None else (t, seed)
    q, stats = jax.shard_map(
        body, mesh=rules.mesh, in_specs=(in_spec, P())[:len(args)],
        out_specs=(out_spec, P()), check_vma=False)(*args)
    return q, stats, bool(amax_axes)


def _sharded_qmm(rules, ap, bp, spec_a: QuantSpec, spec_b: QuantSpec, *,
                 mode_a: str, mode_b: str, trans_a: bool, trans_b: bool,
                 block: int, seeds, real_dims, axes, collect_stats: bool,
                 interpret: bool, role, cell):
    """``pallas_qmm`` over a mesh of more than one device: each quantized
    operand's pass under ``shard_map`` (scope ``shard_qmm``), then XLA's
    dot outside it, which GSPMD partitions like any other.  The two-pass
    pipeline always: the streaming kernel fuses the product."""
    m, k, n = real_dims
    qa, qb = ap, bp
    stats = [None, None]
    cross = False
    with jax.named_scope("shard_qmm"):
        if mode_a != "pass":
            qa, stats[0], c = _sharded_pass(
                rules, ap, spec_a, lhs=True, mode=mode_a, trans=trans_a,
                seed=seeds[0], axes=axes[0], block=block, rows_real=m,
                k_real=k, collect_stats=collect_stats, interpret=interpret)
            cross |= c
        if mode_b != "pass":
            qb, stats[1], c = _sharded_pass(
                rules, bp, spec_b, lhs=False, mode=mode_b, trans=trans_b,
                seed=seeds[1], axes=axes[1], block=block, rows_real=n,
                k_real=k, collect_stats=collect_stats, interpret=interpret)
            cross |= c
    if routing.active() is not None:
        routing.record(
            role or "?", "pallas", spec_a.to_str(), spec_b.to_str(),
            mode_a=mode_a, mode_b=mode_b, pipeline="two_pass",
            sr_a=seeds[0] is not None, sr_b=seeds[1] is not None,
            cell=cell, sharded=True,
            cross_amax=cross)
    y = _mm._xla_dot(qa, qb, trans_a=trans_a and mode_a == "pass",
                     trans_b=trans_b and mode_b == "pass")[:m, :n]
    return (y, tuple(stats)) if collect_stats else y


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
