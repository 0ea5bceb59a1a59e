"""jit'd public wrappers for the Pallas kernels: padding, the mesh split
of the quantize pass, the splash flash-attention call, and interpret-mode
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
pipeline unchanged.  ``flash_attention`` likewise runs under
``shard_map`` over such a mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as _splash
from jax.sharding import PartitionSpec as P

from repro.core import routing
from repro.core.quantize import QuantSpec
from repro.kernels import fp4_matmul as _mm
from repro.kernels import quantize as _q

__all__ = ["fp4_matmul", "pallas_qmm", "quantize_blockwise",
           "flash_attention", "flash_route"]


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


def _flash_block(seq: int) -> int:
    """The splash kernels' tile along both sequence axes, for the forward,
    ``dq`` and ``dkv`` kernels alike: the largest of 512, 256 and 128 that
    divides the sequence."""
    return next(b for b in (512, 256, 128) if seq % b == 0)


def _splash_call(q, k, v, *, causal: bool, interpret: bool):
    """Pre-scaled (B, S, H, D) queries against (B, S, KVH, D) keys and
    values on one device: the splash kernels, vmapped over batch.  One
    KV head takes the MQA kernel, more the MHA kernel's own head grouping,
    so K and V are never repeated per query head."""
    s, h = q.shape[1], q.shape[2]
    kvh = k.shape[2]
    blk = _flash_block(s)
    mask = (_splash.CausalMask if causal else _splash.FullMask)((s, s))
    sizes = _splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk)
    make = (_splash.make_splash_mqa_single_device if kvh == 1
            else _splash.make_splash_mha_single_device)
    kernel = make(_splash.MultiHeadMask([mask] * h), block_sizes=sizes,
                  interpret=interpret)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    if kvh == 1:
        kt, vt = kt[:, 0], vt[:, 0]
    return jax.vmap(kernel)(qt, kt, vt).transpose(0, 2, 1, 3)


# Ambient default matmul precisions under which the splash kernels' bf16
# dots compile: Mosaic refuses HIGH and float32 contract precisions.
_FLASH_PRECISIONS = (None, "default", "bfloat16")


def flash_route(q_shape, k_shape, rules=None):
    """Whether the splash kernels can run self-attention here, and how
    they split over the mesh: ``(reasons, specs)``.

    ``reasons`` names each condition that keeps the call off the kernels
    (empty: they run): ``not_tpu``; ``precision``, an ambient default
    matmul precision above bfloat16; ``seq_len``, q and k lengths that
    differ or are no multiple of 128.  Under ``rules`` whose mesh has more
    than one device, also ``seq_sharded``, a sequence axis split over the
    mesh; ``kv_heads_split``, KV heads split unlike the query heads; and
    ``mesh_axis_unused``, a mesh axis that neither q's batch nor its heads
    take, along which ``shard_map`` would repeat the whole call (the
    vmapped per-data-shard body of the fp8-compressed data-parallel step,
    whose rules leave the batch unmapped).  ``specs``: the ``shard_map``
    specs of q and of k/v there, batch over its mesh axes and heads over
    theirs (``models.attention`` hints the same logical axes); ``None`` on
    one device or when a reason holds."""
    reasons = []
    if _mm.interpret_mode():
        reasons.append("not_tpu")
    if jax.config.jax_default_matmul_precision not in _FLASH_PRECISIONS:
        reasons.append("precision")
    if q_shape[1] != k_shape[1] or q_shape[1] % 128:
        reasons.append("seq_len")
    if rules is None or rules.mesh.size == 1:
        return tuple(reasons), None

    def spec(axes, shape):
        return P(*(tuple(rules.activation_sharding(axes, shape).spec)
                   + (None,) * 4)[:4])
    q_spec = spec(("batch", "seq_q", "heads", None), q_shape)
    kv_spec = spec(("batch", "seq", "kv_heads", None), k_shape)
    if q_spec[1] is not None or kv_spec[1] is not None:
        reasons.append("seq_sharded")
    if k_shape[2] > 1 and kv_spec[2] != q_spec[2]:
        reasons.append("kv_heads_split")
    used = {a for entry in (q_spec[0], q_spec[2]) if entry is not None
            for a in ((entry,) if isinstance(entry, str) else entry)}
    if any(n > 1 and a not in used for a, n in rules.mesh.shape.items()):
        reasons.append("mesh_axis_unused")
    return tuple(reasons), (None if reasons else (rules.mesh, q_spec,
                                                 kv_spec))


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, specs=None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """softmax(q kᵀ / √D) v for self-attention, forward and backward on
    the splash flash kernels of JAX's Pallas TPU ops: scores and
    probabilities stay in VMEM, and tiles a causal mask hides are skipped.

    q: (B, S, H, D); k, v: (B, S, KVH, D), KVH dividing H; S a multiple of
    128.  q is scaled by 1/√D in its own dtype first, as
    ``models.attention.chunked_attention`` does.  The kernels' dots read
    q, k, v, dO and the backward's P and dS in the operands' dtype and
    accumulate in float32, as do the softmax statistics; the forward's P·V
    multiplies P by V upcast to float32 (the chunked path casts P to V's
    dtype).  ``specs`` (``(mesh, q_spec, kv_spec)`` from ``flash_route``)
    runs the call inside ``shard_map`` over that mesh: GSPMD cannot
    partition a Mosaic call.
    """
    interpret = _mm.interpret_mode() if interpret is None else interpret
    q = q * jnp.asarray(1.0 / math.sqrt(q.shape[-1]), q.dtype)
    call = functools.partial(_splash_call, causal=causal,
                             interpret=interpret)
    if specs is None:
        return call(q, k, v)
    mesh, q_spec, kv_spec = specs
    return jax.shard_map(
        call, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec, check_vma=False)(q, k, v)
