"""Pallas TPU kernels: quantize-once K-panel pipeline for FP4/FP8 matmuls.

The paper's §3.2 hotspot — a linear whose operands are quantized per-group
and whose dot runs on the low-precision unit — used to be ONE fused kernel
that re-quantized every LHS K-tile ``N/bn`` times and every RHS K-tile
``M/bm`` times (once per output-tile visit), with RTN emulated through
``log2``/``ldexp`` transcendentals on the VPU.  Following the
quantize-once operand caching of "FP4 All the Way" (Chmiel et al., 2025)
and "Quartet" (Castro et al., 2025), the pipeline is now two phases:

**Phase 1 — quantize pass** (``quantize_panels`` / ``_quantize_operand``).
One grid sweep over each operand's K-panels QDQs every element exactly
once, in the *effective* (post-transpose) orientation, and writes the
on-grid values back in MXU-ready layout.  In this QDQ simulation the
emitted values are the dequantized grid points (bf16/f32 — what the MXU
consumes); on FP4-native hardware the same layout holds the 4-bit codes
plus per-group scales.  All four paper granularities run in-kernel:

  * ``block`` — per-(1 x 128) groups along the reduction axis;
  * ``tile``  — per-(128 x 128) tiles;
  * ``token`` / ``tensor`` — amax groups spanning the whole reduction axis,
    computed by a two-sweep grid (sweep 0 accumulates amax in scratch,
    sweep 1 quantizes) — this subsumes the old external ``_rank1_scale``
    XLA reduction, so "scaled" modes no longer exist.

Rounding is the **bit-exact integer RTN** of ``kernels.rounding`` (exponent
extracted from the f32 bit pattern, grid step assembled by writing the
exponent field — no transcendentals), verified bit-exact against
``formats.round_to_format``.  ``sr=True`` switches to in-kernel unbiased
stochastic rounding: on TPU via ``pltpu.prng_seed`` +
``pltpu.prng_random_bits``, in interpret mode (no CPU lowering for the TPU
PRNG) via the tiling-invariant counter hash ``rounding.hash_uniform`` —
noise is keyed by each element's *global* coordinate, so results do not
depend on panel sizes.  ``collect_stats=True`` adds a telemetry epilogue:
clip/underflow/rel-err/scale-spread accumulators ride in VMEM scratch and
are emitted as one (1, 8) vector, replacing the full re-QDQ that
``telemetry.tap_matmul`` used to pay (see ``finalize_quant_stats``).

**Phase 2 — the product** (``_xla_dot``).  XLA's own dot of the
quantize-pass outputs: bf16 x bf16 products with an f32 accumulator, the
result cast to the input dtype.  Its dimension numbers read ``pass``-mode
(unquantized) operands in their stored layout, so a bf16 x bf16 role
(the paper's FFN dgrad) runs no kernel at all.  This is the default
pipeline, ``two_pass``, for every granularity pair and shape: on a v5e
XLA's dot runs these shapes near the MXU's bf16 rate, while a quantize
pass costs a few bytes of HBM an element, once.

**Single-pass streaming pipeline** (``_stream_kernel``, reachable only
through ``pipeline="stream"`` or ``use_pipeline("stream")``).  ONE
``pallas_call`` whose grid walks ``(M/bm, N/bn)`` output tiles with K
innermost: each K-step's operand tiles are fetched by the grid pipeline,
quantized in VMEM and consumed directly by the MXU accumulation, so the
dequantized panels never touch HBM.  The LHS row panel is cached in a
VMEM scratch across the ``N/bn`` output-column revisits and the quantized
RHS across the ``M/bm`` output-row revisits, each under its own VMEM
budget; past the budgets, tiles re-quantize per revisit.  On a v5e that
requantization is VPU work (the chip has no bf16 VPU arithmetic) that
sets the kernel's pace, which is why it is not the default.
Because the codec is the bit-exact integer RTN of ``kernels.rounding`` and
SR noise is keyed by each element's *global* coordinate, re-quantizing a
tile reproduces the quantize pass bit-for-bit: the streaming pipeline's
telemetry stats are **bit-identical** to the two-pass pipeline's, and so
is ``y`` where a single K step (``bk == K``) leaves the two dots the same
f32 accumulation order; otherwise ``y`` differs in that order only.
``token``/``tensor`` granularities need their whole-reduction-axis amax
sweep before any element can quantize, so those roles route through the
two-pass pipeline automatically.

``fused_qmm`` orchestrates the pipelines and keeps its role-parameterized
contract: per-operand modes ``pass | block | tile | token | tensor``,
``trans_a``/``trans_b`` stored-layout transposition, per-operand formats
and pow2-scale flags, plus per-operand ``sr`` flags and seeds.  Tile
knobs: ``block`` (quant group, 128), ``bm``/``bn``/``bk`` (the streaming
kernel's MXU tiling — when all three are omitted the persistent
autotuning table (``kernels.autotune``, committed ``tuning_table.json``,
populated by ``kernel_bench --autotune``) is consulted first, falling
back to the ``_pick_tile`` heuristic on a miss).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FORMATS
from repro.kernels.rounding import (group_scale, hash_uniform, round_to_grid,
                                    snap_to_dtype, uniform_from_bits)

__all__ = ["fp4_matmul", "fused_qmm", "quantize_panels", "quantize_for_dot",
           "interpret_mode", "finalize_quant_stats", "QUANT_MODES",
           "STATS_WIDTH", "PIPELINES", "default_pipeline", "use_pipeline",
           "stream_supported", "resolve_pipeline"]

QUANT_MODES = ("pass", "block", "tile", "token", "tensor")

# Matmul pipelines: "two_pass" = quantize pass + XLA's dot (the default),
# "stream" = single-pass quantize->MXU fusion.  token/tensor granularities
# always take two_pass (see stream_supported).
PIPELINES = ("two_pass", "stream")

# LHS row-panel VMEM cache budget for the streaming kernel: the quantized
# (bm, K) panel is kept in scratch across N/bn output-column revisits when it
# fits, so the LHS quantizes exactly once.  Tests monkeypatch this to force
# the requantize-per-revisit branch.
_AQ_CACHE_BYTES = 4 * 1024 * 1024

# RHS VMEM cache budget: the full quantized (K, N) operand is kept in scratch
# across M/bm output-row revisits when it fits, so the RHS also quantizes
# exactly once.  Safe to cache bitwise: the SR noise is keyed by the tile's
# (j, kk) coordinates only, so an i-revisit would reproduce identical bits.
_BQ_CACHE_BYTES = 4 * 1024 * 1024

# Telemetry-epilogue accumulator lanes (f32, shape (1, STATS_WIDTH)):
#   0 clip count   1 underflow count   2 nonzero count   3 sum err^2
#   4 sum x^2      5 min group scale   6 max group scale 7 valid-element count
STATS_WIDTH = 8
_STATS_BIG = 3.0e38


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: everywhere but a TPU.

    The one place every kernel entry point decides it when the caller does
    not; a test that compiles for a described TPU patches this function.
    """
    return jax.default_backend() != "tpu"


def _mxu_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """In-kernel dot with an f32 accumulator.

    bf16 products are exact in f32, so bf16 operands pin DEFAULT
    precision: Mosaic refuses the fp32 contract precision that a global
    ``jax_default_matmul_precision="highest"`` would otherwise request.
    """
    prec = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=prec)


def _amax_f32(mag: jnp.ndarray) -> jnp.ndarray:
    """Whole-group amax as an f32 scalar (``tile`` and ``tensor`` groups).

    Mosaic cannot reduce a bf16 array to a scalar; the max of the exact
    f32 upcast is the same value, so this changes no bit anywhere.
    """
    return jnp.max(mag.astype(jnp.float32))


def _pick_tile(dim: int, block: int = 128) -> int:
    """Largest friendly tile (multiple of ``block``) dividing ``dim``."""
    for c in (4 * block, 3 * block, 2 * block, block):
        if dim % c == 0:
            return c
    raise ValueError(f"dim {dim} not a multiple of block {block}")


# Stack-shaped so nested `use_pipeline` contexts unwind correctly; the
# resolution happens OUTSIDE the jit boundary (`fused_qmm` is a plain python
# wrapper), so flipping the pipeline can never serve a stale jit cache.
_pipeline_stack = ["two_pass"]


def default_pipeline() -> str:
    """The pipeline `fused_qmm` uses when none is passed explicitly."""
    return _pipeline_stack[-1]


@contextlib.contextmanager
def use_pipeline(name: str):
    """Temporarily override the default matmul pipeline (re-entrant)."""
    assert name in PIPELINES, name
    _pipeline_stack.append(name)
    try:
        yield
    finally:
        _pipeline_stack.pop()


def stream_supported(a_mode: str, b_mode: str) -> bool:
    """Whether the streaming pipeline can run this granularity pair.

    ``token``/``tensor`` scale groups span the whole reduction axis — their
    amax sweep must complete before the first element can quantize, which is
    exactly the dependency the streaming pipeline removes — so those roles
    fall back to the two-pass pipeline.
    """
    streamable = ("pass", "block", "tile")
    return a_mode in streamable and b_mode in streamable


def resolve_pipeline(pipeline: Optional[str], a_mode: str,
                     b_mode: str) -> str:
    """The pipeline ``fused_qmm`` will actually run for this call: the
    explicit choice (or the process default), demoted to ``two_pass`` when
    the granularity pair is not streamable.  Exposed so observability
    layers (routing census / qlint) report the EFFECTIVE pipeline, not the
    requested one."""
    if pipeline is None:
        pipeline = default_pipeline()
    assert pipeline in PIPELINES, pipeline
    if pipeline == "stream" and not stream_supported(a_mode, b_mode):
        pipeline = "two_pass"
    return pipeline


def finalize_quant_stats(vec: jnp.ndarray):
    """Reduce a quantize-pass stats vector to the telemetry stat dict.

    Same four signals as ``telemetry.collect.operand_stats`` (clip /
    underflow / rel_err / scale_spread), but computed over the FULL operand
    in the quantization kernel itself (no group subsampling, no second QDQ
    pass).  Padded rows/cols are masked out of counts and scale extrema.
    """
    v = vec.reshape(STATS_WIDTH).astype(jnp.float32)
    clip_c, under, nz, err2, val2, smin, smax, cnt = (v[i] for i in range(8))
    smin = jnp.minimum(smin, smax)  # guard the +inf init if no valid group
    return {
        "clip": clip_c / jnp.maximum(cnt, 1.0),
        "underflow": under / jnp.maximum(nz, 1.0),
        "rel_err": jnp.sqrt(err2 / jnp.maximum(val2, 1e-30)),
        "scale_spread": jnp.log2(jnp.maximum(smax, 1e-30)
                                 / jnp.maximum(smin, 1e-30)),
    }


# ---------------------------------------------------------------------------
# In-kernel telemetry accumulation (shared by both pipelines)
# ---------------------------------------------------------------------------

def _stats_init():
    """Fresh per-grid-step stats partials (numpy scalars: kernel-closable)."""
    return dict(clip=np.float32(0), under=np.float32(0), nzc=np.float32(0),
                err2=np.float32(0), val2=np.float32(0),
                smin=np.float32(_STATS_BIG), smax=np.float32(0),
                cnt=np.float32(0))


def _stats_accum(st, sub, qsub, scale_f32, gvalid, fmt):
    """Fold one quant group's QDQ result into the stats partials."""
    af, qf = sub.astype(jnp.float32), qsub.astype(jnp.float32)
    magf = jnp.abs(af)
    nonzero = magf > 0  # zero-padding never counts as nonzero
    thr = scale_f32 * np.float32(fmt.max_value * (1.0 + 1e-6))
    st["clip"] += jnp.sum((magf > thr).astype(jnp.float32))
    st["under"] += jnp.sum((nonzero & (qf == 0)).astype(jnp.float32))
    st["nzc"] += jnp.sum(nonzero.astype(jnp.float32))
    st["err2"] += jnp.sum((af - qf) ** 2)
    st["val2"] += jnp.sum(af * af)
    st["smin"] = jnp.minimum(
        st["smin"], jnp.min(jnp.where(gvalid, scale_f32, _STATS_BIG)))
    st["smax"] = jnp.maximum(
        st["smax"], jnp.max(jnp.where(gvalid, scale_f32, 0.0)))


def _stats_slab_flush(sacc_ref, row, lane, st):
    """Fold one (block-row, k-slab) stats partial into its block-row's
    accumulator row of the (R, STATS_WIDTH) scratch (``row`` may be traced).

    Accumulation granularity is one ``(block, block)`` slab per flush —
    never a whole multi-slab tile — so the f32 fold each block-row sees is
    the SAME sequence of adds (its k-slabs in increasing-k order) no matter
    how the surrounding kernel tiles the operand.  This is what makes the
    stats bit-identical between the streaming and two-pass pipelines and
    across every ``(bm, bn, bk)``: order-sensitive float sums are pinned to
    a canonical order instead of the kernel's walk order.
    """
    addvec = jnp.stack(
        [st["clip"], st["under"], st["nzc"], st["err2"], st["val2"],
         jnp.zeros(()), jnp.zeros(()), st["cnt"]]).reshape(1, STATS_WIDTH)
    acc = sacc_ref[pl.ds(row, 1), :]
    new = acc + addvec
    new = jnp.where(lane == 5, jnp.minimum(acc, st["smin"]), new)
    new = jnp.where(lane == 6, jnp.maximum(acc, st["smax"]), new)
    sacc_ref[pl.ds(row, 1), :] = new


def _stats_fold(sacc_ref, lane):
    """Canonical final fold of the (R, STATS_WIDTH) per-block-row partials
    into the (1, STATS_WIDTH) output vector.  R depends only on the operand
    shape (never on the kernel tiling), so this reduction's shape — and
    therefore its bit pattern — is identical across pipelines and tilings."""
    acc = sacc_ref[...]
    tot = jnp.sum(acc, axis=0, keepdims=True)
    mn = jnp.min(acc, axis=0, keepdims=True)
    mx = jnp.max(acc, axis=0, keepdims=True)
    return jnp.where(lane == 5, mn, jnp.where(lane == 6, mx, tot))


# ---------------------------------------------------------------------------
# Phase 1: quantize pass
# ---------------------------------------------------------------------------

def _quant_kernel(*refs, mode, fmt, pow2, sr, trans, emit_trans, use_hw_rng,
                  grid_kind, bq, bkq, nk, block, m_real, k_real,
                  collect_stats, ext_amax):
    """QDQ one (bq, bkq) quant-orientation panel tile.

    Quant orientation = (non-reduction rows, reduction cols): (M, K) for
    the LHS, (N, K) for the RHS — groups always reduce along axis 1 here.
    ``trans`` transposes the stored read into that orientation in VMEM;
    ``emit_trans`` transposes the write back out (the RHS emits (K, N) so
    the product reads it plain).

    ``grid_kind``: 'one' = single sweep, grid (panels, ktiles) — block/tile
    groups live inside a tile.  'token' = grid (panels, 2, ktiles), sweep 0
    accumulates per-row amax in scratch; 'tensor' = grid (2, panels,
    ktiles), sweep 0 accumulates one global amax (the scale group spans the
    whole operand, so amax must complete before any element quantizes).
    ``ext_amax``: a ``token``/``tensor`` pass whose amax arrives as an
    input (per quant-orientation row, or one SMEM scalar) runs the single
    sweep 'one' — the sharded pass, whose groups span chips, takes it so.
    """
    it = iter(refs)
    seed_ref = next(it) if sr else None
    # Q_max arrives as a traced SMEM scalar: a compile-time-constant divisor
    # would be strength-reduced to reciprocal-multiply inside the kernel
    # (1 ulp off the QDQ reference's true division, and non-idempotent).
    qmax_ref = next(it)
    amax_in_ref = next(it) if ext_amax else None
    x_ref, o_ref = next(it), next(it)
    stats_ref = next(it) if collect_stats else None
    amax_ref = next(it) if grid_kind in ("token", "tensor") else None
    sacc_ref = next(it) if collect_stats else None
    qm = qmax_ref[0]

    if grid_kind == "one":
        p, kt, s = pl.program_id(0), pl.program_id(1), None
        first = (p == 0) & (kt == 0)
        last = ((p == pl.num_programs(0) - 1)
                & (kt == pl.num_programs(1) - 1))
    elif grid_kind == "token":
        p, s, kt = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        first = (p == 0) & (s == 0) & (kt == 0)
        last = ((p == pl.num_programs(0) - 1) & (s == 1)
                & (kt == pl.num_programs(2) - 1))
    else:  # tensor
        s, p, kt = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        first = (s == 0) & (p == 0) & (kt == 0)
        last = ((s == 1) & (p == pl.num_programs(1) - 1)
                & (kt == pl.num_programs(2) - 1))

    xt = x_ref[...]
    if trans:
        xt = xt.T  # stored (bkq, bq) -> effective (bq, bkq)
    in_dt = xt.dtype
    mag = jnp.abs(xt)

    if collect_stats:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, STATS_WIDTH), 1)

        @pl.when(first)
        def _():
            sacc_ref[...] = jnp.broadcast_to(
                jnp.where(lane == 5, _STATS_BIG, 0.0), sacc_ref.shape)

    # --- sweep 0: amax accumulation for whole-reduction-axis groups ------
    if grid_kind == "token":
        @pl.when((s == 0) & (kt == 0))
        def _():
            amax_ref[...] = jnp.zeros_like(amax_ref)

        @pl.when(s == 0)
        def _():
            amax_ref[...] = jnp.maximum(
                amax_ref[...],
                jnp.max(mag, axis=1, keepdims=True).astype(jnp.float32))
    elif grid_kind == "tensor":
        @pl.when(first)
        def _():
            amax_ref[...] = jnp.zeros_like(amax_ref)

        @pl.when(s == 0)
        def _():
            amax_ref[...] = jnp.maximum(amax_ref[...],
                                        _amax_f32(mag))

    # --- quantize sweep ---------------------------------------------------
    def _quantize():
        if sr:
            if use_hw_rng:
                # Distinct hardware stream per grid step (TPU path).
                pltpu.prng_seed(seed_ref[0] + p * nk + kt)
                bits = pltpu.bitcast(pltpu.prng_random_bits((bq, bkq)),
                                     jnp.uint32)
                noise = uniform_from_bits(bits)
            else:
                # Interpret mode: tiling-invariant counter hash keyed by the
                # element's global (row, col) in the effective operand.
                noise = hash_uniform((bq, bkq), seed_ref[0],
                                     p * bq, kt * bkq)
        else:
            noise = None

        if collect_stats:
            rows_valid = (p * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, 1), 0)) < m_real
            cols_valid = (kt * bkq + jax.lax.broadcasted_iota(
                jnp.int32, (1, bkq), 1)) < k_real

        if mode in ("block", "tile"):
            per_row = mode == "block"
            for i in range(bq // block):
                for j in range(bkq // block):
                    rs = slice(i * block, (i + 1) * block)
                    cs = slice(j * block, (j + 1) * block)
                    sub, smag = xt[rs, cs], mag[rs, cs]
                    amax = (jnp.max(smag, axis=1, keepdims=True) if per_row
                            else _amax_f32(smag))
                    scale = group_scale(amax, fmt, pow2, qm)
                    sc = scale.astype(in_dt)
                    nsub = noise[rs, cs] if noise is not None else None
                    qsub = round_to_grid(sub / sc, fmt, nsub) * sc
                    if emit_trans:
                        o_ref[cs, rs] = qsub.T
                    else:
                        o_ref[rs, cs] = qsub
                    if collect_stats:
                        if per_row:  # (1 x block) groups: row x k-group
                            gvalid = (rows_valid[rs]
                                      & (kt * bkq + j * block < k_real))
                        else:        # one (block x block) tile group
                            gvalid = ((p * bq + i * block < m_real)
                                      & (kt * bkq + j * block < k_real))
                        st = _stats_init()
                        _stats_accum(st, sub, qsub, scale, gvalid, fmt)
                        st["cnt"] = (
                            jnp.sum(rows_valid[rs].astype(jnp.float32))
                            * jnp.sum(cols_valid[:, cs].astype(jnp.float32)))
                        _stats_slab_flush(sacc_ref, p * (bq // block) + i,
                                          lane, st)
        else:  # token / tensor: scale broadcast from the amax scratch
            # (or the amax input)
            # tensor: one scalar scale (Mosaic cannot broadcast a (1, 1)
            # vector over sublanes and lanes at once).
            if ext_amax:
                amax = (amax_in_ref[...].astype(jnp.float32)
                        if mode == "token" else amax_in_ref[0])
            else:
                amax = (amax_ref[...] if grid_kind == "token"
                        else amax_ref[0, 0])
            scale = group_scale(amax, fmt, pow2, qm)
            sc = scale.astype(in_dt)
            qt = round_to_grid(xt / sc, fmt, noise) * sc
            o_ref[...] = qt.T if emit_trans else qt
            if collect_stats:
                gvalid = rows_valid if mode == "token" else True
                st = _stats_init()
                _stats_accum(st, xt, qt, scale, gvalid, fmt)
                st["cnt"] = (jnp.sum(rows_valid.astype(jnp.float32))
                             * jnp.sum(cols_valid.astype(jnp.float32)))
                # Whole-tile partial into the panel's first block-row: the
                # final fold sums all rows, so placement is arbitrary (only
                # two-pass runs token/tensor — no cross-pipeline order
                # contract to honor here).
                _stats_slab_flush(sacc_ref, p * (bq // block), lane, st)

    if grid_kind == "one":
        _quantize()
    else:
        pl.when(s == 1)(_quantize)

    if collect_stats:
        @pl.when(last)
        def _():
            stats_ref[...] = _stats_fold(sacc_ref, lane)


def _quantize_operand(t: jnp.ndarray, *, mode: str, fmt, pow2: bool,
                      sr: bool, seed: Optional[jnp.ndarray], trans: bool,
                      emit_trans: bool, block: int, m_real: int, k_real: int,
                      collect_stats: bool, interpret: bool,
                      amax: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Run the quantize pass over one padded stored operand.

    ``t`` read in quant orientation (rows, reduction) — transposed from the
    stored layout when ``trans`` — QDQ'd once, and written out as
    (rows, reduction), or (reduction, rows) when ``emit_trans`` (the RHS
    case, so phase 2 reads (K, N) plain).  Also returns the raw stats
    vector when ``collect_stats``.  ``amax`` (``token``/``tensor`` only):
    the groups' amax, given rather than swept, so one sweep quantizes:
    (rows, 1) in ``t``'s dtype for ``token`` (a max of its magnitudes is
    exact in it), one f32 in SMEM, (1,), for ``tensor``.
    """
    if trans:
        k_eff, m_eff = t.shape
    else:
        m_eff, k_eff = t.shape
    bq, bkq = _pick_tile(m_eff, block), _pick_tile(k_eff, block)
    np_, nk = m_eff // bq, k_eff // bkq
    ext_amax = amax is not None
    assert not ext_amax or mode in ("token", "tensor"), mode
    grid_kind = ("one" if ext_amax else
                 {"block": "one", "tile": "one",
                  "token": "token", "tensor": "tensor"}[mode])

    if grid_kind == "one":
        grid = (np_, nk)
        gids = lambda p, kt: (p, kt)            # noqa: E731
    elif grid_kind == "token":
        grid = (np_, 2, nk)
        gids = lambda p, s, kt: (p, kt)         # noqa: E731
    else:
        grid = (2, np_, nk)
        gids = lambda s, p, kt: (p, kt)         # noqa: E731

    def xmap(*ids):
        p, kt = gids(*ids)
        return (kt, p) if trans else (p, kt)

    in_specs = []
    operands = []
    if sr:
        assert seed is not None, "stochastic quantize pass needs a seed"
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed.reshape(1).astype(jnp.int32))
    # Q_max as a traced SMEM scalar (see _quant_kernel); the optimization
    # barrier keeps XLA from constant-folding it back into the kernel
    # (which would re-enable the reciprocal-multiply strength reduction).
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(jax.lax.optimization_barrier(
        jnp.full((1,), fmt.max_value, jnp.float32)))
    if ext_amax and mode == "token":
        in_specs.append(pl.BlockSpec((bq, 1),
                                     lambda *ids: (gids(*ids)[0], 0)))
        operands.append(amax.reshape(m_eff, 1))
    elif ext_amax:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(amax.reshape(1).astype(jnp.float32))
    in_specs.append(pl.BlockSpec((bkq, bq) if trans else (bq, bkq), xmap))
    operands.append(t)

    if emit_trans:
        out_specs = [pl.BlockSpec((bkq, bq),
                                  lambda *ids: tuple(reversed(gids(*ids))))]
        out_shapes = [jax.ShapeDtypeStruct((k_eff, m_eff), t.dtype)]
    else:
        out_specs = [pl.BlockSpec((bq, bkq), lambda *ids: gids(*ids))]
        out_shapes = [jax.ShapeDtypeStruct((m_eff, k_eff), t.dtype)]
    if collect_stats:
        out_specs.append(pl.BlockSpec((1, STATS_WIDTH), lambda *ids: (0, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((1, STATS_WIDTH), jnp.float32))

    scratch = []
    if grid_kind == "token":
        scratch.append(pltpu.VMEM((bq, 1), jnp.float32))
    elif grid_kind == "tensor":
        scratch.append(pltpu.VMEM((1, 1), jnp.float32))
    if collect_stats:
        # Per-block-row partials (see _stats_slab_flush): R rows depend only
        # on the operand shape, keeping the stats fold order canonical.
        scratch.append(pltpu.VMEM((m_eff // block, STATS_WIDTH),
                                  jnp.float32))

    kernel = functools.partial(
        _quant_kernel, mode=mode, fmt=fmt, pow2=pow2, sr=sr, trans=trans,
        emit_trans=emit_trans, use_hw_rng=not interpret, grid_kind=grid_kind,
        bq=bq, bkq=bkq, nk=nk, block=block, m_real=m_real, k_real=k_real,
        collect_stats=collect_stats, ext_amax=ext_amax)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        # Scratch accumulators (amax sweeps, stats epilogue) need sequential
        # revisiting; the quantize pass is VPU/bandwidth-bound anyway.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
    )(*operands)
    if collect_stats:
        return out[0], out[1]
    return out[0], None


@functools.partial(jax.jit, static_argnames=(
    "mode", "fmt_name", "pow2", "sr", "trans", "block", "real_dims",
    "collect_stats", "interpret"))
def quantize_panels(t: jnp.ndarray, *, mode: str = "block",
                    fmt_name: str = "fp4_e2m1", pow2: bool = False,
                    sr: bool = False, seed: Optional[jnp.ndarray] = None,
                    trans: bool = False, block: int = 128,
                    real_dims: Optional[Tuple[int, int]] = None,
                    collect_stats: bool = False,
                    interpret: Optional[bool] = None):
    """Public quantize-pass entry point (phase 1 standalone).

    ``t``: stored 2-D operand, dims multiples of ``block``; effective
    orientation is ``t.T`` under ``trans``; groups reduce along axis 1 of
    the effective operand (the LHS convention).  Returns the QDQ'd
    effective operand, or ``(values, stats_vec)`` with ``collect_stats``
    (see ``finalize_quant_stats``).  ``real_dims`` = unpadded (rows, cols)
    of the effective operand, used only to mask padding out of the stats.
    """
    assert mode in QUANT_MODES and mode != "pass", mode
    if interpret is None:
        interpret = interpret_mode()
    m_eff, k_eff = (t.shape[1], t.shape[0]) if trans else t.shape
    m_real, k_real = real_dims if real_dims is not None else (m_eff, k_eff)
    q, stats = _quantize_operand(
        t, mode=mode, fmt=FORMATS[fmt_name], pow2=pow2, sr=sr, seed=seed,
        trans=trans, emit_trans=False, block=block, m_real=m_real,
        k_real=k_real, collect_stats=collect_stats, interpret=interpret)
    return (q, stats) if collect_stats else q


def quantize_for_dot(t: jnp.ndarray, *, lhs: bool, mode: str,
                     fmt_name: str, pow2: bool, sr: bool,
                     seed: Optional[jnp.ndarray], trans: bool, block: int,
                     rows_real: int, k_real: int, collect_stats: bool,
                     interpret: bool, amax: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Phase 1 for one stored operand of ``A' @ B'`` (``trans`` is its
    ``trans_a``/``trans_b``), emitted in the layout the product reads
    plain: the LHS as (M, K), the RHS as (K, N).

    The LHS quant orientation (M, K) is its effective orientation.  The
    RHS quant orientation is (N, K) — groups reduce over K, which is axis
    0 of the effective (K, N) — so its pass reads the stored array
    transposed iff NOT ``trans`` and emits (K, N) back.  ``rows_real``/
    ``k_real``: the unpadded quant-orientation dims (stats masking);
    ``amax``: see ``_quantize_operand``.
    """
    fmt = FORMATS[fmt_name]
    return _quantize_operand(
        t, mode=mode, fmt=fmt, pow2=pow2, sr=sr and not fmt.passthrough,
        seed=seed, trans=trans if lhs else not trans, emit_trans=not lhs,
        block=block, m_real=rows_real, k_real=k_real,
        collect_stats=collect_stats, interpret=interpret, amax=amax)


# ---------------------------------------------------------------------------
# Phase 2: the product on XLA's dot (no quantization left in here)
# ---------------------------------------------------------------------------

def _xla_dot(a: jnp.ndarray, b: jnp.ndarray, *, trans_a: bool,
             trans_b: bool) -> jnp.ndarray:
    """y = A' @ B' with an f32 accumulator, cast to ``a.dtype``.

    The arithmetic of ``_mxu_dot``: bf16 operands pin DEFAULT precision
    (their products are exact in f32); wider operands take HIGHEST, the
    full-precision contract a Mosaic dot gives them.  Stored layouts are
    read through the dimension numbers, so no transpose is materialized.
    """
    prec = (jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)
    dims = (((0 if trans_a else 1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(
        a, b, dims, precision=prec,
        preferred_element_type=jnp.float32).astype(a.dtype)


# ---------------------------------------------------------------------------
# Single-pass streaming pipeline: quantize fused into the MXU loop
# ---------------------------------------------------------------------------

def _qdq_stream_tile(xt, *, mode, fmt, pow2, qm, block, noise, sacc_ref,
                     lane, gate, row0, col0, m_real, k_real):
    """QDQ one (R, C) quant-orientation tile inside the streaming kernel.

    Mirrors ``_quant_kernel``'s block/tile sub-group loop op-for-op (same
    amax -> scale -> divide -> round -> rescale order on the same 128-aligned
    groups), so every element's QDQ value is bit-identical to the two-pass
    quantize pass.  ``row0``/``col0`` are the tile's global offsets in the
    quant-orientation operand (traced scalars): they key the SR noise and
    mask padding out of the stats.  Stats (from the pre-materialization
    qsub, exactly as ``_quant_kernel``) flush per (block-row, k-slab) into
    ``sacc_ref`` — the canonical order that makes them tiling- and
    pipeline-invariant — under ``gate`` (the once-per-element condition,
    e.g. first operand revisit); ``sacc_ref=None`` skips stats entirely.
    """
    rt, ct = xt.shape
    in_dt = xt.dtype
    mag = jnp.abs(xt)
    per_row = mode == "block"
    if sacc_ref is not None:
        rows_valid = (row0 + jax.lax.broadcasted_iota(
            jnp.int32, (rt, 1), 0)) < m_real
        cols_valid = (col0 + jax.lax.broadcasted_iota(
            jnp.int32, (1, ct), 1)) < k_real
    rows = []
    for i in range(rt // block):
        cols = []
        for j in range(ct // block):
            rs = slice(i * block, (i + 1) * block)
            cs = slice(j * block, (j + 1) * block)
            sub, smag = xt[rs, cs], mag[rs, cs]
            amax = (jnp.max(smag, axis=1, keepdims=True) if per_row
                    else _amax_f32(smag))
            scale = group_scale(amax, fmt, pow2, qm)
            sc = scale.astype(in_dt)
            nsub = noise[rs, cs] if noise is not None else None
            qsub = round_to_grid(sub / sc, fmt, nsub) * sc
            cols.append(qsub)
            if sacc_ref is not None:
                if per_row:  # (1 x block) groups: row x k-group
                    gvalid = rows_valid[rs] & (col0 + j * block < k_real)
                else:        # one (block x block) tile group
                    gvalid = ((row0 + i * block < m_real)
                              & (col0 + j * block < k_real))
                st = _stats_init()
                _stats_accum(st, sub, qsub, scale, gvalid, fmt)
                st["cnt"] = (
                    jnp.sum(rows_valid[rs].astype(jnp.float32))
                    * jnp.sum(cols_valid[:, cs].astype(jnp.float32)))
                row = row0 // block + i

                def _flush(row=row, st=st):
                    _stats_slab_flush(sacc_ref, row, lane, st)
                if gate is None:
                    _flush()
                else:
                    pl.when(gate)(_flush)
        rows.append(cols[0] if len(cols) == 1 else
                    jnp.concatenate(cols, axis=1))
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)


def _stream_noise(shape, seed_ref, tile_id, nk, row0, col0, use_hw_rng):
    """SR noise for one streamed tile.

    Interpret mode uses the coordinate-keyed counter hash — bit-identical
    to the two-pass quantize pass AND tiling-invariant, because each
    element's noise depends only on its global (row, col).  On TPU the
    hardware PRNG is reseeded per (tile, K-step) — deterministic across
    revisits of the same tile, but a different stream than the two-pass
    pipeline's panel order (the standing PR-3 TPU-validation caveat).
    """
    if use_hw_rng:
        pltpu.prng_seed(seed_ref[0] + tile_id * nk + pl.program_id(2))
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
        return uniform_from_bits(bits)
    return hash_uniform(shape, seed_ref[0], row0, col0)


def _stream_kernel(*refs, a_mode, b_mode, fmt_a, fmt_b, a_pow2, b_pow2,
                   sr_a, sr_b, trans_a, trans_b, use_hw_rng, cache_a,
                   cache_b, bm, bn, bk, nk, block, m_real, k_real, n_real,
                   emit_sa, emit_sb):
    """One fused grid step: quantize the (i, kk) / (j, kk) operand tiles in
    VMEM and accumulate their product into the (i, j) output tile.

    Grid (M/bm, N/bn, K/bk), K innermost, sequential ("arbitrary") order.
    The LHS panel is quantized once per ``i`` (at j == 0) into the ``aq``
    VMEM scratch when ``cache_a``, else requantized per revisit (bit-
    identical either way — the codec is deterministic given the element's
    global coordinate).  RHS tiles are quantized once (at i == 0) into the
    ``bq`` VMEM scratch when ``cache_b``, else requantized per ``i``
    revisit — also bit-identical, the SR seed never involves ``i``.  Stats
    accumulate exactly once per element (A gated on j == 0, B on i == 0)
    into per-operand scratch, flushed to the stats outputs at the last step.
    """
    it = iter(refs)
    seed_a_ref = next(it) if sr_a else None
    seed_b_ref = next(it) if sr_b else None
    qmax_a_ref = next(it) if a_mode != "pass" else None
    qmax_b_ref = next(it) if b_mode != "pass" else None
    a_ref, b_ref, o_ref = next(it), next(it), next(it)
    stats_a_ref = next(it) if emit_sa else None
    stats_b_ref = next(it) if emit_sb else None
    acc_ref = next(it)
    aq_ref = next(it) if cache_a else None
    bq_ref = next(it) if cache_b else None
    sa_ref = next(it) if emit_sa else None
    sb_ref = next(it) if emit_sb else None

    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    first = (i == 0) & (j == 0) & (kk == 0)
    last = ((i == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1)
            & (kk == pl.num_programs(2) - 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, STATS_WIDTH), 1)

    if emit_sa or emit_sb:
        @pl.when(first)
        def _():
            init = jnp.where(lane == 5, jnp.float32(_STATS_BIG),
                             jnp.float32(0.0))
            if sa_ref is not None:
                sa_ref[...] = jnp.broadcast_to(init, sa_ref.shape)
            if sb_ref is not None:
                sb_ref[...] = jnp.broadcast_to(init, sb_ref.shape)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- LHS tile -> effective (bm, bk) -----------------------------------
    def _qdq_a(gate):
        xt = a_ref[...]
        if trans_a:
            xt = xt.T  # stored (bk, bm) -> quant orientation (bm, bk)
        noise = (_stream_noise((bm, bk), seed_a_ref, i, nk,
                               i * bm, kk * bk, use_hw_rng)
                 if sr_a else None)
        q = _qdq_stream_tile(xt, mode=a_mode, fmt=fmt_a, pow2=a_pow2,
                             qm=qmax_a_ref[0], block=block, noise=noise,
                             sacc_ref=sa_ref, lane=lane, gate=gate,
                             row0=i * bm, col0=kk * bk,
                             m_real=m_real, k_real=k_real)
        return snap_to_dtype(q)

    if a_mode == "pass":
        at = a_ref[...]
        if trans_a:
            at = at.T
    elif cache_a:
        @pl.when(j == 0)
        def _():
            # The whole call runs once per (i, kk) — stats ungated inside.
            aq_ref[:, pl.ds(kk * bk, bk)] = _qdq_a(gate=None)
        at = aq_ref[:, pl.ds(kk * bk, bk)]
    else:
        # Requantized per j-revisit; stats must still fold exactly once.
        at = _qdq_a(gate=(j == 0))

    # --- RHS tile -> effective (bk, bn) -----------------------------------
    def _qdq_b(gate):
        xt = b_ref[...]
        if not trans_b:
            xt = xt.T  # effective (bk, bn) -> quant orientation (bn, bk)
        noise = (_stream_noise((bn, bk), seed_b_ref, j, nk,
                               j * bn, kk * bk, use_hw_rng)
                 if sr_b else None)
        q = _qdq_stream_tile(xt, mode=b_mode, fmt=fmt_b, pow2=b_pow2,
                             qm=qmax_b_ref[0], block=block, noise=noise,
                             sacc_ref=sb_ref, lane=lane, gate=gate,
                             row0=j * bn, col0=kk * bk,
                             m_real=n_real, k_real=k_real)
        return snap_to_dtype(q).T  # (bk, bn)

    if b_mode == "pass":
        bt = b_ref[...]
        if trans_b:
            bt = bt.T  # stored (bn, bk) -> effective (bk, bn)
    elif cache_b:
        @pl.when(i == 0)
        def _():
            # The whole call runs once per (j, kk) — stats ungated inside.
            bq_ref[pl.ds(kk * bk, bk), pl.ds(j * bn, bn)] = _qdq_b(gate=None)
        bt = bq_ref[pl.ds(kk * bk, bk), pl.ds(j * bn, bn)]
    else:
        # Requantized per i-revisit; stats must still fold exactly once.
        bt = _qdq_b(gate=(i == 0))

    acc_ref[...] += _mxu_dot(at, bt)

    @pl.when(kk == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    if emit_sa or emit_sb:
        @pl.when(last)
        def _():
            if stats_a_ref is not None:
                stats_a_ref[...] = _stats_fold(sa_ref, lane)
            if stats_b_ref is not None:
                stats_b_ref[...] = _stats_fold(sb_ref, lane)


def _stream_qmm(a: jnp.ndarray, b: jnp.ndarray, *, a_mode, b_mode,
                fmt_a, fmt_b, a_pow2, b_pow2, sr_a, sr_b, seed_a, seed_b,
                trans_a, trans_b, block, bm, bn, bk, m_real, k_real, n_real,
                collect_stats, interpret):
    """Build the single fused pallas_call for the streaming pipeline.

    Returns ``(y, (stats_a, stats_b))`` — stats slots None for pass-mode
    operands or when ``collect_stats`` is off.
    """
    m, k = (a.shape[1], a.shape[0]) if trans_a else a.shape
    _, n = (b.shape[1], b.shape[0]) if trans_b else b.shape
    grid = (m // bm, n // bn, k // bk)
    ni, nj, nk = grid
    # Cache the quantized LHS row panel across output-column revisits when
    # it fits the VMEM budget (weight-stationary flavor: quantize A once).
    cache_a = (a_mode != "pass" and nj > 1
               and bm * k * a.dtype.itemsize <= _AQ_CACHE_BYTES)
    # Cache the full quantized RHS across output-row revisits likewise
    # (quantize B once; the SR seed is (j, kk)-keyed so this is bitwise
    # identical to requantizing).
    cache_b = (b_mode != "pass" and ni > 1
               and k * n * b.dtype.itemsize <= _BQ_CACHE_BYTES)
    emit_sa = collect_stats and a_mode != "pass"
    emit_sb = collect_stats and b_mode != "pass"

    in_specs, operands = [], []
    for sr, seed in ((sr_a, seed_a), (sr_b, seed_b)):
        if sr:
            assert seed is not None, "stochastic rounding needs a seed"
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            operands.append(seed.reshape(1).astype(jnp.int32))
    for mode, fmt in ((a_mode, fmt_a), (b_mode, fmt_b)):
        if mode != "pass":
            # Q_max as a traced SMEM scalar (see _quant_kernel).
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            operands.append(jax.lax.optimization_barrier(
                jnp.full((1,), fmt.max_value, jnp.float32)))
    in_specs.append(
        pl.BlockSpec((bk, bm), lambda i, j, kk: (kk, i)) if trans_a
        else pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)))
    operands.append(a)
    in_specs.append(
        pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)) if trans_b
        else pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)))
    operands.append(b)

    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))]
    out_shapes = [jax.ShapeDtypeStruct((m, n), a.dtype)]
    for emit in (emit_sa, emit_sb):
        if emit:
            out_specs.append(pl.BlockSpec((1, STATS_WIDTH),
                                          lambda i, j, kk: (0, 0)))
            out_shapes.append(
                jax.ShapeDtypeStruct((1, STATS_WIDTH), jnp.float32))

    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if cache_a:
        scratch.append(pltpu.VMEM((bm, k), a.dtype))
    if cache_b:
        scratch.append(pltpu.VMEM((k, n), b.dtype))
    # Per-block-row stats partials (see _stats_slab_flush): one row per
    # 128-row slab of the quant-orientation operand (A: M rows, B: N rows),
    # tiling-independent so the final fold order is canonical.
    if emit_sa:
        scratch.append(pltpu.VMEM((m // block, STATS_WIDTH), jnp.float32))
    if emit_sb:
        scratch.append(pltpu.VMEM((n // block, STATS_WIDTH), jnp.float32))

    kernel = functools.partial(
        _stream_kernel, a_mode=a_mode, b_mode=b_mode, fmt_a=fmt_a,
        fmt_b=fmt_b, a_pow2=a_pow2, b_pow2=b_pow2, sr_a=sr_a, sr_b=sr_b,
        trans_a=trans_a, trans_b=trans_b, use_hw_rng=not interpret,
        cache_a=cache_a, cache_b=cache_b, bm=bm, bn=bn, bk=bk, nk=nk,
        block=block,
        m_real=m_real, k_real=k_real, n_real=n_real,
        emit_sa=emit_sa, emit_sb=emit_sb)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        # Scratch (acc, LHS panel cache, stats) is revisited across grid
        # steps -> sequential order required.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(*operands)
    y = outs[0]
    idx = 1
    stats_a = stats_b = None
    if emit_sa:
        stats_a, idx = outs[idx], idx + 1
    if emit_sb:
        stats_b = outs[idx]
    return y, (stats_a, stats_b)


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "a_mode", "b_mode", "a_fmt", "b_fmt", "a_pow2", "b_pow2", "a_sr", "b_sr",
    "trans_a", "trans_b", "block", "bm", "bn", "bk", "pipeline", "real_dims",
    "collect_stats", "interpret"))
def _fused_qmm(a: jnp.ndarray, b: jnp.ndarray, *,
               a_mode: str, b_mode: str, a_fmt: str, b_fmt: str,
               a_pow2: bool, b_pow2: bool, a_sr: bool, b_sr: bool,
               seed_a: Optional[jnp.ndarray], seed_b: Optional[jnp.ndarray],
               trans_a: bool, trans_b: bool, block: int,
               bm: Optional[int], bn: Optional[int], bk: Optional[int],
               pipeline: str,
               real_dims: Optional[Tuple[int, int, int]],
               collect_stats: bool, interpret: bool):
    """Jit'd pipeline body — every knob arrives concrete (see fused_qmm)."""
    assert a_mode in QUANT_MODES and b_mode in QUANT_MODES, (a_mode, b_mode)
    assert pipeline in PIPELINES, pipeline
    m, k = (a.shape[1], a.shape[0]) if trans_a else a.shape
    kb, n = (b.shape[1], b.shape[0]) if trans_b else b.shape
    assert k == kb, (a.shape, b.shape, trans_a, trans_b)
    assert m % block == 0 and k % block == 0 and n % block == 0, \
        (m, k, n, block)
    mr, kr, nr = real_dims if real_dims is not None else (m, k, n)

    if pipeline == "stream":
        assert stream_supported(a_mode, b_mode), (a_mode, b_mode)
        assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
            (m, n, k, bm, bn, bk)
        y, (stats_a, stats_b) = _stream_qmm(
            a, b, a_mode=a_mode, b_mode=b_mode,
            fmt_a=FORMATS[a_fmt], fmt_b=FORMATS[b_fmt],
            a_pow2=a_pow2, b_pow2=b_pow2,
            sr_a=(a_sr and a_mode != "pass"
                  and not FORMATS[a_fmt].passthrough),
            sr_b=(b_sr and b_mode != "pass"
                  and not FORMATS[b_fmt].passthrough),
            seed_a=seed_a, seed_b=seed_b, trans_a=trans_a, trans_b=trans_b,
            block=block, bm=bm, bn=bn, bk=bk, m_real=mr, k_real=kr,
            n_real=nr, collect_stats=collect_stats, interpret=interpret)
        if collect_stats:
            return y, (stats_a, stats_b)
        return y

    stats_a = stats_b = None
    mm_trans_a, mm_trans_b = trans_a, trans_b
    if a_mode != "pass":
        a, stats_a = quantize_for_dot(
            a, lhs=True, mode=a_mode, fmt_name=a_fmt, pow2=a_pow2, sr=a_sr,
            seed=seed_a, trans=trans_a, block=block, rows_real=mr,
            k_real=kr, collect_stats=collect_stats, interpret=interpret)
        mm_trans_a = False
    if b_mode != "pass":
        b, stats_b = quantize_for_dot(
            b, lhs=False, mode=b_mode, fmt_name=b_fmt, pow2=b_pow2, sr=b_sr,
            seed=seed_b, trans=trans_b, block=block, rows_real=nr,
            k_real=kr, collect_stats=collect_stats, interpret=interpret)
        mm_trans_b = False

    y = _xla_dot(a, b, trans_a=mm_trans_a, trans_b=mm_trans_b)
    if collect_stats:
        return y, (stats_a, stats_b)
    return y


def fused_qmm(a: jnp.ndarray, b: jnp.ndarray, *,
              a_mode: str = "block", b_mode: str = "tile",
              a_fmt: str = "fp4_e2m1", b_fmt: str = "fp4_e2m1",
              a_pow2: bool = False, b_pow2: bool = False,
              a_sr: bool = False, b_sr: bool = False,
              seed_a: Optional[jnp.ndarray] = None,
              seed_b: Optional[jnp.ndarray] = None,
              trans_a: bool = False, trans_b: bool = False,
              block: int = 128,
              bm: Optional[int] = None, bn: Optional[int] = None,
              bk: Optional[int] = None,
              pipeline: Optional[str] = None,
              real_dims: Optional[Tuple[int, int, int]] = None,
              collect_stats: bool = False,
              interpret: bool = False):
    """y = Q(A') @ Q(B'); A' = a^T under ``trans_a`` (same for B').
    Effective shapes A': (M, K), B': (K, N); all dims must be multiples of
    ``block`` (the ops.py wrapper pads).

    ``pipeline`` picks the implementation: ``"two_pass"`` (the default
    via ``default_pipeline``/``use_pipeline``) quantizes each operand once
    and runs the product on XLA's dot; ``"stream"`` fuses quantize into
    the MXU loop in ONE pallas_call.  Their quantized values and stats are
    bit-identical; ``y`` differs only in the f32 accumulation order along
    K.  token/tensor granularities silently take two_pass
    (stream_supported).

    Tiling (the streaming kernel's only): explicit ``bm``/``bn``/``bk``
    win; when ALL are omitted the autotuning table (``kernels.autotune``)
    is consulted, falling back to the ``_pick_tile`` heuristic on a miss
    (partially-specified tiles skip the table).  This wrapper is
    deliberately NOT jit'd: pipeline and tile resolution happen per call,
    outside the jit boundary, so a flipped default pipeline or an updated
    tuning table can never serve a stale jit cache — the resolved static
    knobs key ``_fused_qmm``'s cache.

    ``a_sr``/``b_sr`` enable in-kernel stochastic rounding (seeds
    required); ``real_dims`` = unpadded (M, K, N) for stats masking; with
    ``collect_stats`` returns ``(y, (stats_a, stats_b))`` where pass-mode
    slots are None.
    """
    pipeline = resolve_pipeline(pipeline, a_mode, b_mode)
    if pipeline == "stream":
        m, k = (a.shape[1], a.shape[0]) if trans_a else a.shape
        _, n = (b.shape[1], b.shape[0]) if trans_b else b.shape
        if bm is None and bn is None and bk is None:
            from repro.kernels import autotune  # lazy: autotune imports us
            hit = autotune.resolve_tiles(
                m, n, k, dtypes=(a.dtype.name, b.dtype.name),
                modes=(a_mode, b_mode), trans=(trans_a, trans_b),
                block=block)
            if hit is not None:
                bm, bn, bk = hit
        bm = bm if bm is not None else _pick_tile(m, block)
        bn = bn if bn is not None else _pick_tile(n, block)
        bk = bk if bk is not None else _pick_tile(k, block)
    else:
        bm = bn = bk = None
    return _fused_qmm(a, b, a_mode=a_mode, b_mode=b_mode, a_fmt=a_fmt,
                      b_fmt=b_fmt, a_pow2=a_pow2, b_pow2=b_pow2, a_sr=a_sr,
                      b_sr=b_sr, seed_a=seed_a, seed_b=seed_b,
                      trans_a=trans_a, trans_b=trans_b, block=block,
                      bm=bm, bn=bn, bk=bk, pipeline=pipeline,
                      real_dims=real_dims, collect_stats=collect_stats,
                      interpret=interpret)


def fp4_matmul(x: jnp.ndarray, w: jnp.ndarray, *,
               x_fmt: str = "fp4_e2m1", w_fmt: str = "fp4_e2m1",
               block: int = 128, interpret: bool = False) -> jnp.ndarray:
    """y = Q_blk(x) @ Q_tile(w) (the paper's fwd FFN matmul).

    x: (M, K), w: (K, N); M, K, N must be multiples of ``block``
    (the ops.py wrapper pads).  Returns x.dtype.  Kept as the historical
    fwd-only entry point; a thin specialization of ``fused_qmm`` (and like
    it deliberately un-jit'd, so the pipeline default resolves per call).
    """
    return fused_qmm(x, w, a_mode="block", b_mode="tile", a_fmt=x_fmt,
                     b_fmt=w_fmt, block=block, interpret=interpret)
