"""Shared rounding / scaling helpers for every quantization code path.

This is the single source of truth for kernel-side rounding: the fused
matmul pipeline (``kernels.fp4_matmul``), the standalone quantizer
(``kernels.quantize``) and the pure-jnp oracles (``kernels.ref``) all import
from here instead of carrying private ``_round_tile`` copies.

``round_to_grid`` is the *bit-exact integer* round-to-nearest: instead of the
``log2``/``ldexp`` transcendentals of ``formats.round_to_format`` (VPU-hostile
inside a Pallas kernel), it extracts the binade exponent straight from the
f32 bit pattern and assembles the per-binade grid step by writing the
exponent field back — every intermediate is an exact integer/power-of-two
operation, so the result lands on exactly the same grid as
``formats.round_to_format`` (tested on a dense sweep of exponent-boundary
values in ``tests/test_rounding.py``).  With ``noise`` it becomes the
unbiased stochastic-rounding codec (``floor(t + u)``, ``u ~ U[0,1)``),
matching the QDQ SR reference in distribution.

``hash_uniform`` is a counter-based (Philox-style, but cheaper) uniform
generator built purely from uint32 vector arithmetic: every element's noise
is a hash of its *global* (row, col) coordinate plus the seed, so stochastic
rounding results are independent of the kernel's tile sizes and grid order,
and the same code path runs under Pallas interpret mode (where
``pltpu.prng_seed``/``prng_random_bits`` have no CPU lowering) and on TPU.
The fused kernel uses the hardware PRNG on real TPUs and this hash in
interpret mode (see ``kernels.fp4_matmul``).

Dtype discipline: math runs in f32 internally (bit tricks need the IEEE
f32 layout) but both grids and steps are exact powers of two, so results
cast back to bf16 without error — callers keep the input-dtype QDQ
discipline of ``core.quantize.quantize_dequantize``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# The Eq.-3 scale formula (including its eps floor) is owned by
# core.quantize — shared bitwise by the QDQ reference, these kernels and
# the telemetry stats; re-exported here under the kernel-side names.
from repro.core.quantize import pow2_floor, scale_from_amax

__all__ = ["round_to_grid", "pow2_floor", "group_scale",
           "quantize_tile", "hash_bits", "hash_uniform",
           "uniform_from_bits", "fold_seed", "snap_to_dtype"]

_F32_MANT = 23
_F32_BIAS = 127


def snap_to_dtype(t: jnp.ndarray) -> jnp.ndarray:
    """Force a (possibly wider-carried) bf16 intermediate onto the bf16 grid.

    Inside a fused Pallas kernel XLA:CPU carries bf16 intermediates at f32
    precision; a value that the two-pass pipeline would round through a bf16
    HBM write can therefore reach a downstream consumer (the MXU dot, a
    rounding tie) with extra mantissa bits.  A bitcast round-trip forces
    materialization on the bf16 grid; outside kernels, and for every other
    dtype, it is an exact no-op.
    """
    if t.dtype == jnp.bfloat16:
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(t, jnp.uint16), jnp.bfloat16)
    return t


def round_to_grid(t: jnp.ndarray, fmt,
                  noise: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Round pre-scaled values onto ``fmt``'s grid — bit-exact integer RTN.

    Matches ``formats.round_to_format`` exactly (RTN half-to-even, clip to
    ``fmt.max_value``, fixed subnormal grid ``2^(emin - mbits)``) without
    transcendentals: the binade exponent comes from the f32 exponent field
    and the grid step is assembled by writing ``e - mbits`` back into an
    exponent field.  ``noise`` (uniform [0,1), same shape) switches to
    stochastic rounding ``floor(t/step + u) * step`` — the unbiased codec
    the QDQ reference implements via ``jax.random.uniform``.
    """
    orig_dtype = t.dtype
    # The pre-scaled quotient reaching us may be carried wider than bf16
    # inside a fused kernel — a plain upcast would leak that extra precision
    # and flip RTN ties vs the (properly rounded) QDQ reference; snap it
    # onto the bf16 grid first (see snap_to_dtype).
    t = snap_to_dtype(t)
    xf = t.astype(jnp.float32)
    sign = jnp.sign(xf)
    mag = jnp.minimum(jnp.abs(xf), np.float32(fmt.max_value))
    bits = jax.lax.bitcast_convert_type(mag, jnp.int32)
    # floor(log2(mag)) for normal f32 is the unbiased exponent field; f32
    # subnormals (and 0) give field 0 -> e = -127 -> clamped to emin, which
    # reproduces round_to_format's fixed subnormal grid including the
    # round-to-zero of anything far below it.
    e = jnp.maximum((bits >> _F32_MANT) - _F32_BIAS, fmt.emin)
    step = jax.lax.bitcast_convert_type(
        (e - fmt.mbits + _F32_BIAS) << _F32_MANT, jnp.float32)
    scaled = mag / step  # step is a power of two: division is exact
    if noise is None:
        q = jnp.round(scaled)  # round-half-to-even, IEEE default
    else:
        q = jnp.floor(scaled + noise.astype(jnp.float32))
    out = sign * q * step
    # Rounding the top binade up can exceed max_value -> saturate again.
    out = jnp.clip(out, -fmt.max_value, fmt.max_value)
    return out.astype(orig_dtype)


def group_scale(amax: jnp.ndarray, fmt, pow2: bool = False,
                qmax=None) -> jnp.ndarray:
    """Per-group scale ``alpha = amax / Q_max`` — alias of
    ``core.quantize.scale_from_amax`` (one formula, shared bitwise across
    the QDQ path, the fused pipeline and the telemetry stats).  In-kernel
    callers must pass ``qmax`` as a traced scalar (see scale_from_amax)."""
    return scale_from_amax(amax, fmt, pow2, qmax)


def quantize_tile(tile: jnp.ndarray, fmt, *, per_row: bool,
                  pow2: bool = False,
                  noise: Optional[jnp.ndarray] = None,
                  qmax: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """QDQ a VMEM tile: per-row (1 x cols) scales or one whole-tile scale.

    Input-dtype discipline (amax in the input dtype, scale math f32,
    divide/round/rescale in the input dtype) matches
    ``core.quantize.quantize_dequantize`` elementwise — in bf16 too.
    In-kernel callers pass ``qmax`` as a traced scalar so the scale division
    stays true IEEE division (see ``core.quantize.scale_from_amax``).
    """
    mag = jnp.abs(tile)
    amax = (jnp.max(mag, axis=-1, keepdims=True) if per_row
            else jnp.max(mag))
    s = group_scale(amax, fmt, pow2, qmax).astype(tile.dtype)
    return round_to_grid(tile / s, fmt, noise) * s


# ---------------------------------------------------------------------------
# Counter-based uniform noise (stochastic rounding, interpret-mode safe)
# ---------------------------------------------------------------------------

# numpy scalars (not jnp): Pallas kernels may not close over jax arrays.
_PHI = np.uint32(0x9E3779B9)   # golden-ratio increment (Weyl / xxhash)
_M1 = np.uint32(0x85EBCA6B)    # murmur3 finalizer constants
_M2 = np.uint32(0xC2B2AE35)


def _mix(h: jnp.ndarray) -> jnp.ndarray:
    h = h ^ (h >> 16)
    h = h * _M1
    h = h ^ (h >> 13)
    h = h * _M2
    h = h ^ (h >> 16)
    return h


def hash_bits(shape, seed: jnp.ndarray, row0, col0) -> jnp.ndarray:
    """uint32 hash bits keyed by (seed, global row, global col).

    ``row0``/``col0`` are the tile's global offsets (traced scalars are
    fine); pure uint32 vector ops, so this lowers inside Pallas on TPU and
    in interpret mode alike, and the stream is tiling-invariant.
    """
    r = jnp.asarray(row0).astype(jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, shape, 0)
    c = jnp.asarray(col0).astype(jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, shape, 1)
    h = jnp.asarray(seed).astype(jnp.uint32) * _PHI
    h = _mix(h ^ (r * _M1))
    h = _mix(h ^ (c * _M2))
    return h


def uniform_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """Map uint32 bits to f32 uniform [0, 1) using the top 24 bits.

    The 24-bit integer goes to f32 through int32 (a same-width bitcast,
    then an exact signed convert): Mosaic has no uint32 -> f32 cast."""
    top = jax.lax.bitcast_convert_type(bits >> np.uint32(8), jnp.int32)
    return top.astype(jnp.float32) * np.float32(2 ** -24)


def hash_uniform(shape, seed: jnp.ndarray, row0, col0) -> jnp.ndarray:
    """f32 uniform [0,1) noise keyed by (seed, global element coordinate)."""
    return uniform_from_bits(hash_bits(shape, seed, row0, col0))


def fold_seed(key_data: jnp.ndarray, salt: int, which: int) -> jnp.ndarray:
    """Derive an int32 kernel PRNG seed from raw uint32[2] key material.

    Cheap integer folding with the same mixing constants as ``hash_bits``
    (one source of truth); distinct per (key, salt, operand index).
    """
    kd = key_data.astype(jnp.uint32)
    base = kd[0] ^ (kd[1] * _PHI)
    base = base ^ np.uint32(((salt * 2 + which) * int(_M1)) & 0xFFFFFFFF)
    return base.astype(jnp.int32).reshape(1)
