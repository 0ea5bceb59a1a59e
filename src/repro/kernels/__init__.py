"""Pallas TPU kernels for the paper's compute hot-spots.

  fp4_matmul        quantize-once pipeline: per-operand Pallas quantize
                    pass + XLA's dot (the §3.2 FFN)
  rounding          shared bit-exact integer RTN / stochastic-rounding
                    codec + counter-hash noise (single source of truth)
  quantize          standalone per-tile quantizer

Each kernel ships with ops.py (jit'd wrapper + interpret fallback on CPU)
and ref.py (pure-jnp oracle used by the allclose test sweeps).
``ops.flash_attention`` wraps JAX's own Pallas TPU flash kernels (splash
attention, forward and backward) for self-attention (§3.1 protection).

``fp4_matmul.fused_qmm`` / ``ops.pallas_qmm`` form the role-parameterized
quantized-matmul family backing the training path's fwd, dgrad and wgrad
(``core.qlinear.pallas_qmatmul``), including in-kernel stochastic rounding
and the quantize-pass telemetry epilogue.
"""
from repro.kernels.ops import (flash_attention, fp4_matmul, pallas_qmm,
                               quantize_blockwise)

__all__ = ["flash_attention", "fp4_matmul", "pallas_qmm",
           "quantize_blockwise"]
