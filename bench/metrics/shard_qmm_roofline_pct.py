"""The sharded quantize pass's share of its roofline: ``qmm_roofline_pct``
over the traced Mosaic calls of every chip, each from its own per-chip
shapes.  Under a mesh every kernel-routed role's quantize pass runs
sharded (``kernels/ops.py``, scope ``shard_qmm``) and the attention is no
Pallas kernel, so the trace's Mosaic calls are the sharded passes."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from qmm_roofline_pct import read  # noqa: E402,F401
