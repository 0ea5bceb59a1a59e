"""Work arithmetic: model flops per token, and a kernel call's least time.

Model flops follow the PaLM MFU convention: forward matmul flops per token
(linears, causal scores and context, the LM head; 2 flops per
multiply-add), times 3 for training (forward, activation gradient, weight
gradient).  Rematerialized recompute is not counted.
"""
from __future__ import annotations

from typing import Dict, Sequence

__all__ = ["fwd_flops_per_token", "train_flops_per_step",
           "matmul_least_time_s", "kernel_least_time_s"]


def fwd_flops_per_token(m: Dict) -> float:
    """Forward matmul flops per token of a dense decoder LM.

    ``m`` holds the configuration's sizes: ``n_layers``, ``d_model``,
    ``n_heads``, ``n_kv_heads``, ``head_dim``, ``d_ff``, ``vocab_size``,
    ``activation`` and the sequence length ``seq_len``.
    """
    dm, hd = m["d_model"], m["head_dim"]
    q_out = m["n_heads"] * hd
    kv_out = 2 * m["n_kv_heads"] * hd
    attn_linear = 2 * dm * (q_out + kv_out) + 2 * q_out * dm
    # scores QK^T and context PV; causal, so half the keys on average
    attn_sdpa = 2 * 2 * m["n_heads"] * hd * (m["seq_len"] / 2)
    n_ff = 3 if m["activation"] == "swiglu" else 2
    ffn = n_ff * 2 * dm * m["d_ff"]
    head = 2 * dm * m["vocab_size"]
    return m["n_layers"] * (attn_linear + attn_sdpa + ffn) + head


def train_flops_per_step(m: Dict, tokens_per_step: int) -> float:
    return 3.0 * fwd_flops_per_token(m) * tokens_per_step


def matmul_least_time_s(mkn: Sequence[int], nbytes: float,
                        peaks: Dict[str, float]) -> float:
    """Least time of one matmul call: the larger of its operations at the
    chip's fastest matmul rate (int8: FP4 E2M1 x FP4 E2M1 products are
    exact in int8, so no change of the kernel's arithmetic can beat it)
    and its operand plus output bytes at the HBM rate."""
    m, k, n = mkn
    return max(2.0 * m * k * n / peaks["int8_ops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def kernel_least_time_s(call: Dict, peaks: Dict[str, float]) -> float:
    """Least time of one kernel call described by its shapes.

    ``call`` has ``operands`` and ``outputs``: lists of ``(bytes per
    element, dims)`` as the trace gives them.  A kernel fused with the
    slicing of a stacked (layer-major) array shows that array whole; only
    its trailing two dims are the call's.  A call whose output and two
    operands form ``(M, K) x (K, N) -> (M, N)``, either side stored
    transposed, is a matmul: its bytes are those two operands and the
    output.  Any other call (a quantize pass) does no matmul work and
    reads its largest operand and writes its output.
    """
    mkn = matmul_dims(call)
    out = _mats(call["outputs"])[0]
    if mkn is None:
        big = max(_mats(call["operands"]), key=_nbytes)
        return (_nbytes(big) + _nbytes(out)) / peaks["hbm_bytes_per_s"]
    a, b = mkn[1]
    nbytes = _nbytes(a) + _nbytes(b) + _nbytes(out)
    return matmul_least_time_s(mkn[0], nbytes, peaks)


def matmul_dims(call: Dict):
    """``((M, K, N), (a, b))`` of a call that computes a matmul, with the
    two operands ``(bytes, dims)`` it reads, or None."""
    outs = _mats(call["outputs"])
    mats = [x for x in _mats(call["operands"]) if _prod(x[1]) > 1]
    if not outs:
        return None
    m, n = outs[0][1]
    for i, (_, a) in enumerate(mats):
        ka = a[1] if a[0] == m else (a[0] if a[1] == m else None)
        if ka is None:
            continue
        for j, (_, b) in enumerate(mats):
            if j == i:
                continue
            kb = b[0] if b[1] == n else (b[1] if b[0] == n else None)
            if kb is not None and kb == ka:
                return (m, ka, n), (mats[i], mats[j])
    return None


def _mats(shapes):
    """Each shape cut to its trailing two dims (scalars and vectors as
    ``(1, n)``)."""
    out = []
    for nb, dims in shapes:
        dims = tuple(dims)
        if len(dims) < 2:
            dims = (1,) * (2 - len(dims)) + dims
        out.append((nb, dims[-2:]))
    return out


def _nbytes(shape) -> float:
    nb, dims = shape
    return nb * _prod(dims)


def _prod(shape) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out
