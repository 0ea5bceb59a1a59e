"""Work and peak arithmetic of the benchmark."""
import itertools

import pytest

import _bench

flops = _bench.module("flops")
peaks = _bench.module("peaks")

V5E = peaks.peaks_for("TPU v5 lite")
GPT2 = dict(d_model=768, n_heads=12, n_kv_heads=12, head_dim=64, d_ff=3072,
            vocab_size=50257, activation="gelu", seq_len=1024)


def test_gpt2_125m_step_flops_match_the_smoke_run():
    # the bring-up's estimate: about 12 TFLOP a 16 x 1024 step
    f = flops.train_flops_per_step(dict(GPT2, n_layers=12), 16 * 1024)
    assert f == pytest.approx(12e12, rel=0.1)


def test_gpt2_774m_24l_step_flops():
    m = dict(GPT2, n_layers=24, d_model=1280, n_heads=20, n_kv_heads=20,
             d_ff=5120)
    assert flops.train_flops_per_step(m, 16 * 1024) == pytest.approx(
        55.8e12, rel=0.005)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


_BYTES = {"fp4": 0.5, "fp8": 1, "bf16": 2, "f32": 4}


@pytest.mark.parametrize("fa,fb", list(itertools.product(_BYTES, repeat=2)))
@pytest.mark.parametrize("mkn", [(128, 128, 128), (16384, 1280, 5120),
                                 (4096, 24576, 6144), (8, 4096, 8)])
def test_least_time_is_never_beaten(fa, fb, mkn):
    """For any format pair the least time is at least the work at the
    chip's fastest rate and the operands' bytes at the fastest bandwidth,
    so a measured share can pass 100% only if the work is miscounted."""
    m, k, n = mkn
    call = {"operands": [(_BYTES[fa], (m, k)), (_BYTES[fb], (k, n))],
            "outputs": [(2, (m, n))]}
    t = flops.kernel_least_time_s(call, V5E)
    fastest = max(V5E["bf16_flops_per_s"], V5E["int8_ops_per_s"])
    assert t >= 2 * m * k * n / fastest
    nbytes = (_BYTES[fa] * m * k + _BYTES[fb] * k * n + 2 * m * n)
    assert t >= nbytes / V5E["hbm_bytes_per_s"]


def test_matmul_dims_reads_transposed_layouts():
    out = [(2, (256, 512))]
    for a, b in [((256, 128), (128, 512)), ((128, 256), (128, 512)),
                 ((256, 128), (512, 128)), ((128, 256), (512, 128))]:
        call = {"operands": [(4, (1,)), (2, a), (2, b)], "outputs": out}
        assert flops.matmul_dims(call)[0] == (256, 128, 512)
    quant = {"operands": [(4, (1,)), (2, (256, 128))],
             "outputs": [(2, (256, 128))]}
    assert flops.matmul_dims(quant) is None


def test_kernel_fused_with_a_layer_slice():
    """A kernel fused with the slicing of stacked weights shows the
    stacked array; the call itself is one layer's matmul."""
    call = {"operands": [(2, (24, 512, 256)), (4, ()), (4, (1,)),
                         (2, (512, 512)), (2, (512, 256))],
            "outputs": [(2, (24, 512, 256))]}
    assert flops.matmul_dims(call)[0] == (512, 512, 256)
    t = flops.kernel_least_time_s(call, V5E)
    nbytes = 2 * (512 * 512 + 512 * 256 + 512 * 256)
    assert t == max(2 * 512 * 512 * 256 / V5E["int8_ops_per_s"],
                    nbytes / V5E["hbm_bytes_per_s"])
