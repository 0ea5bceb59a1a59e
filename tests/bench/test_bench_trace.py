"""The trace reduction on a small trace recorded on a v5e chip
(``bench/record_trace.py``: two fused-kernel ``paper_fp4`` steps of a
two-layer model of width 256, batch 2 x 256).  To keep the file small,
the planes and lines the reduction does not read were dropped from the
recording (the HLO protos of ``/host:metadata`` and the runtime's host
threads); what is left is as recorded."""
import os

import pytest

import _bench

flops = _bench.module("flops")
peaks = _bench.module("peaks")
run = _bench.module("run")

TR = run.load_module("trace")
SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return TR.reduce_profile(ProfileData.from_file(SMALL), n_devices=1)


def test_fixture_is_small():
    assert os.path.getsize(SMALL) < 1 << 20


def test_union_length():
    assert TR.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4.0
    assert TR.union_length([]) == 0.0


def test_window_busy_and_host_spans(reduced):
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["host_n"]["step"] == 2
    assert reduced["host_n"]["data"] == reduced["host_n"]["host"] == 2
    assert reduced["host_s"]["step"] <= reduced["window_s"]


def test_kernels_and_their_shapes(reduced):
    calls = reduced["devices"][0]["kernels"]
    assert calls, "no Mosaic kernel found in the trace"
    assert all(c["operands"] for c in calls)
    mkn = [flops.matmul_dims(c) for c in calls]
    assert any(d is not None for d in mkn)
    for d in mkn:
        assert d is None or all(x % 128 == 0 for x in d[0])


def test_roofline_share_stays_under_100(reduced):
    v5e = peaks.peaks_for("TPU v5 lite")
    ctx = {"trace": reduced, "steps": 2, "chips": 1, "peaks": v5e,
           "flops_per_step": 1.0, "tokens_per_step": 512}
    share = run.load_module("metrics/qmm_roofline_pct").read(ctx)
    assert share is not None and 0 < share <= 100


def test_breakdown_is_bounded(reduced):
    b = reduced["breakdown"]
    assert 0 < len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"])
