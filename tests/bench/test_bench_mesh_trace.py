"""``bench/mesh_trace.py`` and the readers of ``collective_exposed_ms`` and
``shard_qmm_roofline_pct``: exposed collective time on hand-made
intervals and traces, and the readers on the stored v5e trace
``small_scoped.xplane.pb``."""
import math
import os
from types import SimpleNamespace as NS

import pytest

import _bench

run = _bench.module("run")
peaks = _bench.module("peaks")
mesh_trace = _bench.module("mesh_trace")
trace = run.load_module("trace")

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def read(name, ctx):
    return run.load_module("metrics/" + name).read(ctx)


@pytest.mark.parametrize("coll,other,want", [
    ([(0, 4)], [], 4.0),
    ([(0, 4)], [(1, 2)], 3.0),
    ([(0, 4), (3, 6)], [(1, 2), (5, 9)], 4.0),
    ([(2, 3)], [(0, 10)], 0.0),
    ([], [(0, 1)], 0.0),
])
def test_exposed_is_collective_time_outside_other_ops(coll, other, want):
    assert mesh_trace.exposed_s(coll, other) == pytest.approx(want)


def test_collective_names():
    for base in ("all-gather", "all-reduce-scatter", "reduce-scatter",
                 "all-gather-start", "async-collective-done",
                 "collective-permute-done", "all-to-all"):
        assert mesh_trace.is_collective(base), base
    for base in ("fusion", "_fused_qmm", "convolution", "copy-start"):
        assert not mesh_trace.is_collective(base), base


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _pd(ops_by_chip):
    planes = [NS(name="/host:CPU", lines=[NS(name="spans", events=[
        _ev("step", 0, 1000)])])]
    for i, ops in enumerate(ops_by_chip):
        planes.append(NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Ops", events=[_ev(*o) for o in ops])]))
    return NS(planes=planes)


KERNEL = ('%_fused_qmm.1 = bf16[256,128]{1,0} custom-call(f32[1]{0} %q, '
          'bf16[256,128]{1,0} %x), custom_call_target="tpu_custom_call"')


def test_reduction_on_two_chips():
    gather = "%all-gather.7 = bf16[256,128]{1,0} all-gather(bf16[64,128]{1,0} %w)"
    pmax = "%all-reduce.2 = f32[256]{0} all-reduce(f32[256]{0} %m)"
    loop = "%while.9 = (s32[]) while((s32[]) %t), body=%b"
    chips = [[(loop, 0, 900), (gather, 0, 100), (KERNEL, 100, 50),
              (pmax, 150, 10), ("%fusion.3 = bf16[8]{0} fusion(%z)", 120, 100)],
             [(gather, 0, 200), (KERNEL, 200, 100)]]
    pd = _pd(chips)
    red = mesh_trace.reduce_profile(pd, 2)
    d0, d1 = red["devices"]
    assert d0["collective_s"] == pytest.approx(110e-9)
    assert d0["exposed_s"] == pytest.approx(100e-9)   # pmax under fusion.3
    assert d1["exposed_s"] == pytest.approx(200e-9)
    ctx = {"mesh": red, "trace": trace.reduce_profile(pd, 2), "steps": 1,
           "peaks": peaks.peaks_for("TPU v5e")}
    assert read("collective_exposed_ms", ctx) == pytest.approx(1.5e-4)
    share = read("shard_qmm_roofline_pct", ctx)
    # each call reads 256 x 128 bf16 and writes as much: 131072 bytes
    assert share == pytest.approx(100 * 2 * 131072 / 819e9 / 150e-9)


def test_readers_read_nothing_without_a_trace():
    assert read("collective_exposed_ms", {"steps": 3}) is None
    assert read("shard_qmm_roofline_pct", {"steps": 3, "trace": {
        "devices": [{"kernels": []}]}}) is None
    assert read("collective_exposed_ms", {"mesh": {"devices": []},
                                          "steps": 3}) is None


def test_readers_on_the_scoped_v5e_trace():
    """A real one-chip v5e trace: no collective, so no exposed time; its
    Mosaic calls give a roofline share in (0, 100], the same as
    ``qmm_roofline_pct``'s."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(DATA, "small_scoped.xplane.pb"))
    ctx = {"mesh": mesh_trace.reduce_profile(pd, 1),
           "trace": trace.reduce_profile(pd, 1), "steps": 2,
           "peaks": peaks.peaks_for("TPU v5e")}
    assert ctx["trace"]["devices"][0]["kernels"]
    assert read("collective_exposed_ms", ctx) is None
    share = read("shard_qmm_roofline_pct", ctx)
    assert share is not None and 0 < share <= 100 and math.isfinite(share)
    assert share == read("qmm_roofline_pct", ctx)
