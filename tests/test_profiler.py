"""Step/phase profiler: percentiles, StepTimer warmup/window/MFU,
train-step flop accounting, span no-op safety."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.core.cost_model import ModelDims
from repro.telemetry.profiler import (PHASES, StepTimer, device_peak_flops,
                                      graph_span, percentiles, phase_span,
                                      train_step_flops)


# ---------------------------------------------------------------------------
# percentiles (nearest-rank)
# ---------------------------------------------------------------------------

def test_percentiles_nearest_rank_exact():
    xs = list(range(1, 101))  # 1..100: pN is exactly N (nearest rank)
    p = percentiles(xs)
    assert p == {"p50": 50.0, "p95": 95.0, "p99": 99.0}
    # order-independent, no interpolation ever (values come FROM the data)
    p = percentiles([3.0, 1.0, 2.0])
    assert p["p50"] == 2.0 and p["p95"] == 3.0 and p["p99"] == 3.0
    assert percentiles([7.0])["p50"] == 7.0


def test_percentiles_empty_is_nan():
    p = percentiles([])
    assert all(v != v for v in p.values())  # NaN
    assert set(p) == {"p50", "p95", "p99"}


def test_percentiles_custom_qs():
    p = percentiles(list(range(1, 11)), qs=(10.0, 90.0))
    assert p == {"p10": 1.0, "p90": 9.0}


# ---------------------------------------------------------------------------
# StepTimer
# ---------------------------------------------------------------------------

def test_step_timer_warmup_excluded_and_window_bounded():
    t = StepTimer(warmup=2, window=4)
    for dt in (99.0, 88.0):      # compile-time outliers: counted, excluded
        t.record(dt)
    assert t.n_total == 2 and t.times == []
    assert t.summary() == {"steps": 0, "warmup": 2, "spikes": 0}
    for dt in (1.0, 2.0, 3.0, 4.0, 5.0):   # 5 post-warmup, window keeps 4
        t.record(dt)
    assert t.times == [2.0, 3.0, 4.0, 5.0]
    s = t.summary()
    assert s["steps"] == 4
    assert s["p50_ms"] == 3.0e3 and s["p99_ms"] == 5.0e3
    assert s["mean_ms"] == pytest.approx(3.5e3)


def test_step_timer_recompile_spike_excluded():
    """A post-warmup recompilation (e.g. a controller plan edit) must not
    drag the percentiles: records > spike_factor x window median are
    counted/reported separately, not kept."""
    t = StepTimer(warmup=0, spike_factor=20.0)
    for dt in (0.10, 0.11, 0.09, 0.10):
        t.record(dt)
    t.record(3.27)               # the old baseline's p95=3.27s pathology
    assert t.n_spikes == 1
    assert 3.27 not in t.times and len(t.times) == 4
    s = t.summary()
    assert s["spikes"] == 1
    assert s["spike_max_ms"] == pytest.approx(3270.0)
    assert s["p95_ms"] == pytest.approx(110.0)  # spike-free percentiles
    t.record(0.10)               # normal steps keep flowing afterwards
    assert len(t.times) == 5 and t.n_spikes == 1


def test_step_timer_spike_filter_needs_a_median():
    """The first 3 post-warmup records are always kept — there is no
    median to judge against yet (a slow-but-real first step must not be
    silently dropped)."""
    t = StepTimer(warmup=0, spike_factor=20.0)
    for dt in (5.0, 0.1, 0.1):
        t.record(dt)
    assert t.times == [5.0, 0.1, 0.1] and t.n_spikes == 0
    t.record(5.0)                # now 5.0 > 20 x median(=0.1): spike
    assert t.n_spikes == 1 and len(t.times) == 3


def test_step_timer_spike_filter_disabled():
    t = StepTimer(warmup=0, spike_factor=None)
    for dt in (0.1, 0.1, 0.1, 99.0):
        t.record(dt)
    assert t.n_spikes == 0 and 99.0 in t.times
    assert "spike_max_ms" not in t.summary()


def test_step_timer_summary_throughput_and_mfu():
    t = StepTimer(warmup=0)
    for _ in range(5):
        t.record(0.5)   # p50 = 0.5s
    s = t.summary(tokens_per_step=1024, flops_per_step=2e9, peak_flops=1e10)
    assert s["tokens_per_sec"] == pytest.approx(2048.0)
    assert s["flops_per_sec"] == pytest.approx(4e9)
    assert s["mfu"] == pytest.approx(0.4)


def test_step_timer_time_call_blocks_and_returns():
    t = StepTimer(warmup=0)
    out = t.time_call(lambda x: x * 2, jnp.ones((4,)))
    assert out.tolist() == [2.0] * 4
    assert len(t.times) == 1 and t.times[0] > 0


# ---------------------------------------------------------------------------
# flops / MFU helpers
# ---------------------------------------------------------------------------

def test_train_step_flops_is_3x_forward():
    dims = ModelDims.from_config(get_config("tiny"), seq_len=64)
    tokens = 8 * 64
    assert train_step_flops(dims, tokens) == 3.0 * dims.total_fwd_flops * tokens


def test_device_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PEAK_FLOPS", "1.23e14")
    assert device_peak_flops() == 1.23e14
    monkeypatch.delenv("REPRO_PEAK_FLOPS")
    # the CPU has no entry in the peak table: no made-up denominator
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(ValueError, match="no peak flops"):
        device_peak_flops()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_phase_span_is_safe_noop_wrapper():
    for name in PHASES:
        with phase_span(name):
            pass  # always-on: must never raise outside a capture


def test_graph_span_pure_metadata_bit_identical():
    """named_scope must not change the compiled computation."""
    x = jnp.arange(8, dtype=jnp.float32)

    def plain(x):
        return jnp.sum(x * x)

    def spanned(x):
        with graph_span("fwd"):
            y = x * x
        with graph_span("collective"):
            return jnp.sum(y)

    a = jax.jit(plain)(x)
    b = jax.jit(spanned)(x)
    assert float(a) == float(b)
    # identical lowered program shape (metadata-only difference; the name
    # itself only survives into debug/xprof metadata, not the default text)
    assert jax.jit(spanned).lower(x).as_text() is not None


def test_graph_span_differentiable():
    def f(x):
        with graph_span("quantize"):
            return jnp.sum(x ** 3)
    g = jax.grad(f)(jnp.full((3,), 2.0))
    assert g.tolist() == [12.0] * 3
