"""Step/phase profiler: percentiles, StepTimer warmup/window/MFU,
train-step flop accounting, span no-op safety, the trainer's step-phase
spans and row counters."""
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import TrainConfig, get_config
from repro.core.cost_model import ModelDims
from repro.data import SyntheticLM
from repro.models import build_model
from repro.telemetry.profiler import (StepTimer, compile_count,
                                      device_peak_flops, graph_span,
                                      percentiles, phase_span,
                                      train_step_flops)
from repro.train.trainer import Trainer

# the host spans Trainer.train writes each step
LOOP_SPANS = ("data", "step", "dispatch", "wait", "host")


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
    for name in LOOP_SPANS:
        with phase_span(name):
            pass  # always-on: must never raise outside a capture
        with phase_span(name, step_num=3):
            pass


def test_graph_span_pure_metadata_bit_identical():
    """named_scope must not change the compiled computation."""
    x = jnp.arange(8, dtype=jnp.float32)

    def plain(x):
        return jnp.sum(x * x)

    def spanned(x):
        with graph_span("attn"):
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
        with graph_span("mlp"):
            return jnp.sum(x ** 3)
    g = jax.grad(f)(jnp.full((3,), 2.0))
    assert g.tolist() == [12.0] * 3


# ---------------------------------------------------------------------------
# the trainer's step-phase spans and row counters
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_trainer():
    cfg = get_config("tiny")
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=40, global_batch=4,
                       seq_len=32, log_every=0)
    return Trainer(build_model(cfg), tcfg,
                   SyntheticLM(cfg.vocab_size, 32, 4, seed=0))


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = {k: [] for k in LOOP_SPANS}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append((ev.start_ns, ev.start_ns
                                         + ev.duration_ns,
                                         dict(list(ev.stats)).get(
                                             "step_num")))
    return {k: sorted(v) for k, v in out.items()}


def test_trainer_spans_nest_and_match_row_counters(tiny_trainer, tmp_path):
    """Under the profiler, each step's ``dispatch`` and ``wait`` lie inside
    its ``step`` span, every span carries the step's number, and the row's
    ``t_dispatch + t_wait`` is the ``step`` span's length to 0.5 ms."""
    tr = tiny_trainer
    state = tr.train(num_steps=1)           # compile outside the trace
    h0 = len(tr.history)
    with jax.profiler.trace(str(tmp_path)):
        state = tr.train(state, num_steps=3)
    rows = tr.history[h0:]
    spans = _host_spans(str(tmp_path))
    assert all(len(spans[k]) == 3 for k in LOOP_SPANS), {
        k: len(v) for k, v in spans.items()}
    for i, row in enumerate(rows):
        s0, s1, n = spans["step"][i]
        assert n == row["step"]
        for k in ("dispatch", "wait"):
            a, b, m = spans[k][i]
            assert s0 <= a <= b <= s1 and m == n
        assert spans["dispatch"][i][1] <= spans["wait"][i][0]
        assert (row["t_dispatch"] + row["t_wait"]) * 1e9 == pytest.approx(
            s1 - s0, abs=0.5e6)
        assert row["dt"] == pytest.approx(row["t_dispatch"] + row["t_wait"])


def test_row_counters_add_up_to_the_loop_wall_time(tiny_trainer):
    """``t_host`` runs from the previous step's sync to this step's start
    (so it holds what the caller did between ``train`` calls), and the
    four parts of consecutive rows add up to the wall time between."""
    tr = tiny_trainer
    state = tr.train(num_steps=1)
    h0 = len(tr.history)
    t0 = time.perf_counter()
    for _ in range(3):
        time.sleep(0.05)
        state = tr.train(state, num_steps=1)
    wall = time.perf_counter() - t0
    rows = tr.history[h0:]
    for row in rows:
        assert all(row[k] >= 0 for k in ("t_host", "t_data", "t_dispatch",
                                          "t_wait"))
        assert row["t_host"] >= 0.05
    parts = sum(r["t_host"] + r["t_data"] + r["t_dispatch"] + r["t_wait"]
                for r in rows)
    # they differ by the first step's lead-in before the clock started and
    # the last step's bookkeeping after its sync
    assert parts == pytest.approx(wall, abs=0.02)


def test_compiles_counts_the_first_step_only():
    cfg = get_config("tiny").replace(d_ff=96)   # a graph no test compiled
    tcfg = TrainConfig(recipe="bf16", total_steps=10, global_batch=4,
                       seq_len=32, log_every=0)
    tr = Trainer(build_model(cfg), tcfg,
                 SyntheticLM(cfg.vocab_size, 32, 4, seed=1))
    tr.train(num_steps=2)
    assert tr.history[0]["compiles"] >= 1
    assert tr.history[1]["compiles"] == 0


def test_compile_count_is_one_process_wide_listener():
    before = compile_count()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0))
    assert compile_count() >= before + 1
    n = compile_count()
    jax.jit(lambda x: x * 3 + 1)   # no call, no compile
    assert compile_count() == n


def test_layer_scopes_are_metadata_only(monkeypatch):
    """The step graph compiled with its layer scopes is the one compiled
    without them, but for the metadata: same ops, same fusions."""
    from jax._src import source_info_util as siu

    from repro.core.recipe import RECIPES, as_plan
    from repro.train.train_step import make_optimizer, make_train_step

    cfg = get_config("tiny")
    model = build_model(cfg)
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=10, global_batch=2,
                       seq_len=32)
    params = model.abstract_params(jnp.float32)
    sds = jax.ShapeDtypeStruct
    args = (params, jax.eval_shape(make_optimizer(model, tcfg).init, params),
            sds((), jnp.float32),
            {"tokens": sds((2, 32), jnp.int32),
             "targets": sds((2, 32), jnp.int32)},
            sds((), jnp.int32), sds((), jnp.float32))

    def compiled_text():
        step = make_train_step(model, tcfg, as_plan(RECIPES["paper_fp4"],
                                                    cfg.n_layers),
                               donate=False)
        # the computations, without the source-location tables before them
        text = step.lower(*args).compile().as_text()
        return text[re.search(r"^(ENTRY )?%", text, re.M).start():]

    def ops(text):
        return re.sub(r", metadata=\{[^}]*\}", "", text)

    scoped = compiled_text()
    enter = siu.ExtendNameStackContextManager.__enter__

    def skip_layer_scopes(self):
        if self.name in ("embed", "attn", "mlp", "norm", "head", "optim"):
            self.prev = siu._source_info_context.context
            return self.prev.name_stack
        return enter(self)

    monkeypatch.setattr(siu.ExtendNameStackContextManager, "__enter__",
                        skip_layer_scopes)
    plain = compiled_text()
    assert "/attn/" in scoped and "/attn/" not in plain
    assert ops(plain) == ops(scoped)
