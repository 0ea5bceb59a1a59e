"""Measured-performance profiling: spans, scopes, counters, step timing.

The paper's efficiency claims are theoretical (speed factors derived from
bit-widths); this module produces the *measured* side of that argument —
host spans and graph scopes a profiler trace can attribute time to, a
process-wide compile count, wall-clock step-time percentiles, tokens/sec
and MFU.

Pieces:

  * **Host spans** — :func:`phase_span` wraps a *host-side* region in a
    ``jax.profiler.TraceAnnotation`` (a named slice in a captured trace).
    ``Trainer.train`` writes per step, each with the stat ``step_num``:
    ``data`` (the batch to the device), ``step`` (the measured step)
    holding ``dispatch`` (the call of the jitted step until it returns)
    and ``wait`` (the ``block_until_ready`` on its loss), then ``host``
    (metric reads and bookkeeping).  Each history row carries the same
    boundaries as counters: ``t_host``, ``t_data``, ``t_dispatch``,
    ``t_wait`` (seconds) and ``compiles`` (``Trainer`` defines them).
    The serving runtime writes ``decode_prefill``, ``decode_insert`` and
    ``decode_generate``.
  * **Graph scopes** — :func:`graph_span` is the trace-time counterpart
    (``jax.named_scope``) for jitted code: ops traced under it carry the
    name in their HLO ``op_name`` metadata, through ``jvp``/``transpose``
    and rematerialization.  The step graph's layer scopes are ``embed``,
    ``attn``, ``mlp``, ``norm``, ``head`` (models) and ``optim`` (clipping
    and the optimizer update, ``train_step``); ``collective`` marks the
    fp8 gradient reduction (``analysis.qlint``'s comms audit reads it) and
    ``qrole_*``/``qdq_*`` mark quantized matmul roles (``analysis.qlint``).
  * **Compile count** — :func:`compile_count`: backend compilations in
    this process (persistent-cache hits included), from one
    ``jax.monitoring`` listener registered on first use.
  * **StepTimer** — rolling step-time statistics with correct device-sync
    discipline: callers time ``fn(...)`` to the ``block_until_ready`` of
    its outputs (``time_call`` does this for you), the first ``warmup``
    records are excluded (compile + autotune), and :meth:`summary` reports
    p50/p95/p99/mean over a bounded rolling window plus throughput
    (tokens/sec) and MFU when given the model's flop count.
  * **Flops/MFU helpers** — :func:`train_step_flops` turns
    ``core.cost_model.ModelDims`` into a per-step training-flop count
    (fwd + dgrad + wgrad = 3x forward matmul flops);
    :func:`device_peak_flops` provides the per-chip peak-flops
    denominator (known TPU generations or the ``REPRO_PEAK_FLOPS`` env
    override; any other device raises).  MFU is a device metric: callers
    on the CPU leave it out.

Capturing a real trace around the annotated regions:

    with jax.profiler.trace("/tmp/trace"):   # or profiler server + xprof
        trainer.train(num_steps=20)

then open the trace in TensorBoard/xprof: the host spans appear by name
on the host's thread, and each device op's HLO metadata names its scopes.
"""
from __future__ import annotations

import collections
import contextlib
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax

__all__ = ["phase_span", "graph_span", "compile_count", "percentiles",
           "StepTimer", "train_step_flops", "device_peak_flops"]


@contextlib.contextmanager
def phase_span(name: str, **meta):
    """Host-side span: annotate a region of host code (data loading, the
    dispatch+sync of one step, controller/writer work) so it shows as a
    named slice in a ``jax.profiler`` trace; ``meta`` (``step_num=...``)
    becomes the slice's stats and leaves its name as it is.  No-op
    overhead when no trace is being captured (~sub-microsecond), so it is
    always on."""
    with jax.profiler.TraceAnnotation(name, **meta):
        yield


def graph_span(name: str):
    """Trace-time span for *jitted* code: a ``jax.named_scope`` context.
    Ops traced under it carry ``name`` in their HLO ``op_name`` metadata,
    which maps device time to the layer that caused it.  Pure metadata —
    the compiled computation is unchanged."""
    return jax.named_scope(name)


class _CompileCount:
    """Backend compilations seen by one process-wide ``jax.monitoring``
    listener (JAX keeps listeners for the life of the process, so one is
    registered, on first use, however many trainers read it)."""

    def __init__(self):
        self.n = 0
        self.listening = False

    def on_event(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.n += 1

    def read(self) -> int:
        if not self.listening:
            jax.monitoring.register_event_duration_secs_listener(
                self.on_event)
            self.listening = True
        return self.n


_COMPILES = _CompileCount()


def compile_count() -> int:
    """Backend compilations in this process so far: every executable JAX
    builds, persistent-cache hits included.  Counting starts at the first
    call; differences between two calls count what happened in between."""
    return _COMPILES.read()


def percentiles(xs: Sequence[float],
                qs: Sequence[float] = (50.0, 95.0, 99.0)) -> Dict[str, float]:
    """Nearest-rank percentiles of ``xs`` as ``{"p50": ..., ...}``.

    Deterministic (no interpolation) and dependency-free so report code and
    tests agree exactly; empty input yields NaNs.
    """
    out: Dict[str, float] = {}
    s = sorted(xs)
    for q in qs:
        key = f"p{int(q) if float(q).is_integer() else q}"
        if not s:
            out[key] = float("nan")
            continue
        rank = max(1, -(-len(s) * q // 100))  # ceil(n*q/100), 1-based
        out[key] = float(s[int(rank) - 1])
    return out


class StepTimer:
    """Rolling wall-clock step statistics with warmup exclusion.

    Record either with :meth:`record` (caller already blocked on device
    outputs — the trainer's path) or :meth:`time_call`, which runs
    ``fn(*args)``, blocks via ``jax.block_until_ready`` on the result (the
    device-sync discipline: without it you time the dispatch, not the
    step), records, and returns the result.

    The first ``warmup`` records are counted (``n_total``) but excluded
    from statistics — they measure compilation, not steady state.  Kept
    times live in a bounded rolling window (``window`` entries) so a long
    run's summary reflects recent behavior and memory stays constant.

    Post-warmup **recompile spikes** are excluded too: a shape change (or a
    controller plan edit) can trigger a recompilation long after warmup,
    and one multi-second compile landing in the window drags p95/p99 orders
    of magnitude away from steady state (the old baseline showed p95=3.27s
    against p50=103ms from exactly this).  A record more than
    ``spike_factor`` x the current window median is counted and reported
    separately (``spikes`` / ``spike_max_ms`` in :meth:`summary`) instead
    of polluting the percentiles.  The first 3 post-warmup records are
    always kept (no median to judge against yet); ``spike_factor=None``
    disables the filter.
    """

    def __init__(self, warmup: int = 2, window: int = 1024,
                 spike_factor: Optional[float] = 20.0):
        self.warmup = warmup
        self.window = window
        self.spike_factor = spike_factor
        self.n_total = 0
        self.n_spikes = 0
        self._times: collections.deque = collections.deque(maxlen=window)
        self._spike_times: collections.deque = collections.deque(maxlen=16)

    def record(self, seconds: float) -> None:
        self.n_total += 1
        if self.n_total <= self.warmup:
            return
        t = float(seconds)
        if self.spike_factor is not None and len(self._times) >= 3:
            med = percentiles(self._times, qs=(50.0,))["p50"]
            if t > self.spike_factor * med:
                self.n_spikes += 1
                self._spike_times.append(t)
                return
        self._times.append(t)

    def time_call(self, fn: Callable, *args: Any, **kw: Any) -> Any:
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        self.record(time.perf_counter() - t0)
        return out

    @property
    def times(self) -> List[float]:
        """Post-warmup step times (seconds), oldest first."""
        return list(self._times)

    def summary(self, tokens_per_step: Optional[float] = None,
                flops_per_step: Optional[float] = None,
                peak_flops: Optional[float] = None) -> Dict[str, float]:
        """Step-time stats: ``steps`` (post-warmup count), ``warmup``,
        ``spikes`` (excluded recompile-spike count, plus ``spike_max_ms``
        when any), ``mean_ms``/``p50_ms``/``p95_ms``/``p99_ms``, and — when
        the caller supplies the model numbers — ``tokens_per_sec``,
        ``flops_per_sec`` and ``mfu``, all at the p50 step time (median:
        robust to straggler steps).  ``peak_flops`` is the peak of every
        chip the step ran on together (chips x per-chip peak); without it
        there is no ``mfu``.
        """
        ts = self.times
        out: Dict[str, float] = {"steps": len(ts), "warmup": self.warmup,
                                 "spikes": self.n_spikes}
        if self.n_spikes:
            out["spike_max_ms"] = max(self._spike_times) * 1e3
        if not ts:
            return out
        pct = percentiles(ts)
        out["mean_ms"] = sum(ts) / len(ts) * 1e3
        for k, v in pct.items():
            out[f"{k}_ms"] = v * 1e3
        p50 = pct["p50"]
        if tokens_per_step is not None and p50 > 0:
            out["tokens_per_sec"] = tokens_per_step / p50
        if flops_per_step is not None and p50 > 0:
            out["flops_per_sec"] = flops_per_step / p50
            if peak_flops is not None:
                out["mfu"] = flops_per_step / p50 / peak_flops
        return out


# ---------------------------------------------------------------------------
# Flops / MFU
# ---------------------------------------------------------------------------

def train_step_flops(dims, tokens_per_step: float) -> float:
    """Training matmul flops of one step from ``cost_model.ModelDims``.

    ``dims.total_fwd_flops`` is forward matmul flops per token (already
    2x mult+add); training runs fwd + dgrad + wgrad = 3x forward.  This is
    the model-flops convention of the PaLM MFU definition — rematerialized
    recompute is deliberately NOT counted, so MFU measures useful work.
    """
    return 3.0 * dims.total_fwd_flops * tokens_per_step


# Peak dense matmul throughput (flops/sec, bf16) of one chip by TPU device
# kind, for the MFU denominator (Google Cloud TPU documentation, per
# generation).
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def device_peak_flops(device=None) -> float:
    """Peak flops/sec of one chip of ``device``'s kind (default: the
    first local device).

    Resolution order: ``REPRO_PEAK_FLOPS`` env var (authoritative — set it
    when your part's spec is known), then the known-TPU table.  Any other
    device raises: a peak it does not have would make MFU a made-up number.
    """
    env = os.environ.get("REPRO_PEAK_FLOPS")
    if env:
        return float(env)
    device = device or jax.devices()[0]
    kind = device.device_kind
    for name, peak in _PEAK_FLOPS.items():
        if kind.lower().startswith(name.lower()):
            return peak
    raise ValueError(f"no peak flops known for device kind {kind!r} "
                     f"(platform {device.platform}); set REPRO_PEAK_FLOPS")
