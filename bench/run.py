#!/usr/bin/env python3
"""The benchmark: one training cell on the chip, end to end.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Builds the cell's ``Trainer`` through the program's normal path
(``build_model``, ``TrainConfig``, ``Trainer``) at the cell's sizes, from
weights made on the device from ``--seed``, feeds it the benchmark's own
seeded tokens, and:

1. set-up: makes the parameters and the optimizer state, compiles the
   cell's stage-1 step graph (served from JAX's persistent compilation
   cache after the first run in a checkout) and drives the first three
   steps through ``Trainer.train``, which warm it up and are the steps the
   reference checks;
2. the window: ``k`` steps, each one ``Trainer.train(num_steps=1)`` call
   (so that the chip holds two states, the step's input and output, and
   not a third the caller would keep), with ``k`` picked from the warm
   step time to fill ``--seconds`` (``--trace 0``), or a few steps under
   the profiler (``--trace 1``);
3. reads the peak device memory, frees the program's state, runs the
   plain reference over the same first three steps and compares.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and last ``checked``, each compared number
beside its limit.  Off a TPU, or with fewer chips than the cell asks for,
it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import cells  # noqa: E402

sys.path.insert(0, os.path.join(cells.ROOT, "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def tpu_devices(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: no TPU (JAX found {devs[0].platform}); nothing was run")
        sys.exit(2)
    if len(devs) < count:
        log(f"bench: the cell needs {count} TPU chips, JAX found "
            f"{len(devs)}; nothing was run")
        sys.exit(2)
    return devs[:count]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        cells.ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations while ``counting`` is on."""

    def __init__(self):
        import jax
        self.counting = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.counting and event.endswith("backend_compile_duration"):
            self.n += 1


def trace_options():
    """The profiler without its Python tracer, and with the host's spans
    at the level the loop's ``TraceAnnotation`` spans are written at."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def load_module(rel: str):
    """A module of the benchmark by its path under ``bench/``, without
    the ``.py``."""
    path = os.path.join(BENCH_DIR, rel + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + rel.replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             devices, *, t_start: float = T_START, plant=None) -> Dict:
    """One run of ``cell``; returns the result object.  ``plant`` (tests
    only) is called with the trainer before set-up, to break the timed
    path underneath and see the check catch it."""
    import jax
    import numpy as np

    import check
    import reference
    import weights
    from program import Program

    w = cell.workload
    prog = Program(cell, plant=plant)
    counter = CompileCounter()
    readings, batches, warm_dt = prog.first_steps(seed)

    # -- the window --
    tps = cell.tokens_per_step
    n_steps = (max(1, int(w["trace_steps"])) if trace
               else max(2, math.ceil(seconds / warm_dt)))
    log(f"[bench] {cell.name}: seed {seed}, warm step {warm_dt * 1e3:.1f} "
        f"ms, window of {n_steps} steps of {tps} tokens")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    h0 = len(prog.trainer.history)
    counter.counting = True
    setup_s = time.time() - t_start
    if trace:
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options())
    t0 = time.perf_counter()
    prog.train(n_steps)
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    counter.counting = False
    losses = [r["loss"] for r in prog.trainer.history[h0:]]
    failed = int(sum(not np.isfinite(x) for x in losses))
    log("[bench] window step times (ms): " + " ".join(
        f"{r['dt'] * 1e3:.1f}" for r in prog.trainer.history[h0:]))
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    log(f"[bench] device {dev.platform} / {dev.device_kind} x "
        f"{len(devices)}; peak memory {peak} bytes")
    log(f"[bench] window {window_s:.6f} s, {len(losses)} steps, "
        f"{counter.n} compiles inside the window")

    # -- free the program's state, then the reference --
    prog.free()
    del prog
    gc.collect()
    breakdown = None
    if trace:
        metrics, device_extra, breakdown = read_trace(
            cell, trace_dir, devices, n_steps)
        device.update(device_extra)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {
            "train_tokens_per_s": {"value": n_steps * tps / window_s,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    jax.clear_caches()
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.readings(cell, seed, batches)
    log(f"[bench] reference took {time.perf_counter() - t_ref:.1f} s")
    numbers = check.gaps(readings, ref, weights.slice_names(
        weights.layout(cell.model)))
    correct, shown = check.verdict(numbers, w["limits"])
    log(f"[bench] losses program {readings['losses']} reference "
        f"{ref['losses']}")
    log(f"[bench] worst slices {numbers['worst']}; left out of "
        f"update_gap: {numbers['left_out']}")
    log("[bench] not compared: " + ", ".join(
        f"{k} {numbers[k]!r}" for k in check.NUMBERS if k not in shown))
    for k, v in shown.items():
        log(f"[check] {k} {v['value']!r} limit {v['limit']!r}")
    out = {"correct": correct, "attempted": len(losses), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checked"] = shown
    return out


def read_trace(cell: cells.Cell, trace_dir: str, devices, n_steps: int):
    """Per-layer metrics, device busy time and the breakdown from the
    profiler's trace of the window."""
    import flops
    import peaks

    # by path: the standard library has a module named ``trace`` too
    red = load_module("trace").reduce_dir(trace_dir, n_devices=len(devices))
    ctx = {
        "trace": red,
        "steps": n_steps,
        "chips": len(devices),
        "peaks": peaks.peaks_for(devices[0].device_kind),
        "flops_per_step": flops.train_flops_per_step(
            cell.model, cell.tokens_per_step),
        "tokens_per_step": cell.tokens_per_step,
    }
    metrics = {}
    for m in cells.per_layer_metrics(cell.name):
        value = load_module("metrics/" + m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
    return metrics, device, red["breakdown"]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    # before JAX starts its backend: libtpu would log under /tmp otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices = tpu_devices(cell.chips)
    enable_compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
