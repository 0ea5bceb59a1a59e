"""Measured step/phase profile -> ``BENCH_step.json`` (``check_bench --step``).

Produces the wall-clock evidence the paper's theoretical cost tables lack:

  * a short profiled Trainer smoke run per recipe (paper_fp4 vs bf16) —
    the trainer's ``StepTimer`` supplies warmup-excluded p50/p95/p99 step
    times, tokens/sec and, on a TPU, MFU (``step/train_step_*`` entries,
    percentile fields in the record);
  * a per-phase breakdown (``step/phase_*``).  Phases inside ONE jitted
    step cannot be separately host-timed, so the breakdown uses jitted-
    callable deltas at the same shape: fwd = t(loss); bwd = t(grad) - fwd;
    optim = t(step) - t(grad); quantize = t(fwd_fp4) - t(fwd_bf16) (the
    QDQ work the FP4 forward adds over the plain one).  For intra-step
    attribution beyond this, capture a real trace — the train loop and
    step graph carry ``phase_span``/``graph_span`` annotations (see the
    README's observability section);
  * the telemetry tap overhead (instrumented vs plain step graph) and the
    async JSONL writer's drop counter from the smoke run.

All timings are CPU/interpret-mode and trend-only; ``check_bench --step``
therefore gates on the fp4/bf16 *ratio* (host speed cancels), mirroring
the kernel gate's normalize-then-compare discipline.

Usage:
    python -m benchmarks.profile_report --json BENCH_step.json
"""
from __future__ import annotations

import argparse
import os
import tempfile

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit, write_json
from repro.configs.base import TrainConfig, get_config
from repro.core.recipe import RECIPES, as_plan
from repro.data import SyntheticLM
from repro.models import build_model
from repro.train.train_step import make_optimizer, make_train_step
from repro.train.trainer import Trainer

SEQ, BATCH = 64, 8


def _smoke_run(model, recipe: str, steps: int) -> dict:
    """Profiled Trainer run: StepTimer percentiles + MFU + writer drops."""
    with tempfile.TemporaryDirectory() as td:
        tcfg = TrainConfig(recipe=recipe, total_steps=steps,
                           global_batch=BATCH, seq_len=SEQ, log_every=0,
                           telemetry_jsonl=os.path.join(td, "tel.jsonl"))
        pipe = SyntheticLM(model.cfg.vocab_size, SEQ, BATCH, seed=0)
        tr = Trainer(model, tcfg, pipe)
        tr.train()
        summ = tr.step_time_summary()
        summ["writer_dropped"] = tr.writer.dropped
        tr.writer.close()
    return summ


def _phase_breakdown(model, steps_hint: int = 10) -> None:
    """Jitted-callable phase deltas at the smoke shape (fp4 recipe)."""
    plan_fp4 = as_plan(RECIPES["paper_fp4"], model.cfg.n_layers)
    plan_bf16 = as_plan(RECIPES["bf16"], model.cfg.n_layers)
    pipe = SyntheticLM(model.cfg.vocab_size, SEQ, BATCH, seed=0)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(0).items()}
    params = model.init(jax.random.PRNGKey(0))
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=100,
                       global_batch=BATCH, seq_len=SEQ)
    opt_state = make_optimizer(model, tcfg).init(params)
    comp = jnp.zeros((), jnp.float32)
    step0 = jnp.asarray(0, jnp.int32)

    f_fwd = jax.jit(lambda p, b: model.loss(p, b, plan_fp4)[0])
    f_fwd_bf16 = jax.jit(lambda p, b: model.loss(p, b, plan_bf16)[0])
    f_grad = jax.jit(jax.grad(lambda p, b: model.loss(p, b, plan_fp4)[0]))
    f_step = make_train_step(model, tcfg, plan_fp4, jit=True, donate=False)
    f_step_tel = make_train_step(
        model, TrainConfig(recipe="paper_fp4", total_steps=100,
                           global_batch=BATCH, seq_len=SEQ, telemetry=True),
        plan_fp4, jit=True, donate=False)

    n = steps_hint
    t_fwd = timeit(f_fwd, params, batch, n=n)
    t_fwd_bf16 = timeit(f_fwd_bf16, params, batch, n=n)
    t_grad = timeit(f_grad, params, batch, n=n)
    t_step = timeit(f_step, params, opt_state, comp, batch, step0, n=n)
    t_tel = timeit(f_step_tel, params, opt_state, comp, batch, step0, n=n)

    def _emit_phase(name: str, raw_delta: float, method: str) -> None:
        """One phase row from a jitted-callable delta.

        The deltas are differences of noisy measurements, so a phase whose
        true cost is below the timing noise can come out negative.  A
        negative share is impossible by construction — emit the clamped
        value with a ``noise=true`` marker instead of a bogus negative
        share (``check_bench --step`` rejects negative shares outright).
        """
        t = max(0.0, raw_delta)
        share = t / t_step if t_step > 0 else float("nan")
        noisy = ";noise=true" if raw_delta < 0 else ""
        emit(name, t,
             f"recipe=paper_fp4;share={share:.3f};method={method}{noisy}")

    _emit_phase("step/phase_fwd", t_fwd, "jit_delta")
    _emit_phase("step/phase_bwd", t_grad - t_fwd, "jit_delta(grad-fwd)")
    _emit_phase("step/phase_optim", t_step - t_grad,
                "jit_delta(step-grad)")
    _emit_phase("step/phase_quantize", t_fwd - t_fwd_bf16,
                "jit_delta(fwd_fp4-fwd_bf16)")
    emit("step/telemetry_overhead", t_tel,
         f"recipe=paper_fp4;overhead_x={t_tel / t_step:.3f};"
         "taps=in_graph")


def run(steps: int = 12) -> None:
    cfg = get_config("tiny")
    model = build_model(cfg)
    for recipe in ("paper_fp4", "bf16"):
        summ = _smoke_run(model, recipe, steps)
        p50_us = summ.get("p50_ms", float("nan")) * 1e3
        # MFU exists on a TPU only (the trainer leaves it out elsewhere)
        has_mfu = "mfu" in summ
        emit(f"step/train_step_{'fp4' if recipe != 'bf16' else 'bf16'}",
             p50_us,
             f"recipe={recipe};steps={int(summ['steps'])};"
             f"warmup={int(summ['warmup'])};"
             f"spikes={int(summ.get('spikes', 0))};"
             + (f"mfu={summ['mfu']:.5f};" if has_mfu else "")
             + f"writer_dropped={int(summ['writer_dropped'])}",
             extra={"p50_us": summ.get("p50_ms", float("nan")) * 1e3,
                    "p95_us": summ.get("p95_ms", float("nan")) * 1e3,
                    "p99_us": summ.get("p99_ms", float("nan")) * 1e3,
                    "tokens_per_sec": summ.get("tokens_per_sec",
                                               float("nan")),
                    **({"mfu": summ["mfu"]} if has_mfu else {})})
    _phase_breakdown(model)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12,
                    help="smoke-run steps per recipe")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the BENCH_step.json artifact here")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(steps=args.steps)
    if args.json:
        write_json(args.json)


if __name__ == "__main__":
    main()
