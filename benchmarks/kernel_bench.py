"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels run in interpret mode (correctness
path), so wall-times here measure (a) the pure-jnp QDQ+matmul simulation
(what training actually pays on CPU) and (b) the chunked-flash vs naive
attention — both meaningful CPU comparisons.  TPU wall-times come from the
roofline analysis instead.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit, timeit_stats, write_json
from repro.core.qlinear import (pallas_qmatmul, pallas_qmatmul_two_pass,
                                qlinear, qmatmul)
from repro.core.recipe import RECIPES, MatmulRecipe
from repro.kernels.fp4_matmul import fused_qmm, use_pipeline
from repro.kernels.ref import fp4_matmul_ref
from repro.models.attention import chunked_attention
from repro.kernels.ref import flash_attention_ref


def _quant_work_counters(m, k, n, tag: str) -> None:
    """Analytic per-role quantize-work counters for the two-phase pipeline.

    The pre-rework fused kernel re-QDQ'd every LHS (128 x 128) K-tile once
    per output-column visit and every RHS K-tile once per output-row visit
    — O(M/bm * N/bn) tile-QDQs per operand element-touch.  The quantize
    pass does each K-panel ONCE.  Counts are exact tile-QDQ totals for one
    matmul of the given shape (128-padded), emitted so the redundancy win
    is visible in the BENCH JSON.
    """
    t = 128
    mt, kt, nt = -(-m // t), -(-k // t), -(-n // t)
    for role, (op_tiles, revisit) in {
        "fwd": ((mt * kt, nt), (kt * nt, mt)),
        "dgrad": ((mt * nt, kt), (nt * kt, mt)),
        "wgrad": ((kt * mt, nt), (mt * nt, kt)),
    }.items():
        (lhs_tiles, lhs_rev), (rhs_tiles, rhs_rev) = op_tiles, revisit
        old = lhs_tiles * lhs_rev + rhs_tiles * rhs_rev
        new = lhs_tiles + rhs_tiles
        emit(f"kernel/{tag}_quant_tile_qdqs_{role}", float(new),
             f"old_fused={old};new_pipeline={new};"
             f"redundancy_x={old / new:.1f};one_qdq_per_kpanel=true",
             unit="tile_qdqs")


def _bench_fused_roles(x, w, recipe, tag: str) -> None:
    """Time the Pallas pipelines vs unfused qmatmul for all three training
    matmuls: fwd via the primal, dgrad+wgrad via the VJP.

    ``pallas_fused`` is the two-pass pipeline, the default (quantize each
    operand to HBM once, then XLA's dot); ``pallas_stream`` is the
    single-pass streaming pipeline (quantized K-panels live in VMEM and are
    consumed directly by the MXU loop).  The stream rows carry a
    ``speedup_vs_two_pass`` derived field so the overlap win is measured,
    and ``check_bench`` REQUIREs both entry families.
    """
    key = jnp.zeros((2,), jnp.uint32)
    c = jnp.ones((x.shape[0], w.shape[1]), x.dtype)

    times = {}
    for impl_name, mm, pipe in (
            ("qdq", qmatmul, None),
            ("pallas_fused", pallas_qmatmul_two_pass, None),
            ("pallas_stream", pallas_qmatmul, "stream")):
        # use_pipeline must cover tracing, which happens at the first timed
        # call; pallas_stream pins the pipeline explicitly so the row stays
        # a stream measurement even if the session default changes.
        ctx = use_pipeline(pipe) if pipe else contextlib.nullcontext()
        with ctx:
            f_fwd = jax.jit(lambda a, b, mm=mm: mm(a, b, key, recipe))
            # vjp once OUTSIDE the timed region (it runs the primal); time
            # only the jitted pullback so the row really is dgrad+wgrad.
            _, pullback = jax.vjp(lambda p, q: mm(p, q, key, recipe), x, w)
            f_bwd = jax.jit(pullback)
            times[impl_name] = (timeit(f_fwd, x, w, n=15),
                                timeit(f_bwd, c, n=15))
    for impl_name, (t_fwd, t_bwd) in times.items():
        extra_f = extra_b = ""
        if impl_name == "pallas_stream":
            tp_f, tp_b = times["pallas_fused"]
            extra_f = f";speedup_vs_two_pass={tp_f / t_fwd:.3f}"
            extra_b = f";speedup_vs_two_pass={tp_b / t_bwd:.3f}"
        emit(f"kernel/{tag}_fwd_{impl_name}", t_fwd,
             f"impl={impl_name};role=fwd{extra_f}")
        emit(f"kernel/{tag}_dgrad_wgrad_{impl_name}", t_bwd,
             f"impl={impl_name};role=dgrad+wgrad{extra_b}")
    _quant_work_counters(x.shape[0], x.shape[1], w.shape[1], tag)


def _bench_stream_overlap(x, w, tag: str) -> None:
    """The streaming kernel pinned to a fixed (128, 128, 128) tiling — the
    constrained multi-tile regime real VMEM budgets force on TPU — against
    the two-pass pipeline, which takes no tiles: a quantize grid per
    operand with an HBM round-trip, then XLA's dot; stream walks one fused
    grid with both operand caches live.  NOTE: interpret mode prices
    emulated op count, not launches or HBM traffic, so the CPU ratio here
    is a trend anchor for the TPU re-measurement (ROADMAP item 3), not a
    speedup claim."""
    times = {}
    for pipe in ("two_pass", "stream"):
        f = jax.jit(lambda a, b, p=pipe: fused_qmm(
            a, b, a_mode="block", b_mode="tile", bm=128, bn=128, bk=128,
            pipeline=p, interpret=True))
        times[pipe] = timeit(f, x, w, n=15)
    emit(f"kernel/{tag}_fwd_two_pass_t128", times["two_pass"],
         "impl=two_pass;tiles=128x128x128")
    emit(f"kernel/{tag}_fwd_stream_t128", times["stream"],
         f"impl=stream;tiles=128x128x128;"
         f"speedup_vs_two_pass={times['two_pass'] / times['stream']:.3f}")


def _bench_telemetry_epilogue(x, w, recipe, tag: str) -> None:
    """Quantize-pass telemetry epilogue on vs off (same kernel, stats
    accumulators + (1, 8) stats output added) for the fwd role."""
    from repro.core.qlinear import kernel_quant_mode
    from repro.kernels.ops import pallas_qmm
    sa, sb = recipe.fwd_x, recipe.fwd_w
    ma, mb = kernel_quant_mode(sa), kernel_quant_mode(sb)
    f_off = jax.jit(lambda a, b: pallas_qmm(
        a, b, sa, sb, mode_a=ma, mode_b=mb))
    f_on = jax.jit(lambda a, b: pallas_qmm(
        a, b, sa, sb, mode_a=ma, mode_b=mb, collect_stats=True)[0])
    t_off = timeit(f_off, x, w, n=15)
    t_on = timeit(f_on, x, w, n=15)
    emit(f"kernel/{tag}_quant_epilogue_off", t_off, "telemetry_epilogue=off")
    emit(f"kernel/{tag}_quant_epilogue_on", t_on,
         f"telemetry_epilogue=on;overhead_x={t_on / t_off:.3f}")


def _bench_flash_attention() -> None:
    """The splash flash-attention forward kernel (interpret mode on CPU)
    vs the chunked-jnp path at the same shape — closes the benchmark
    coverage gap: the matmul kernels were regression-guarded, the
    attention kernel was not.  256-seq keeps interpret-mode runtime sane
    (one 256 x 256 tile per batch row and head)."""
    from repro.kernels import flash_attention
    b, s, h, d = 2, 256, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    f_flash = jax.jit(lambda q, k, v: flash_attention(q, k, v,
                                                      causal=True))
    f_chunk = jax.jit(lambda q, k, v: chunked_attention(
        q, k, v, pos, pos, causal=True, chunk=128))
    t_f = timeit(f_flash, q, k, v, n=10)
    t_c = timeit(f_chunk, q, k, v, n=10)
    emit("kernel/flash_attention_fwd_256", t_f,
         f"impl=splash_interpret;block=256;rel_chunked={t_f / t_c:.2f}")
    emit("kernel/attention_chunked_256", t_c, "impl=chunked_jnp;chunk=128")


def _bench_telemetry_step() -> None:
    """Full train-step wall time, telemetry off vs on (tiny config).

    The in-graph taps add O(elements) stat reductions next to O(M*K*N)
    matmuls; the emitted overhead ratio is the acceptance number for the
    telemetry subsystem (<10% at real model sizes — the tiny-config CPU
    ratio here is the pessimistic bound since its matmuls are small).
    """
    from repro.configs.base import TrainConfig, get_config
    from repro.data import SyntheticLM
    from repro.models import build_model
    from repro.train.train_step import make_optimizer, make_train_step

    cfg = get_config("tiny")
    model = build_model(cfg)
    pipe = SyntheticLM(cfg.vocab_size, 64, 8, seed=0)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(0).items()}
    params = model.init(jax.random.PRNGKey(0))
    step0 = jnp.asarray(0, jnp.int32)
    times = {}
    for tel in (False, True):
        tcfg = TrainConfig(recipe="paper_fp4", total_steps=20,
                           global_batch=8, seq_len=64, telemetry=tel)
        step = make_train_step(model, tcfg, RECIPES["paper_fp4"],
                               jit=True, donate=False)
        opt_state = make_optimizer(model, tcfg).init(params)
        comp = jnp.zeros((), jnp.float32)
        times[tel] = timeit_stats(step, params, opt_state, comp, batch,
                                  step0, n=10)
    emit("kernel/train_step_tiny_telemetry_off", times[False]["median_us"],
         "recipe=paper_fp4;telemetry=off",
         extra={k: times[False][k] for k in ("p50_us", "p95_us", "p99_us")})
    emit("kernel/train_step_tiny_telemetry_on", times[True]["median_us"],
         f"recipe=paper_fp4;telemetry=on;overhead_x="
         f"{times[True]['median_us'] / times[False]['median_us']:.3f}",
         extra={k: times[True][k] for k in ("p50_us", "p95_us", "p99_us")})
    # production setting: sample stats every N steps (telemetry_every)
    t_on, t_off = times[True]["median_us"], times[False]["median_us"]
    for every in (5, 10):
        amortized = (t_on + (every - 1) * t_off) / every
        emit(f"kernel/train_step_tiny_telemetry_every{every}", amortized,
             f"recipe=paper_fp4;telemetry_every={every};"
             f"overhead_x={amortized / t_off:.3f}")


def measure_speed_factors(size: int = 256, n: int = 10,
                          recipes=("bf16", "fp8", "paper_fp4",
                                   "fine_grained_fp4")):
    """Measure wall-clock matmul speed factors for the cost model.

    For every distinct operand-spec pair appearing in the given recipes'
    matmul roles (fwd: (fwd_x, fwd_w), dgrad: (dgrad_g, dgrad_w), wgrad:
    (wgrad_x, wgrad_g) — exactly the pairings ``cost_model._linear_time``
    prices), time the jitted QDQ matmul at ``size^3`` and express its
    throughput relative to the plain matmul at the same shape — the same
    normalization as the paper's ``_SPEED`` theory, so the table drops
    straight into ``cost_model.calibrate``.  Keys follow
    ``cost_model._cal_key``: ``fmt`` for passthrough, ``fmt@granularity``
    otherwise.

    Returns a ``CostCalibration``.  On this CPU container the QDQ
    simulation is *slower* than the plain matmul (factors < 1 where theory
    says 4x) — which is the point: the searcher should rank plans by what
    this host actually pays, and on FP4 tensor-core hardware the same
    harness measures the real speedup.
    """
    from repro.core.cost_model import _cal_key, calibrate
    from repro.core.qlinear import dot_qdq

    x = jax.random.normal(jax.random.PRNGKey(0), (size, size), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (size, size), jnp.float32) * 0.05
    t_ref = timeit(jax.jit(lambda a, b: a @ b), x, w, n=n)
    pairs = {}
    for rname in recipes:
        recipe = RECIPES[rname]
        for f in dataclasses.fields(recipe):
            mm = getattr(recipe, f.name)
            if not isinstance(mm, MatmulRecipe):
                continue
            for sa, sb in ((mm.fwd_x, mm.fwd_w), (mm.dgrad_g, mm.dgrad_w),
                           (mm.wgrad_x, mm.wgrad_g)):
                pairs.setdefault((_cal_key(sa), _cal_key(sb)), (sa, sb))
    table = {}
    for (ka, kb), (sa, sb) in sorted(pairs.items()):
        f_mm = jax.jit(lambda a, b, sa=sa, sb=sb: dot_qdq(a, b, sa, sb))
        t = timeit(f_mm, x, w, n=n)
        factor = t_ref / t
        table[(ka, kb)] = factor
        emit(f"kernel/speed_factor_{ka}*{kb}", t,
             f"measured_factor={factor:.4f};ref_plain_us={t_ref:.1f};"
             f"shape={size}x{size}x{size}", unit="us")
    return calibrate(table, source=f"kernel_bench:{size}^3")


def run_autotune(path: str) -> None:
    """Populate and save the persistent ``(bm, bn, bk)`` tuning table.

    Sweeps the tile candidates for the paper-recipe FFN matmul roles (the
    shapes/granularities the fused-role benches and the qlinear training
    path actually issue) and writes a ``qmm_tuning_table.v1`` JSON that
    ``fused_qmm`` consults on every call without explicit tiles.  The
    committed copy lives at ``src/repro/kernels/tuning_table.json`` and is
    validated in CI (``python -m repro.kernels.autotune --validate``).
    """
    from repro.kernels.autotune import TuningTable, autotune_qmm

    table = TuningTable(meta={
        "source": "kernel_bench --autotune",
        "backend": jax.default_backend(),
        "note": "interpret-mode timings on CPU; regenerate on TPU for "
                "hardware-true tiles",
    })
    jobs = (
        # paper FFN fwd: fp4 block x fp4 tile, nn
        dict(m=256, n=256, k=256, a_mode="block", b_mode="tile"),
        dict(m=512, n=512, k=512, a_mode="block", b_mode="tile"),
        # paper FFN dgrad: bf16 passthrough pair, g @ w^T
        dict(m=256, n=256, k=256, a_mode="pass", b_mode="pass",
             trans_b=True),
        # paper FFN wgrad: fp8 block pair, x^T @ g
        dict(m=256, n=256, k=256, a_mode="block", b_mode="block",
             a_fmt="fp8_e4m3", b_fmt="fp8_e5m2", trans_a=True),
    )
    for job in jobs:
        tiles, us = autotune_qmm(table=table, **job)
        print(f"[autotune] m{job['m']}_n{job['n']}_k{job['k']} "
              f"{job['a_mode']}:{job['b_mode']} -> bm={tiles[0]} "
              f"bn={tiles[1]} bk={tiles[2]} ({us:.0f}us)", flush=True)
    table.save(path)
    print(f"[autotune] wrote {len(table.entries)} entries -> {path}",
          flush=True)


def run() -> None:
    m, k, n = 512, 512, 512
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32) * 0.05

    f_bf = jax.jit(lambda a, b: a @ b)
    f_q = jax.jit(lambda a, b: fp4_matmul_ref(a, b))
    t_bf = timeit(f_bf, x, w)
    t_q = timeit(f_q, x, w)
    emit("kernel/matmul_plain_512", t_bf, "impl=xla_dot")
    emit("kernel/matmul_fp4qdq_512", t_q,
         f"impl=simulated_qdq;overhead_x={t_q / t_bf:.2f}")

    rcp = RECIPES["paper_fp4"].ffn_linear
    f_lin = jax.jit(lambda a, b: qlinear(a, b, rcp))
    emit("kernel/qlinear_paper_fp4_512", timeit(f_lin, x, w),
         "fwd=fp4_block")

    # Fused Pallas path, all three roles (interpret mode on CPU: this
    # validates the code path and counts; TPU wall-times come from the
    # roofline analysis).  256^3 keeps interpret-mode runtime sane.
    xs, ws = x[:256, :256], w[:256, :256]
    _bench_fused_roles(xs, ws, RECIPES["paper_fp4"].ffn_linear,
                       "qmm256_ffn_paper")
    _bench_stream_overlap(xs, ws, "qmm256_ffn_paper")
    _bench_telemetry_epilogue(xs, ws, RECIPES["paper_fp4"].ffn_linear,
                              "qmm256_ffn_paper")

    b, s, h, d = 2, 512, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    kk = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    f_naive = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v))
    f_chunk = jax.jit(lambda q, k, v: chunked_attention(
        q, k, v, pos, pos, causal=True, chunk=128))
    t_n = timeit(f_naive, q, kk, v, n=10)
    t_c = timeit(f_chunk, q, kk, v, n=10)
    emit("kernel/attention_naive_512", t_n, "memory=O(S^2)")
    emit("kernel/attention_chunked_512", t_c,
         f"memory=O(S*chunk);rel={t_c / t_n:.2f}")

    _bench_flash_attention()
    _bench_telemetry_step()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as machine-readable JSON")
    ap.add_argument("--measure-speed", default=None, metavar="PATH",
                    help="measure wall-clock speed factors and write a "
                         "speed_factors.v1 JSON (feeds TrainConfig."
                         "cost_calibration / cost_model.calibrate); skips "
                         "the full kernel sweep")
    ap.add_argument("--autotune", default=None, metavar="PATH",
                    help="sweep (bm, bn, bk) candidates for the paper-"
                         "recipe matmul roles and write the tuning table "
                         "JSON here (commit to src/repro/kernels/"
                         "tuning_table.json); skips the full kernel sweep")
    args = ap.parse_args()
    if args.measure_speed:
        cal = measure_speed_factors()
        cal.to_json(args.measure_speed)
        print(f"[bench] wrote {len(cal.table)} measured speed factors -> "
              f"{args.measure_speed}", flush=True)
    elif args.autotune:
        run_autotune(args.autotune)
    else:
        run()
    if args.json:
        write_json(args.json)
