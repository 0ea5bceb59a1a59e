#!/usr/bin/env python3
"""Chip smoke run: the FP4 trainer and packed-FP4 decode on a TPU.

    python chip_smoke.py              # one chip: training, then serving
    python chip_smoke.py --chips 4    # four chips: the mesh-native trainer
                                      # against a one-device run, only

Runs the system's main path through its normal entry points (``Trainer``,
``DecodeEngine``) at GPT-2-125M's published widths with random weights
from a fixed seed, and checks what comes out.  Any failed check exits
non-zero.  Off a TPU it exits non-zero before running anything.  The last
line of standard output is one JSON object naming the device::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Times and memory printed on the way are observations of this smoke run,
not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import TrainConfig  # noqa: E402
from repro.configs.gpt2_125m import CONFIG as GPT2_125M  # noqa: E402
from repro.core.recipe import RECIPES  # noqa: E402
from repro.data import SyntheticLM  # noqa: E402
from repro.kernels.fp4_matmul import interpret_mode  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.train.serve import generate  # noqa: E402
from repro.train.serving_runtime import (DecodeEngine,  # noqa: E402
                                         quantize_weights_for_serving)
from repro.train.trainer import Trainer  # noqa: E402

SEED = 0
SEQ = 1024
BATCH = 16            # the paper trains at 480; cut to fit one chip
STEPS = 20            # paper_fp4 switches to its stage-2 plan at step 18
# |fused - QDQ| first-step loss.  Both paths quantize identically; they
# differ only in where the bf16 matmuls round (f32 VMEM accumulator vs
# XLA's dot), which moves a ~11.3 initial loss by far less than this.
LOSS_ATOL = 2e-2
PROMPT_LENS = (37, 100, 64, 250)   # mixed lengths: three prefill buckets
N_NEW = 16
MAX_LEN = 512
# --chips 4: per-step |mesh - one device| loss, and the fp8-compressed
# run's per-step distance from the uncompressed mesh run.
MESH_STEPS = 6
MESH_LOSS_ATOL = 5e-2
FP8_LOSS_BAND = 0.25
KERNEL = 'custom_call_target="tpu_custom_call"'


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"[check] ok: {what}", flush=True)


def observe(what: str) -> None:
    print(f"[smoke observation, not a benchmark metric] {what}", flush=True)


def tpu_devices(count: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              "nothing was run", file=sys.stderr)
        sys.exit(2)
    if len(devs) < count:
        print(f"chip_smoke: need {count} TPU chips, JAX found {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    return devs[:count]


def _losses(trainer: Trainer):
    return [r["loss"] for r in trainer.history]


# ---------------------------------------------------------------------------
# One chip: training
# ---------------------------------------------------------------------------

def train_phase(cfg, *, seq: int = SEQ, batch: int = BATCH,
                steps: int = STEPS):
    """Fused-kernel paper_fp4 training through ``Trainer.train``; returns
    the model, the trained params and the data pipeline."""
    print(f"[train] {cfg.name} at {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; seq {seq}, batch {batch} (only the batch is "
          "cut: the paper uses 480); recipe paper_fp4, linear_impl "
          f"{cfg.linear_impl}, {steps} steps", flush=True)
    model = build_model(cfg)
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=steps,
                       global_batch=batch, seq_len=seq, seed=SEED,
                       log_every=1)
    pipe = SyntheticLM(cfg.vocab_size, seq, batch, seed=SEED)
    trainer = Trainer(model, tcfg, pipe)
    state = trainer.init_state()
    params0 = state.params
    batch0 = {k: jnp.asarray(v) for k, v in pipe.batch(0).items()}
    stage1 = trainer.schedule.plan_at(0)
    stage2 = trainer.schedule.target_plan
    check(0 < trainer.schedule.switch_step < steps,
          f"the stage-2 switch (step {trainer.schedule.switch_step}) "
          f"falls inside the {steps}-step run")

    # The compiled stage-1 step must hold the fused kernels.
    t0 = time.perf_counter()
    step_fn = trainer._step_fn(stage1)
    compiled = step_fn.lower(
        state.params, state.opt_state, state.comp_state, batch0,
        jnp.asarray(0, jnp.int32), jnp.asarray(1.0, jnp.float32)).compile()
    n_kernels = compiled.as_text().count(KERNEL)
    observe(f"stage-1 step compile {time.perf_counter() - t0:.1f} s")
    print(f"[train] compiled stage-1 step: {n_kernels} tpu_custom_call",
          flush=True)
    if not interpret_mode():  # on a TPU: the fused kernels are native
        check(n_kernels > 0, "the compiled stage-1 step contains "
              "tpu_custom_call (fused kernels, not the interpreter)")

    state = trainer.train(state, log=lambda s: print(s, flush=True))
    losses = _losses(trainer)
    plans = {r["recipe"] for r in trainer.history}
    check(len(losses) == steps, f"{steps} steps ran")
    check(bool(np.all(np.isfinite(losses))), "every loss is finite")
    check(losses[-1] < losses[0],
          f"last loss {losses[-1]:.4f} < first {losses[0]:.4f}")
    check(plans == {stage1.name, stage2.name},
          f"both compiled plans ran ({sorted(plans)})")

    # First-step loss of the fused path against the QDQ path, same
    # params and batch.
    qdq = build_model(cfg.replace(linear_impl="qdq"))
    loss_qdq = float(jax.jit(lambda p, b: qdq.loss(p, b, stage1)[0])(
        params0, batch0))
    diff = abs(losses[0] - loss_qdq)
    print(f"[train] first-step loss fused {losses[0]:.6f} qdq "
          f"{loss_qdq:.6f} |diff| {diff:.3e}", flush=True)
    check(diff <= LOSS_ATOL, f"fused first-step loss within {LOSS_ATOL} "
          "of the QDQ path")

    summ = trainer.step_time_summary()
    observe(f"p50 step time after warmup {summ.get('p50_ms', float('nan')):.1f}"
            f" ms over {summ['steps']} steps ({summ['spikes']} recompile "
            "spikes excluded)")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        observe(f"peak device memory {stats['peak_bytes_in_use'] / 2**30:.2f}"
                " GiB")
    return model, state.params, pipe


# ---------------------------------------------------------------------------
# One chip: serving
# ---------------------------------------------------------------------------

def _engine_tokens(model, params, prompts, *, kv_format=None,
                   n_new: int = N_NEW, max_len: int = MAX_LEN):
    engine = DecodeEngine(model, params, n_slots=len(prompts),
                          max_len=max_len, recipe=RECIPES["paper_fp4"],
                          kv_format=kv_format)
    for slot, p in enumerate(prompts):
        tok, c1 = engine.prefill(p)
        engine.insert(c1, tok, slot)
    out = [[int(engine.last_tok[s])] for s in range(len(prompts))]
    for _ in range(n_new - 1):
        nxt = engine.generate_step()
        for s in range(len(prompts)):
            out[s].append(int(nxt[s]))
    return out


def serve_phase(model, params, pipe, *, prompt_lens=PROMPT_LENS,
                n_new: int = N_NEW, max_len: int = MAX_LEN):
    """Packed-FP4 ``DecodeEngine`` against one-at-a-time greedy
    ``generate`` on the same packed params, then with an FP8 KV cache."""
    vocab = model.cfg.vocab_size
    packed = quantize_weights_for_serving(model, params, "fp4_e2m1")
    rows = pipe.batch(10_000)["tokens"]
    prompts = [np.asarray(rows[i, :n], np.int32)
               for i, n in enumerate(prompt_lens)]
    print(f"[serve] packed fp4 weights, recipe paper_fp4 activations; "
          f"{len(prompts)} prompts of lengths {list(prompt_lens)}, "
          f"{n_new} new tokens each", flush=True)

    t0 = time.perf_counter()
    got = _engine_tokens(model, packed, prompts, n_new=n_new,
                         max_len=max_len)
    observe(f"exact-cache engine run {time.perf_counter() - t0:.1f} s "
            "(compiles included)")
    for s, p in enumerate(prompts):
        ref = generate(model, packed, jnp.asarray(p)[None],
                       max_new_tokens=n_new,
                       recipe=RECIPES["paper_fp4"])[0, len(p):]
        ref = [int(t) for t in ref]
        print(f"[serve] prompt {s} (len {len(p)}): engine {got[s]} "
              f"generate {ref}", flush=True)
        check(got[s] == ref, f"prompt {s}: engine tokens equal greedy "
              "generate")

    got8 = _engine_tokens(model, packed, prompts, kv_format="fp8_e4m3",
                          n_new=n_new, max_len=max_len)
    for s, toks in enumerate(got8):
        print(f"[serve] fp8 KV prompt {s}: {toks}", flush=True)
    check(all(len(t) == n_new for t in got8),
          f"fp8 KV cache: every request got {n_new} tokens")
    check(all(0 <= t < vocab for toks in got8 for t in toks),
          "fp8 KV cache: every token is inside the vocabulary")


# ---------------------------------------------------------------------------
# Four chips: mesh-native data-parallel trainer
# ---------------------------------------------------------------------------

def _mesh_trainer(cfg, *, seq, batch, steps, **tkw):
    model = build_model(cfg)
    tcfg = TrainConfig(recipe="paper_fp4", total_steps=steps,
                       global_batch=batch, seq_len=seq, seed=SEED,
                       log_every=1, **tkw)
    return Trainer(model, tcfg, SyntheticLM(cfg.vocab_size, seq, batch,
                                            seed=SEED))


def mesh_phase(cfg, devices, *, seq: int = SEQ, batch: int = BATCH,
               steps: int = MESH_STEPS):
    n = len(devices)
    print(f"[mesh] {cfg.name}, linear_impl {cfg.linear_impl}, global batch "
          f"{batch}, seq {seq}: mesh ({n}, 1) fsdp against one device, "
          f"{steps} steps", flush=True)
    log = lambda s: print(s, flush=True)  # noqa: E731

    one = _mesh_trainer(cfg, seq=seq, batch=batch, steps=steps)
    one.train(log=log)
    ref = _losses(one)

    mesh = _mesh_trainer(cfg, seq=seq, batch=batch, steps=steps,
                         mesh_shape=(n, 1), fsdp=True)
    state = mesh.init_state()
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree.leaves(state.params)}
    check(spans == {n}, f"every param spans {n} devices")
    mesh.train(state, log=log)
    got = _losses(mesh)
    worst = max(abs(a - b) for a, b in zip(got, ref))
    print(f"[mesh] losses mesh {got} one-device {ref} max |diff| "
          f"{worst:.3e}", flush=True)
    check(bool(np.all(np.isfinite(got))), "mesh losses are finite")
    check(worst <= MESH_LOSS_ATOL, f"mesh losses within {MESH_LOSS_ATOL} "
          "of the one-device run, step by step")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]
    observe(f"bytes in use per device {in_use}")
    check(all(b > 0 for b in in_use), "every device holds bytes")

    fp8 = _mesh_trainer(cfg, seq=seq, batch=batch, steps=steps,
                        mesh_shape=(n, 1), fsdp=False,
                        grad_compression="fp8")
    fp8.train(log=log)
    got8 = _losses(fp8)
    worst8 = max(abs(a - b) for a, b in zip(got8, got))
    print(f"[mesh] fp8-compressed losses {got8} max |diff| vs mesh "
          f"{worst8:.3e}", flush=True)
    check(bool(np.all(np.isfinite(got8))), "fp8-compressed losses are "
          "finite")
    check(worst8 <= FP8_LOSS_BAND, f"fp8-compressed losses within "
          f"{FP8_LOSS_BAND} of the uncompressed mesh run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh-native trainer phase")
    args = ap.parse_args(argv)
    # before JAX starts its backend: libtpu would log under /tmp otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices = tpu_devices(args.chips)
    enable_compile_cache()
    if args.chips == 1:
        model, params, pipe = train_phase(
            GPT2_125M.replace(linear_impl="pallas"))
        serve_phase(model, params, pipe)
    else:
        # The fused kernels run under a mesh through the sharded quantize
        # pass (kernels/ops.py), measured by the benchmark's four-chip
        # cell; this phase keeps the QDQ linears, the simulation it
        # compares the mesh, one-device and fp8-compressed runs on.
        mesh_phase(GPT2_125M.replace(linear_impl="qdq"), devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
