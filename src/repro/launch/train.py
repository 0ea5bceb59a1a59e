"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch llama-125m \
        --recipe paper_fp4 --steps 1000 --batch 16 --seq 256 \
        --ckpt /tmp/ck --resume

On a real cluster this process runs once per host (jax.distributed); the
index-addressed data pipeline and GSPMD sharding need no other coordination.
"""
import argparse

from repro.configs.base import TrainConfig, get_config
from repro.data import make_pipeline
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.train.trainer import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config")
    ap.add_argument("--recipe", default="paper_fp4")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--grad-compression", default="none")
    ap.add_argument("--mesh", default="",
                    help="mesh shape, e.g. '4,2' (axes data,model); empty "
                         "= single-device step")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="replicate embed params over the data axes "
                         "(required with --grad-compression fp8)")
    ap.add_argument("--telemetry-jsonl", default="",
                    help="JSONL metrics log (written off the critical "
                         "path by the async writer)")
    ap.add_argument("--cost-calibration", default="",
                    help="measured speed-factor JSON from "
                         "'kernel_bench --measure-speed' (empty = paper "
                         "theory factors)")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.reduced:
        import importlib
        cfg = importlib.import_module(
            "repro.configs."
            + args.arch.replace("-", "_").replace(".", "_")).REDUCED
    else:
        cfg = get_config(args.arch)
    model = build_model(cfg)
    mesh_shape = (tuple(int(d) for d in args.mesh.split(","))
                  if args.mesh else None)
    mesh_axes = (("data", "model")[:len(mesh_shape)]
                 if mesh_shape else None)
    tcfg = TrainConfig(
        recipe=args.recipe, total_steps=args.steps,
        global_batch=args.batch, seq_len=args.seq, learning_rate=args.lr,
        microbatch=args.microbatch, grad_compression=args.grad_compression,
        mesh_shape=mesh_shape, mesh_axes=mesh_axes, fsdp=not args.no_fsdp,
        checkpoint_every=args.ckpt_every, checkpoint_dir=args.ckpt,
        telemetry_jsonl=args.telemetry_jsonl,
        cost_calibration=args.cost_calibration,
        log_every=max(args.steps // 20, 1))
    pipe = make_pipeline(args.data, cfg.vocab_size, args.seq, args.batch)
    trainer = Trainer(model, tcfg, pipe)
    state = trainer.resume() if args.resume else None
    state = trainer.train(state, log=print)
    print("eval:", trainer.evaluate(state))
    summ = trainer.step_time_summary()
    if summ.get("steps"):
        print("step-time: "
              + " ".join(f"{k}={summ[k]:.1f}" for k in
                         ("p50_ms", "p95_ms", "p99_ms") if k in summ)
              + (f" tokens/s={summ['tokens_per_sec']:.0f}"
                 if "tokens_per_sec" in summ else "")
              + (f" mfu={summ['mfu']:.4f}" if "mfu" in summ else ""))


if __name__ == "__main__":
    main()
