"""Serving launcher CLI: continuous-batched generation.

    PYTHONPATH=src python -m repro.launch.serve --arch tiny --requests 6 \
        --weight-quant fp4_e2m1
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core.recipe import RECIPES
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.train.serving_runtime import (ContinuousBatcher,
                                         quantize_weights_for_serving)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--weight-quant", default="none",
                    help="none | fp8_e4m3 | fp4_e2m1 (weight-only serving)")
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
    params = model.init(jax.random.PRNGKey(0))
    if args.weight_quant != "none":
        params = quantize_weights_for_serving(model, params,
                                              args.weight_quant)
        print(f"weights quantized to {args.weight_quant} (per-block-128)")

    rng = np.random.default_rng(0)
    batcher = ContinuousBatcher(model, params, n_slots=args.slots,
                                max_len=256, recipe=RECIPES["bf16"])
    ids = []
    for _ in range(args.requests):
        n = int(rng.integers(4, 24))
        prompt = rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
        ids.append(batcher.submit(prompt, args.max_new))
    t0 = time.time()
    out = batcher.run()
    dt = time.time() - t0
    total = sum(len(v) for v in out.values())
    print(f"served {len(out)} requests / {total} tokens in {dt:.1f}s "
          f"({total / dt:.1f} tok/s) with {args.slots} slots")
    for rid in ids[:3]:
        print(f"  req {rid}: {out[rid]}")


if __name__ == "__main__":
    main()
