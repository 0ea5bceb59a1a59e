#!/usr/bin/env python3
"""Record a profiler trace of a few training steps on the chip, and print
what it holds.

    python3 bench/record_trace.py --out tests/bench/data/small.xplane.pb
    python3 bench/record_trace.py --workload <cell> [--steps 2]

Without ``--workload`` it traces a small model (two layers of width 256,
fused ``paper_fp4`` linears, batch 2 x 256) whose trace is small enough to
keep beside the trace reduction's test; with it, a cell at its own size,
and only the summary is printed.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile
from collections import Counter

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402

SMALL = run.cells.Cell(
    "small", 1,
    {"arch": "gpt2-774m", "model": dict(
        n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, head_dim=64,
        d_ff=512, vocab_size=1024, activation="gelu", norm="layernorm",
        pos_emb="learned", max_seq_len=256, tie_embeddings=True,
        dtype="bfloat16", attention_impl="chunked", linear_impl="pallas")},
    dict(config="small", recipe="paper_fp4", global_batch=2, seq_len=256,
         mesh_shape=None, fsdp=True, total_steps=100000, warmup_frac=0.0015,
         learning_rate=6e-4, data={"n_patterns": 512, "noise": 0.02},
         trace_steps=2, limits={}))


def summarize(path: str, n_devices: int) -> None:
    from jax.profiler import ProfileData
    tr = run.load_module("trace")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}")
        for line in lines:
            evs = list(line.events)
            names = Counter(e.name for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events; top "
                  f"{names.most_common(12)}")
            shown = 0
            for ev in evs:
                st = tr._stats(ev)
                if shown < 3 or (tr.is_kernel(ev.name, tr.parse_instr(
                        ev.name)) and shown < 6):
                    print(f"    {ev.name!r} start {ev.start_ns} dur "
                          f"{ev.duration_ns} {st!r}"[:2000])
                    shown += 1
    red = tr.reduce_profile(pd, n_devices)
    k = red["devices"][0]["kernels"] if red["devices"] else []
    print("REDUCED window_s", red["window_s"], "busy_s", red["busy_s"],
          "host_s", red["host_s"], "host_n", red["host_n"])
    print("KERNELS", len(k), k[:4])
    print("BREAKDOWN", red["breakdown"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = (run.cells.load_cell(args.workload) if args.workload
            else SMALL)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices = run.tpu_devices(cell.chips)
    run.enable_compile_cache()
    import jax

    from program import Program
    prog = Program(cell)
    prog.first_steps(seed=1)
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(tmp, profiler_options=run.trace_options())
    prog.train(args.steps)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    print("TRACE", path, os.path.getsize(path), "bytes")
    summarize(path, len(devices))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        shutil.copy(path, args.out)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
