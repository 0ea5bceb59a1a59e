#!/usr/bin/env python3
"""A cell's untraced window, step by step, from the loop's own counters.

    python3 bench/window_steps.py --workload <cell> --seed <n> --seconds <s>

Builds the cell and runs its set-up and window as ``bench/run.py --trace
0`` does (no reference, no check), then reads each window step's history
row: ``t_host``, ``t_data``, ``t_dispatch``, ``t_wait`` and ``compiles``
(``Trainer.train``).  Prints on standard error every step whose loop time
(the four parts) is over 1.5 times the window's median, with its split,
and the window's means; the last line of standard output is one JSON
object with the same numbers.  Off a TPU it exits with code 2.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402

PARTS = ("t_host", "t_data", "t_dispatch", "t_wait")


def split(rows, window_s: float):
    """The window's per-step split, its slow steps, and the parts' sum to
    hold against the window's wall time.  The first step's ``t_host``
    holds the set-up's last work, before the window, and is left out."""
    rows = [dict(rows[0], t_host=0.0)] + list(rows[1:])
    loop = [sum(r[k] for k in PARTS) for r in rows]
    med = statistics.median(loop)
    slow = [dict({k: r[k] for k in PARTS + ("compiles", "step")},
                 loop=t) for r, t in zip(rows, loop) if t > 1.5 * med]
    return {
        "steps": len(rows), "window_s": window_s,
        "parts_s": sum(loop), "median_loop_s": med,
        "mean_s": {k: sum(r[k] for r in rows) / len(rows) for k in PARTS},
        "compiles": sum(r["compiles"] for r in rows),
        "slow": slow,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = run.cells.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    run.tpu_devices(cell.chips)
    run.enable_compile_cache()
    from program import Program

    prog = Program(cell)
    _, _, warm_dt = prog.first_steps(args.seed)
    n = max(2, math.ceil(args.seconds / warm_dt))
    h0 = len(prog.trainer.history)
    t0 = time.perf_counter()
    prog.train(n)
    window_s = time.perf_counter() - t0
    out = split(prog.trainer.history[h0:], window_s)
    out["workload"], out["seed"] = cell.name, args.seed
    run.log(f"[steps] {cell.name}: {n} steps in {window_s:.6f} s; parts "
            f"add up to {out['parts_s']:.6f} s; median loop "
            f"{1e3 * out['median_loop_s']:.3f} ms; means (ms) " + " ".join(
                f"{k} {1e3 * v:.3f}" for k, v in out["mean_s"].items())
            + f"; {out['compiles']} compiles")
    for s in out["slow"]:
        run.log(f"[steps] slow step {s['step']}: {1e3 * s['loop']:.3f} ms = "
                + " + ".join(f"{k} {1e3 * s[k]:.3f}" for k in PARTS)
                + f"; compiles {s['compiles']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
