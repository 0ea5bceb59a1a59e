#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and the
planted faults', over many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 12 \\
        [--control-seeds 3] [--first-seed N] [--out FILE]

For each seed the program runs its checked steps (``Program``) and the
plain reference runs the same steps from the same weights; the gaps
between the two are the program's readings (a limit sits above the
largest).  On the first ``--control-seeds`` seeds it also reads the
control (the reference with each bfloat16 operand in FP8) and the planted
faults: the reference with the mean taken over half the batch, and, on a
mesh cell, over one chip's share of it (the exchange between chips left
out).  A state left unchanged reads 1 on ``update_gap`` by construction
and needs no run.  Each reading is one JSON line, on standard output and
in ``--out``.  Needs the chips the cell asks for; the benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402


def readings_over_seeds(cell, seeds, control_seeds, emit):
    import jax

    import check
    import reference
    import weights
    from program import Program

    names = weights.slice_names(weights.layout(cell.model))
    prog = Program(cell)
    mesh = cell.workload["mesh_shape"]
    share = mesh[0] if mesh else 1
    for i, seed in enumerate(seeds):
        got, batches, warm = prog.first_steps(seed)
        prog.free()
        gc.collect()
        ref = reference.readings(cell, seed, batches)
        runs = {"program": got}
        if i < control_seeds:
            runs["control"] = reference.readings(cell, seed, batches,
                                                 lower=True)
            runs["half_batch"] = reference.readings(cell, seed, batches,
                                                    half_batch=True)
            if share > 1:
                runs["one_chip_share"] = reference.readings(
                    cell, seed, [{k: v[: len(v) // share] for k, v in
                                  b.items()} for b in batches])
        for kind, r in runs.items():
            g = check.gaps(r, ref, names)
            emit({"cell": cell.name, "seed": seed, "kind": kind,
                  "warm_step_s": warm, **{k: g[k] for k in check.NUMBERS},
                  "worst": g["worst"], "losses": list(r["losses"]),
                  "ref_losses": list(ref["losses"])})
        jax.clear_caches()
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = run.cells.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    run.tpu_devices(cell.chips)
    run.enable_compile_cache()
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    readings_over_seeds(cell, seeds, args.control_seeds, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
