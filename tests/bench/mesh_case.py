"""Run by ``test_bench_mesh.py`` in a process of its own with four virtual
CPU devices: the tiny cell on a (4, 1) FSDP mesh, sound and with the
exchange between chips left out (each chip's gradient from its own rows
alone, taken here as chip 0's)."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "bench"))

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

import cells  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402


def one_chip_share(trainer):
    orig = trainer.model.loss
    trainer.model.loss = lambda p, b, plan: orig(
        p, {k: v[: v.shape[0] // 4] for k, v in b.items()}, plan)


def main():
    base = tiny.cell()
    cell = cells.Cell("tiny-mesh", 4, base.config,
                      dict(base.workload, mesh_shape=[4, 1]))
    plant = one_chip_share if sys.argv[1] == "fault" else None
    out = run.run_cell(cell, 3_000_000_023, 0.3, False, jax.devices()[:4],
                       plant=plant)
    print(json.dumps({"correct": out["correct"],
                      "checked": out["checked"]}))


if __name__ == "__main__":
    main()
