"""Run by ``test_bench_mesh_pallas.py`` in a process of its own with four
virtual CPU devices: the tiny cell on a (4, 1) FSDP mesh through the fused
quantize pass (``linear_impl="pallas"``, the kernels in interpret mode,
each operand's pass under ``shard_map``), sound and with the exchange
between chips left out (``mesh_case.one_chip_share``)."""
import json
import sys

import mesh_case
from mesh_case import cells, jax, run, tiny


def main():
    base = tiny.cell(linear_impl="pallas")
    cell = cells.Cell("tiny-mesh-pallas", 4, base.config,
                      dict(base.workload, mesh_shape=[4, 1]))
    plant = mesh_case.one_chip_share if sys.argv[1] == "fault" else None
    out = run.run_cell(cell, 3_000_000_029, 0.3, False, jax.devices()[:4],
                       plant=plant)
    print(json.dumps({"correct": out["correct"],
                      "checked": out["checked"]}))


if __name__ == "__main__":
    main()
