"""Device time per step, averaged over the chips, of collective operations
(the FSDP gathers, the gradient reductions, the sharded quantize pass's
cross-chip amax) during which no other operation ran on the same chip
(``mesh_trace.exposed_s``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import mesh_trace  # noqa: E402


def read(ctx):
    red = mesh_trace.window(ctx)
    devs = (red or {}).get("devices") or []
    if not devs or not any(d["collective_s"] > 0 for d in devs):
        return None
    return 1e3 * sum(d["exposed_s"] for d in devs) / len(devs) / ctx["steps"]
