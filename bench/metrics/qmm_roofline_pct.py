"""The kernels' share of their roofline: the sum over kernel calls of each
call's least time (``flops.kernel_least_time_s``, from the call's own
shapes) over the calls' device time."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import flops  # noqa: E402


def read(ctx):
    calls = [k for d in ctx["trace"]["devices"] for k in d["kernels"]]
    if not calls or any(k["operands"] is None for k in calls):
        return None
    spent = sum(k["seconds"] for k in calls)
    least = sum(flops.kernel_least_time_s(k, ctx["peaks"]) for k in calls)
    if spent <= 0:
        return None
    return 100.0 * least / spent
