"""Device time per step of the ops the step graph scopes ``attn``
(``models/attention.py``: the QKV and output projections and the
attention itself, forward, recompute and backward), averaged over the
chips."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import scopes  # noqa: E402


def read(ctx):
    red = scopes.window(ctx)
    s = scopes.mean_scope_s(red or {}).get("attn", 0.0)
    return 1e3 * s / ctx["steps"] if s > 0 else None
