"""Host time per step from the call of the jitted step until it returns:
the loop's ``dispatch`` span, inside ``step`` (``Trainer.train``).  The
loop blocks on every step, so the chip waits through it."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import scopes  # noqa: E402


def read(ctx):
    red = scopes.window(ctx)
    if red is None or not red["span_n"]["dispatch"]:
        return None
    return 1e3 * red["span_s"]["dispatch"] / red["span_n"]["dispatch"]
