"""Host time per step of the training loop outside the step's dispatch
and sync: the ``data`` and ``host`` spans of ``Trainer.train``.  The loop
blocks on every step, so the chip waits through this time."""


def read(ctx):
    t = ctx["trace"]
    n = t["host_n"].get("step", 0)
    if n == 0:
        return None
    return 1e3 * (t["host_s"]["data"] + t["host_s"]["host"]) / n
