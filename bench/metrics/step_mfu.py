"""The whole step's share of the chip's bf16 peak: model flops of the
traced steps (``flops.train_flops_per_step``) over the traced window's
length, the chips and the peak."""


def read(ctx):
    t = ctx["trace"]
    if t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    work = ctx["flops_per_step"] * ctx["steps"]
    return 100.0 * work / (t["window_s"] * ctx["chips"]
                           * ctx["peaks"]["bf16_flops_per_s"])
