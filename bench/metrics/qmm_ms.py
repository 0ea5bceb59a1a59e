"""Device time per step of the fused quantize-matmul (Mosaic) kernels,
averaged over the chips."""


def read(ctx):
    devs = ctx["trace"]["devices"]
    total = sum(k["seconds"] for d in devs for k in d["kernels"])
    if not devs or total <= 0:
        return None
    return 1e3 * total / len(devs) / ctx["steps"]
