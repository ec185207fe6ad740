"""Device milliseconds of the serve programs (``layers.json``: the
fused scans and the Pallas serve kernel) per million simulated DRAM
requests completed in the traced window."""


def read(ctx):
    tr, w = ctx["trace"], ctx["window"]
    if not tr or not tr["layer_programs"]["serve"] or not w["requests"]:
        return None
    return tr["layer_s"]["serve"] * 1e3 / (w["requests"] / 1e6)
