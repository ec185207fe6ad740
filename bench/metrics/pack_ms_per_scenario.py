"""Device milliseconds of the pack programs (``layers.json``: the device
pack's classify/block and scatter dispatches) per scenario completed in
the traced window."""


def read(ctx):
    tr, w = ctx["trace"], ctx["window"]
    if not tr or not tr["layer_programs"]["pack"] or not w["scenarios"]:
        return None
    return tr["layer_s"]["pack"] * 1e3 / w["scenarios"]
