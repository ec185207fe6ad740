"""Serve dispatches (the program's ``dispatch_counts()``: Pallas serve
chunks, fused-scan chunks and batched fused-scan chunks) per scenario
completed in the window."""


def read(ctx):
    w = ctx["window"]
    if not w["scenarios"]:
        return None
    return w["serve_dispatches"] / w["scenarios"]
