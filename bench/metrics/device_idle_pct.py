"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device-op intervals over the window."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0 or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
