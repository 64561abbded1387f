"""idle_share.serve: share of the traced window, in %, in which no
operation ran on the device (1 - union of device-op intervals / window).
Moves ``tpot_p95_ms``."""


def read(ctx):
    red = ctx.reduced
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
