"""device.idle_share: the share of the traced window in which no
operation ran on the chip (1 − busy / window, busy the union of the
operation intervals)."""


def read(ctx):
    t = ctx.trace
    if t["window_s"] <= 0 or not t["ops"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
