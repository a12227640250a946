"""device_idle_share: the share of the traced window in which no
operation runs on the device (1 - union of its op intervals / window),
averaged over the chips."""


def read(ctx):
    if not ctx.get("window_s"):
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
