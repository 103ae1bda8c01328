"""device_idle_share: 1 - (union of device op intervals) / traced window (%)."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
