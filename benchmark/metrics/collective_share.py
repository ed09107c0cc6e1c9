"""Collective operations' device time over the traced window, device 0, %."""


def read(ctx):
    if not ctx.trace or ctx.trace["devices"] < 2:
        return None
    return 100.0 * ctx.trace["collective_s"] / ctx.trace["window_s"]
