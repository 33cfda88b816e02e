"""Time per training iteration of the traced window in which no
operation runs on the device (device trace: window minus busy)."""
UNIT = "ms/iter"


def read(ctx):
    tr = ctx.trace
    if not tr or not ctx.window.iterations:
        return None
    return (tr["window_s"] - tr["busy_s"]) * 1e3 / ctx.window.iterations
