"""Share of the traced window in which no operation ran on the device
(1 - union of the operations' intervals over the window; device trace)."""
UNIT = "%"


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
