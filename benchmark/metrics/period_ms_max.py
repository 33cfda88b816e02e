"""The longest period of the window: host clock at ``driver.iteration``,
boundary to boundary (the last boundary's clock is read after the
closing device barrier). Stands beside the rate, which a stall lowers."""
UNIT = "ms"


def read(ctx):
    t = [clock for _, clock in ctx.window.boundaries]
    if len(t) < 2:
        return None
    return max(b - a for a, b in zip(t, t[1:])) * 1e3
