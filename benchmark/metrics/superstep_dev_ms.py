"""Device time of ``jit(_superstep)`` in the traced window, per training iteration
(device trace, the program line). Nothing to read where the cell's loop
does not dispatch that program."""
UNIT = "ms/iter"


def read(ctx):
    p = (ctx.trace or {}).get("programs", {}).get("_superstep")
    if not p or not p["runs"]:
        return None
    return p["seconds"] * 1e3 / ctx.window.iterations
