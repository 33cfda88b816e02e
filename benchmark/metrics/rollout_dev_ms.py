"""Device time of ``jit(_rollout)`` in the traced window, per execution (training and test mode carry one name)
(device trace, the program line). Nothing to read where the cell's loop
does not dispatch that program."""
UNIT = "ms/run"


def read(ctx):
    p = (ctx.trace or {}).get("programs", {}).get("_rollout")
    if not p or not p["runs"]:
        return None
    return p["seconds"] * 1e3 / (p["runs"] / (ctx.trace.get("n_planes") or 1))
