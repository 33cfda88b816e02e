"""Device self time under the scope ``agent.latent`` (``models/trunk.py``:
the down-projection to the key/value latent and the shared rotary key,
the latent's norm, the up-projection to the held heads' no-position keys
and values — what latent attention does that grouped-query attention
does not), whatever the outer scope, per training iteration of the
traced window (``benchmark/moe.py``). ``None`` where the program opens
no such scope."""
UNIT = "ms/iter"


def read(ctx):
    from benchmark import moe
    spent = moe.inner_seconds(ctx).get("agent.latent")
    if not spent or not ctx.window.iterations:
        return None
    return spent * 1e3 / ctx.window.iterations
