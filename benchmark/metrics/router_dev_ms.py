"""Device self time under the scope ``agent.router`` (``models/trunk.py``:
the router product, its scores, the selection bias, the top-k, the
renormalisation and the held experts' weights a token), whatever the
outer scope (``act.forward``, ``learner.agent``, ``learner.target``), per
training iteration of the traced window (``benchmark/moe.py``). ``None``
where the program opens no such scope."""
UNIT = "ms/iter"


def read(ctx):
    from benchmark import moe
    spent = moe.inner_seconds(ctx).get("agent.router")
    if not spent or not ctx.window.iterations:
        return None
    return spent * 1e3 / ctx.window.iterations
