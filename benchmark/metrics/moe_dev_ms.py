"""Device self time under the scopes ``agent.router`` + ``agent.experts``
(``models/trunk.py``), whatever the outer scope (``act.forward``,
``learner.agent``, ``learner.target``), per training iteration of the
traced window (``benchmark/moe.py``)."""
UNIT = "ms/iter"


def read(ctx):
    from benchmark import moe
    s = moe.inner_seconds(ctx)
    hit = [s[n] for n in ("agent.router", "agent.experts") if n in s]
    if not hit or not ctx.window.iterations:
        return None
    return sum(hit) * 1e3 / ctx.window.iterations
