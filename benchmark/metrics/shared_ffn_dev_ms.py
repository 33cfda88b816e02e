"""Device self time under the scopes ``agent.shared`` (a shared expert's
three products) + ``agent.dense`` (a dense layer's feed-forward) of
``models/trunk.py`` — what every chip of a layer group computes alike,
whatever the share — under any outer scope, per training iteration of the
traced window (``benchmark/moe.py``). ``None`` where the program opens
neither scope (a trunk without them, or the parent of the PR that brought
them)."""
UNIT = "ms/iter"


def read(ctx):
    from benchmark import moe
    s = moe.inner_seconds(ctx)
    hit = [s[n] for n in ("agent.shared", "agent.dense") if n in s]
    if not hit or not ctx.window.iterations:
        return None
    return sum(hit) * 1e3 / ctx.window.iterations
