"""Device self time under the scopes ``agent.attention`` + ``agent.latent``
(``models/trunk.py``: the attention sublayer of a catalog trunk — input
norm, projections, RoPE, scores, softmax, context, ``W_o``, the residual
add — with what latent attention adds inside it), whatever the outer
scope (``act.forward``, ``learner.agent``, ``learner.target``; the
mixer's blocks open ``agent.attention`` under ``learner.mixer`` too, and
are counted), per training iteration of the traced window
(``benchmark/moe.py``). ``None`` without a trace or where the program
opens no ``agent.latent`` (a grouped-query trunk, or the parent of the PR
that brought latent attention)."""
UNIT = "ms/iter"


def read(ctx):
    from benchmark import moe
    s = moe.inner_seconds(ctx)
    if ("agent.latent" not in s or "agent.attention" not in s
            or not ctx.window.iterations):
        return None
    return ((s["agent.attention"] + s["agent.latent"]) * 1e3
            / ctx.window.iterations)
