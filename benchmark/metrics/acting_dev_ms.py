"""Device self time under the scopes ``act.forward`` + ``act.select``
(``benchmark/scopes.py``) per rollout of the traced window: training
iterations plus executions of ``_rollout``."""
UNIT = "ms/rollout"


def read(ctx):
    from benchmark import scopes
    return scopes.reduction(ctx).get("acting_dev_ms")
