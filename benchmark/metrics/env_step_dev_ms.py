"""Device self time under the scopes ``env.step`` + ``env.obs`` +
``rollout.reset`` (outermost scope of each operation; ``benchmark/
scopes.py``) per rollout of the traced window: training iterations plus
executions of ``_rollout``. Nothing where the program opens no scopes."""
UNIT = "ms/rollout"


def read(ctx):
    from benchmark import scopes
    return scopes.reduction(ctx).get("env_step_dev_ms")
