"""Device self time under the ``learner.*`` scopes (``benchmark/
scopes.py``) per update that ran in the traced window: the operations of
``learner.optimizer`` run once an update, and count them."""
UNIT = "ms/update"


def read(ctx):
    from benchmark import scopes
    return scopes.reduction(ctx).get("learner_update_dev_ms")
