"""Device self time under ``rollout.store`` + ``replay.insert`` +
``replay.sample`` + ``replay.priority`` (``benchmark/scopes.py``) per
training iteration of the traced window."""
UNIT = "ms/iter"


def read(ctx):
    from benchmark import scopes
    return scopes.reduction(ctx).get("replay_dev_ms")
