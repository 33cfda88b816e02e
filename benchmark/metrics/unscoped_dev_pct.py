"""Share of the device's busy time that no scope of the vocabulary
names (``benchmark/scopes.py``): compiler-made copies and loop control
outside every scope, and programs that open none."""
UNIT = "%"


def read(ctx):
    from benchmark import scopes
    return scopes.reduction(ctx).get("unscoped_dev_pct")
