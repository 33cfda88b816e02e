"""Share of the device's idle time that no host span covers, the
spans read from the profiler's own host plane and the device's gaps moved
onto its clock (``benchmark/scopes.py``)."""
UNIT = "%"


def read(ctx):
    from benchmark import scopes
    return scopes.reduction(ctx).get("idle_unattributed_pct")
