"""Seconds of backend compilation and persistent-cache retrieval before the
window's opening, as the program's own listener counted them: the ``run``
mark's process-wide ``compile_ms`` + ``cache_load_ms``, the same fields of
the spans after it, and the ``compile`` marks no span held
(``benchmark/setup.py``)."""
UNIT = "s"


def read(ctx):
    from benchmark import setup
    return setup.read(ctx, "compile_s")
