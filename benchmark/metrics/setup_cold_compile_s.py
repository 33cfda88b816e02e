"""Seconds of the ``compile`` marks before the window's opening that took a
second or more (JAX's threshold for writing a cache entry) and were not
served by the persistent cache: what the cache could have held and did
not — near 0 in a warm run; the number that tells an evicted cache from a
slower program (``benchmark/setup.py``)."""
UNIT = "s"


def read(ctx):
    from benchmark import setup
    return setup.read(ctx, "cold_compile_s")
