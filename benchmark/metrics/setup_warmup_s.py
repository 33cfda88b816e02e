"""The ``run`` mark to the window's opening, less
``setup_first_dispatch_s``: the warm-up iterations as the device ran them —
the part of ``setup_s`` that follows ``env_steps_per_s``. A traced run's
holds the profiler's start (``benchmark/setup.py``)."""
UNIT = "s"


def read(ctx):
    from benchmark import setup
    return setup.read(ctx, "warmup_s")
