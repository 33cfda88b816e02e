"""Self time of the spans that build objects and should compile nothing —
``setup.build`` (``Experiment.build``), ``setup.programs`` (the jitted
wrappers), ``setup.telemetry`` and ``backend.init`` — before the ``run``
mark (``benchmark/setup.py``)."""
UNIT = "s"


def read(ctx):
    from benchmark import setup
    return setup.read(ctx, "build_s")
