"""Process start to the start of the program's first span (``backend.init``,
opened by ``run.run``): the interpreter's and the harness's imports, the
look for the chips (which starts the backend), ``sanity_check`` and the
logger's sinks (``benchmark/setup.py``)."""
UNIT = "s"


def read(ctx):
    from benchmark import setup
    return setup.read(ctx, "entry_s")
