"""Self time of ``setup.init_state`` + ``setup.restore``: the train state
made leaf by leaf from the seed (or its template and a checkpoint's load)
and the driver's key (``benchmark/setup.py``)."""
UNIT = "s"


def read(ctx):
    from benchmark import setup
    return setup.read(ctx, "init_state_s")
