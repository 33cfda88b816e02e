"""Wall time of the ``dispatch.*`` spans between the ``run`` mark and the
window's opening that carry a compilation: trace, lowering and backend
compile or cache load of each loop program, as a rule its first call
(``benchmark/setup.py``)."""
UNIT = "s"


def read(ctx):
    from benchmark import setup
    return setup.read(ctx, "first_dispatch_s")
