"""Share of ``setup_s`` in none of ``setup_entry_s``, ``setup_build_s``,
``setup_init_state_s``, ``setup_first_dispatch_s``, ``setup_warmup_s``:
the gaps between the program's spans before the ``run`` mark
(``benchmark/setup.py``)."""
UNIT = "%"


def read(ctx):
    from benchmark import setup
    return setup.read(ctx, "unattributed_pct")
