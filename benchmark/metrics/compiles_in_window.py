"""Backend compilations counted between the window's two boundaries
(``jax.monitoring`` listener of the harness). Nothing compiles inside a
sound window, so this reads 0; it is reported as the count it is."""
UNIT = "count"


def read(ctx):
    return ctx.window.compiles_in_window
