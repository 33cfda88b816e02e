"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip at the
window's close, in GB (1e9). What the counter includes of a program's
temporaries is an open question (PERF.md), hence a per-layer reading."""
UNIT = "GB"


def read(ctx):
    peak = ctx.window.memory.get("peak_bytes_in_use")
    return None if not peak else peak / 1e9
