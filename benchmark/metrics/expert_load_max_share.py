"""``moe_load_max / moe_pairs_held`` of the training rollouts logged in
the window (``benchmark/moe.py``): per layer the busiest held expert's
pairs, summed over the layers, over all pairs held — 1 / experts held
under an even load, 1 where one expert takes everything."""
UNIT = "share"


def read(ctx):
    from benchmark import moe
    c = moe.counters(ctx)
    held = c.get("moe_pairs_held_mean")
    if not held or "moe_load_max_mean" not in c:
        return None
    return c["moe_load_max_mean"] / held
