"""The whole step's share of the chip's bf16 peak for a configuration
with a catalog trunk: the operations the mathematics of the window's
periods requires (``configs/<config>.ops.py``: the trunk from shapes and
from the program's count of routed pairs held here, the mixer as
``benchmark/ops.py`` counts it; backward at twice forward, no
recomputation) over the window's time, the chips and the peak of
``benchmark/peaks.json``."""
UNIT = "%"


def read(ctx):
    from benchmark import moe
    from benchmark import ops as shared
    ops = moe.config_ops(ctx)
    c = moe.counters(ctx)
    if (ops is None or "moe_pairs_held" not in c
            or "moe_pairs_held_mean" not in c):
        return None
    cfg, w = ctx.cfg, ctx.window
    lanes = cfg.batch_size_run
    roll = c["moe_pairs_held_mean"] * lanes
    total = ops.period_flops(
        lanes=lanes, batch=cfg.batch_size, steps=cfg.env_args.episode_limit,
        period_iterations=ctx.cell.period_iterations, rollout_pairs=roll,
        test_pairs=c.get("test_moe_pairs_held_mean", roll / lanes) * lanes,
        update_pairs=c["moe_pairs_held"],
        mixer_step=shared.mixer_step(emb=cfg.model.mixer_emb,
                                     depth=cfg.model.mixer_depth,
                                     n_agents=cfg.env_args.agv_num),
    ) * w.iterations / ctx.cell.period_iterations
    return (100.0 * total / w.window_s / ctx.chips
            / moe.peaks(ctx)["bf16_flops_per_s"])
