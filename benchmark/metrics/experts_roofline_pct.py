"""The expert products' needed time — per call the larger of operations
over the bf16 peak and bytes over the HBM peak, from the program's own
count of pairs held (``configs/<config>.ops.py``, ``peaks.json``) — over
the device self time under ``agent.experts`` in the traced window. The
program runs every held expert over every token (its time must not
follow the routing); the pairs no token chose, the norm and, in the
learner, the recomputed forward are the share's shortfall."""
UNIT = "%"


def read(ctx):
    from benchmark import moe
    ops = moe.config_ops(ctx)
    spent = moe.inner_seconds(ctx).get("agent.experts")
    c = moe.counters(ctx)
    if (ops is None or not spent or "moe_pairs_held" not in c
            or "moe_pairs_held_mean" not in c):
        return None
    lanes = ctx.cfg.batch_size_run
    train, test = moe.rollouts_in_window(ctx)
    roll = c["moe_pairs_held_mean"] * lanes
    test_roll = c.get("test_moe_pairs_held_mean", 0.0) * lanes
    rollouts = train + (test * test_roll / roll if roll else 0.0)
    needed = ops.experts_needed_s(
        rollout_pairs=roll, rollouts=rollouts,
        update_pairs=c["moe_pairs_held"], updates=train,
        steps=ctx.cfg.env_args.episode_limit, peak=moe.peaks(ctx))
    return 100.0 * needed / spent
