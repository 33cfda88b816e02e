"""The attention sublayer's needed time — per call the larger of
operations over the bf16 peak and bytes over the HBM peak, FROM SHAPES
ALONE (``configs/<config>.ops.py:attention_needed_s``, ``peaks.json``):
a property of the program, not of a run's routing — over the device self
time under ``agent.attention`` + ``agent.latent`` in the traced window.
Plain XLA products, no kernel of the repo's; the norms, RoPE, the
softmax, the residual add and, in the learner, the recomputed forward
are the share's shortfall. ``None`` where the configuration brings no
such count or the program opens no ``agent.latent``."""
UNIT = "%"


def read(ctx):
    from benchmark import moe
    ops = moe.config_ops(ctx)
    s = moe.inner_seconds(ctx)
    if (ops is None or not hasattr(ops, "attention_needed_s")
            or "agent.latent" not in s or "agent.attention" not in s):
        return None
    train, test = moe.rollouts_in_window(ctx)
    needed = ops.attention_needed_s(
        lanes=ctx.cfg.batch_size_run, batch=ctx.cfg.batch_size,
        steps=ctx.cfg.env_args.episode_limit, rollouts=train + test,
        updates=train, peak=moe.peaks(ctx))
    return 100.0 * needed / (s["agent.attention"] + s["agent.latent"])
