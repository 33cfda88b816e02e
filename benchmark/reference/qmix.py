"""Plain reference of the QMIX episode loss, its gradients and the
optimizer step (the source's ``per_run.py`` learner, never released in
full; the mathematics as ``tests/oracle_torch.py`` states it), in
``jax.numpy`` / float32, independent of the program.

Double-Q targets under the availability mask, both recurrent streams
(agent hidden token, mixer hyper tokens) carried from t = 0, the target
mixer unrolled over all T+1 steps with outputs [1:] as bootstraps,
time-limit steps bootstrap, importance-weighted masked MSE; gradient
clipped by its global norm, then Adam.

Computed one timestep at a time (``lax.scan`` with ``jax.checkpoint``) so
that the dense forward over every token fits beside the program's state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import model


def unroll_agent(p, batch, *, sizes, prec: str = "f32"):
    """The agent over the steps of ``batch`` (rows, mec, mean, std,
    time-major), its hidden token carried from zero at the first →
    (Q-values ``(steps, B, A, n_actions)``, hidden ``(steps, B, A, E)``)."""
    kw = dict(heads=sizes["heads"], depth=sizes["depth"],
              standard_heads=sizes["standard_heads"], prec=prec)
    _, b, a = batch["mec"].shape

    def step(h, xs):
        rows, mec, mean, std = xs
        obs = model.entity_obs(rows, mec, mean, std)
        q, h = model.agent_forward(p, obs, h, **kw)
        return h, (q, h)
    _, (qs, hs) = jax.lax.scan(
        jax.checkpoint(step), jnp.zeros((b, a, sizes["emb"]), jnp.float32),
        (batch["rows"], batch["mec"], batch["mean"], batch["std"]))
    return qs, hs


def episode_loss(params, target_params, batch, weights, *, sizes,
                 gamma: float, prec: str = "f32", half_batch: bool = False):
    """→ (loss, aux). ``batch`` (time-major, float32 unless noted):
    rows ``(T+1, B, A, 8)``, mec ``(T+1, B, A)`` int, mean/std
    ``(T+1, B, A, 9)``, state ``(T+1, B, A*8)``, avail ``(T+1, B, A, n)``
    bool, actions ``(T, B, A)`` int, reward/terminated/filled ``(T, B)``.
    ``params`` = {"agent": tree, "mixer": tree} (flax ``params`` dicts).

    ``half_batch`` plants the fault "half of the batch left out, the mean
    taken over the rest" (for the fault readings; never in a run)."""
    mkw = dict(n_agents=sizes["n_agents"], heads=sizes["mixer_heads"],
               depth=sizes["mixer_depth"],
               standard_heads=sizes["standard_heads"], prec=prec)
    t1, b, a = batch["mec"].shape
    memb = sizes["mixer_emb"]

    qs, hs = unroll_agent(params["agent"], batch, sizes=sizes, prec=prec)
    tqs, ths = jax.lax.stop_gradient(
        unroll_agent(target_params["agent"], batch, sizes=sizes, prec=prec))

    chosen = jnp.take_along_axis(
        qs[:-1], batch["actions"][..., None], axis=-1)[..., 0]
    best = jnp.argmax(jnp.where(batch["avail"], qs, -jnp.inf), axis=-1)
    target_max = jnp.take_along_axis(tqs, best[..., None], axis=-1)[..., 0]

    state_ent = batch["state"].reshape(t1, b, a, -1)

    def unroll_mixer(p, qv, hid, ent):
        def step(hyper, xs):
            q_t, h_t, s_t = xs
            y, hyper = model.mixer_forward(p, q_t, h_t, hyper, s_t, **mkw)
            return hyper, y
        _, ys = jax.lax.scan(jax.checkpoint(step),
                             jnp.zeros((b, 3, memb), jnp.float32),
                             (qv, hid, ent))
        return ys                                       # (len, B)

    q_tot = unroll_mixer(params["mixer"], chosen, hs[:-1], state_ent[:-1])
    target_q_tot = jax.lax.stop_gradient(unroll_mixer(
        target_params["mixer"], target_max, ths, state_ent))[1:]

    term = batch["terminated"].astype(jnp.float32)
    mask = batch["filled"].astype(jnp.float32)
    if half_batch:
        mask = mask * (jnp.arange(b) < b // 2)[None, :]
    targets = batch["reward"] + gamma * (1.0 - term) * target_q_tot
    td = (q_tot - targets) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (weights[None, :] * td ** 2).sum() / denom
    aux = {
        "td_errors_abs": jnp.abs(td).sum(0) / jnp.maximum(mask.sum(0), 1.0),
        "q_taken_mean": (chosen.mean(-1) * mask).sum() / denom,
        "target_mean": (targets * mask).sum() / denom,
    }
    return loss, aux


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    """Scale the whole gradient so that its global norm is at most
    ``max_norm`` (unchanged when already smaller)."""
    g = global_norm(grads)
    scale = jnp.where(g < max_norm, 1.0, max_norm / g)
    return jax.tree.map(lambda x: x * scale, grads)


def adam_step(params, grads, mu, nu, count, *, lr, b1=0.9, b2=0.999,
              eps=1e-8):
    """One Adam update (Kingma & Ba, bias-corrected, eps outside the
    root) → (params', mu', nu'); ``count`` = steps taken before."""
    t = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** t
    c2 = 1 - b2 ** t
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return new, mu, nu


def adam_undo(params_after, mu_after, nu_after, count_after, *, lr,
              b1=0.9, b2=0.999, eps=1e-8):
    """The parameters BEFORE the last Adam step, from the state after it:
    Adam's update depends only on the moments after the step, so it can
    be taken back exactly (to one rounding)."""
    c1 = 1 - b1 ** count_after
    c2 = 1 - b2 ** count_after
    return jax.tree.map(
        lambda p, m, v: p + lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params_after, mu_after, nu_after)
