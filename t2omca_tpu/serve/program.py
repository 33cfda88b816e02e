"""The serving program: greedy ``select_actions`` as ONE jitted step.

Serving is a different program than training (ROADMAP open item 5): no
exploration, no schedules, no env — just ``q = forward(params, obs,
hidden)`` masked-argmaxed over ``avail``. This module is the single
definition every serve surface builds from: the exporter lowers/compiles
it per batch bucket, the front-end dispatches it and the graftprog
registry audits it — so the program the ratchet pins is the program
traffic actually runs.

Bit-parity contract (the K=1-parity convention, pinned by
tests/test_serve.py): with f32 params the step's actions are
bit-identical to the training path's ``BasicMAC.select_actions(...,
test_mode=True)``. That holds by construction — in test mode both
selector families reduce to ``masked_argmax`` over the same
deterministic forward (epsilon is forced to 0; the noisy head takes its
mu-weight eval path), so the serve step simply drops the dead key
plumbing instead of re-deriving the math.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..components.action_selectors import masked_argmax

#: batch bucket the compiled-program audit pins (analysis/registry.py):
#: small enough to lower in the tier-1 prelude budget, > 1 so the
#: batch axis is real
SERVE_AUDIT_BATCH = 4


def build_serve_step(mac):
    """→ jitted ``_serve_step(params, obs, avail, hidden) -> (actions,
    hidden')`` for a built ``BasicMAC``.

    ``params`` may be the raw agent variables or a
    ``prepare_acting_params`` pre-fold (the exporter ships the fold);
    ``obs (B, A, obs_dim)`` f32, ``avail (B, A, n_actions)`` bool/int,
    ``hidden (B, A, emb)``. Greedy and deterministic — no PRNG key in
    the signature, so the exported aval set is exactly the request
    surface. The entity-table acting path is deliberately NOT used:
    serving requests arrive as observation tensors, not env states, and
    the qslice forward is exact for the same params."""

    def _serve_step(params, obs, avail, hidden):
        # train-dtype forward (acting=False default): serving's dtype
        # story is the artifact's per-variant cast, NOT the training
        # run's model.act_dtype rollout knob — the exporter folds at the
        # train dtype for the same reason (export.py), so fold and
        # forward always agree and the f32 variant keeps its bit-parity
        # contract with the training path's greedy select_actions
        if mac.use_qslice:
            q, hidden = mac.forward_qslice(params, obs, hidden, key=None,
                                           deterministic=True)
        else:
            q, hidden = mac.forward(params, obs, hidden, key=None,
                                    deterministic=True)
        return masked_argmax(q, avail).astype(jnp.int32), hidden

    return jax.jit(_serve_step)


def serve_avals(mac, obs_dim: int, n_actions: int, batch: int):
    """The request-surface avals for one batch bucket: (obs, avail,
    hidden) ``ShapeDtypeStruct``s. One definition shared by the
    exporter, the audit hook and the front-end's padding, so the
    compiled fingerprint and the dispatched program can't drift."""
    a = mac.n_agents
    obs = jax.ShapeDtypeStruct((batch, a, obs_dim), jnp.float32)
    avail = jax.ShapeDtypeStruct((batch, a, n_actions), jnp.bool_)
    hidden = jax.eval_shape(lambda: mac.init_hidden(batch))
    return obs, avail, hidden


def register_audit_programs(ctx):
    """graftprog registry hook (analysis/registry.py): the greedy serve
    step at the audit config's scale, ratcheted like every other hot
    program — a FLOPs/bytes/fingerprint regression on the serving path
    fails the tier-1 gate statically, before any latency is measured.
    ``compile=True``: serving is latency-bound, so the peak-memory and
    optimized-HLO budgets matter and the program is small enough to
    compile inside the prelude budget."""
    from ..analysis.registry import AuditProgram
    mac = ctx.exp.mac
    env_info = ctx.exp.env.get_env_info()
    step = build_serve_step(mac)
    # train-dtype fold, like the exporter (act_dtype never reaches serving)
    params = jax.eval_shape(
        lambda p: mac.prepare_acting_params(p, dtype=mac.agent.dtype),
        ctx.ts_shape.learner.params["agent"])
    obs, avail, hidden = serve_avals(mac, env_info["obs_shape"],
                                     env_info["n_actions"],
                                     SERVE_AUDIT_BATCH)
    return {"serve_step": AuditProgram(
        step, (params, obs, avail, hidden), compile=True,
        description=f"greedy AOT serving step (B={SERVE_AUDIT_BATCH} "
                    f"bucket, pre-folded acting params)")}
