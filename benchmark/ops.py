"""Operations the mathematics of one step requires, from shapes — the one
source of operation counts for ``step_mfu_pct``. A multiply-add counts as
two operations. Whatever implements the step is held to this count.

**The count is of the cheapest exact association of the published
mathematics**, not of the dense block over every token:

* keys and values of every block are projections of the *layer-0* tokens
  (the source threads the original keys through the stack), and only
  token 0 of the agent's output — the mixer's ``A + 3`` read-out rows —
  is consumed, so only those query rows go through the blocks;
* an env-step has only ``2 A`` distinct entity tokens (each entity once
  as visible, once as masked; the is-self flag is a rank-one correction),
  shared by its ``A`` observers: their embedding and key/value
  projections are counted once per env-step, i.e. twice per agent-step.

Per agent-step and block, with ``E`` the width and ``N + 1`` the tokens
(``N = A`` entities and the hidden token):

    2 E^2   query projection of the one consumed row
    2 E^2   key projection of the agent's own hidden token   } x 2
    4 E^2   key projection of the 2A entity tokens / A agents} (keys, values)
    4 (N + 1) E   logits against, and the weighted sum of, N + 1 tokens
    2 E^2   unify heads
    16 E^2  feed-forward (E -> 4E -> E)

    = 32 E^2 + 4 (N + 1) E                                    (sliced)

against ``24 E^2 (N + 1) + 4 (N + 1)^2 E`` for the dense block over every
token (``dense_agent_block``), which no exact implementation needs: at
config 3 it is 49 times the sliced count and would read near 480% of the
chip's peak on PR 21's timing.

Not counted: recomputation under ``model.remat``, the env step, the
normaliser, action selection, ring traffic, the optimizer's elementwise
update, softmax/LayerNorm/activation flops.
"""

from __future__ import annotations

FF_MULT = 4


def sliced_agent_block(emb: int, tokens: int) -> int:
    """One block, one agent-step, only token 0 carried (formula above)."""
    return 32 * emb * emb + 4 * tokens * emb


def dense_agent_block(emb: int, tokens: int) -> int:
    """One block over every token: q, k, v, unify (8 E^2) and the
    feed-forward (16 E^2) per token, logits and weighted sum."""
    return 24 * emb * emb * tokens + 4 * tokens * tokens * emb


def agent_step(*, emb: int, depth: int, n_agents: int, n_actions: int,
               feats: int = 9, dense: bool = False) -> int:
    """Forward operations of one agent-step (one agent, one env-step)."""
    tokens = n_agents + 1
    if dense:
        embed = 2 * feats * emb * n_agents
        blocks = depth * dense_agent_block(emb, tokens)
    else:
        embed = 2 * (2 * feats * emb)          # 2A entity tokens / A agents
        blocks = depth * sliced_agent_block(emb, tokens)
    return embed + blocks + 2 * emb * n_actions


def mixer_step(*, emb: int, depth: int, n_agents: int, feats: int = 8) -> int:
    """Forward operations of one mixer step (one episode, one timestep):
    ``M = 2A + 3`` layer-0 tokens (A state entities, A agent hiddens, 3
    hyper tokens), of which the ``R = A + 3`` read-out rows are carried."""
    m, r = 2 * n_agents + 3, n_agents + 3
    embed = 2 * feats * emb * n_agents
    per_block = (2 * emb * emb * r              # queries
                 + 2 * 2 * emb * emb * m        # keys and values
                 + 4 * r * m * emb              # logits, weighted sum
                 + 2 * emb * emb * r            # unify
                 + 4 * FF_MULT * emb * emb * r)  # feed-forward
    readout = 2 * n_agents * emb + 2 * emb + 2 * emb
    return embed + depth * per_block + readout


def rollout(*, lanes: int, steps: int, **agent) -> int:
    """Acting forward passes of one rollout (training or test)."""
    return lanes * steps * agent["n_agents"] * agent_step(**agent)


def learner(*, batch: int, steps: int, mixer_emb: int, mixer_depth: int,
            **agent) -> int:
    """One QMIX update: online and target agents over T + 1 steps, online
    mixer over T and target mixer over T + 1; the backward pass of the
    online networks at twice their forward."""
    a = agent["n_agents"]
    ag = batch * (steps + 1) * a * agent_step(**agent)
    mx = mixer_step(emb=mixer_emb, depth=mixer_depth, n_agents=a)
    online = ag + batch * steps * mx
    target = ag + batch * (steps + 1) * mx
    return 3 * online + target


def period(cfg_sizes: dict, period_iterations: int) -> int:
    """One period: ``period_iterations`` training iterations (a rollout
    and an update each) and the test rollout that follows them."""
    agent = dict(emb=cfg_sizes["emb"], depth=cfg_sizes["depth"],
                 n_agents=cfg_sizes["n_agents"],
                 n_actions=cfg_sizes["n_actions"])
    roll = rollout(lanes=cfg_sizes["lanes"], steps=cfg_sizes["steps"],
                   **agent)
    learn = learner(batch=cfg_sizes["batch"], steps=cfg_sizes["steps"],
                    mixer_emb=cfg_sizes["mixer_emb"],
                    mixer_depth=cfg_sizes["mixer_depth"], **agent)
    return period_iterations * (roll + learn) + roll


def sizes_of(cfg) -> dict:
    m, e = cfg.model, cfg.env_args
    return dict(emb=m.emb, depth=m.depth, mixer_emb=m.mixer_emb,
                mixer_depth=m.mixer_depth, n_agents=e.agv_num,
                n_actions=e.num_channels + 1, lanes=cfg.batch_size_run,
                steps=e.episode_limit, batch=cfg.batch_size)
