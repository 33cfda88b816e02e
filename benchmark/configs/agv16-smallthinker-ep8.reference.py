"""Plain reference of configuration ``agv16-smallthinker-ep8``: member 0's
share of SmallThinker-21BA3B-Instruct's layers 0-3 as the QMIX agent's
trunk (16 AGVs x 4 MEC x 4 channels, 17 agent tokens at width 2560; 7 of
28 query heads on 1 of 4 key/value heads, experts 0-7 of 64 under the
full 64-way top-6 router) with T2OMCA's mixer (35 tokens, one block at
2560) and the QMIX episode loss, float32 ``jax.numpy``
(``benchmark/reference/trunk.py``) — the sizes stated here a second time,
independently of the program's config tree; ``check.py`` refuses a run
where ``SIZES`` and the program's disagree (``heads`` is the program's
unread default: a trunk's heads are ``TRUNK``'s)."""

from benchmark.reference import trunk

SIZES = dict(n_agents=16, emb=2560, heads=3, depth=4, mixer_emb=2560,
             mixer_heads=20, mixer_depth=1, standard_heads=True,
             n_actions=5, n_mec=4)
GAMMA = 0.99
TRUNK = dict(head_dim=128, q_heads=7, kv_heads=1, layers=4, experts=64,
             experts_held=8, expert_offset=0, top_k=6, eps=1e-6,
             rope=(0, 1, 1, 1), window=(0, 4096, 4096, 4096), theta=1.5e6)


def episode_loss(params, target_params, batch, weights, *, prec="f32",
                 half_batch=False):
    return trunk.episode_loss(params, target_params, batch, weights,
                              sizes=SIZES, trunk=TRUNK, gamma=GAMMA,
                              prec=prec, half_batch=half_batch)


def agent_qs(agent_params, batch, *, prec="f32"):
    return trunk.unroll_agent(agent_params, batch, sizes=SIZES, trunk=TRUNK,
                              prec=prec)[0]
