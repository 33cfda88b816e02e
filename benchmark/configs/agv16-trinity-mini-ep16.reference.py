"""Plain reference of configuration ``agv16-trinity-mini-ep16``: member
0's share of Trinity-Mini's published layers 1-5 as the QMIX agent's trunk
(16 AGVs x 4 MEC x 4 channels, 17 agent tokens at width 2048; 8 of 32
query heads on 1 of 4 key/value heads, experts 0-7 of 128 under the full
128-way sigmoid top-8 router with its selection bias, the shared expert
and layer 1's dense feed-forward of 6144 whole; layer 1 dense / sliding,
2 sliding, 3 full, 4 sliding, 5 sliding) with T2OMCA's mixer (35 tokens,
one block at 2048) and the QMIX episode loss, float32 ``jax.numpy``
(``benchmark/reference/afmoe.py``) — the sizes stated here a second time,
independently of the program's config tree; ``check.py`` refuses a run
where ``SIZES`` and the program's disagree (``heads`` is the program's
unread default: a trunk's heads are ``TRUNK``'s)."""

from benchmark.reference import afmoe

SIZES = dict(n_agents=16, emb=2048, heads=3, depth=5, mixer_emb=2048,
             mixer_heads=16, mixer_depth=1, standard_heads=True,
             n_actions=5, n_mec=4)
GAMMA = 0.99
TRUNK = dict(head_dim=128, q_heads=8, kv_heads=1, experts=128,
             experts_held=8, expert_offset=0, top_k=8, route_scale=2.826,
             eps=1e-5, theta=10000.0, window=2048,
             layers=(("dense", "sliding"), ("experts", "sliding"),
                     ("experts", "full"), ("experts", "sliding"),
                     ("experts", "sliding")))


def episode_loss(params, target_params, batch, weights, *, prec="f32",
                 half_batch=False):
    return afmoe.episode_loss(params, target_params, batch, weights,
                              sizes=SIZES, trunk=TRUNK, gamma=GAMMA,
                              prec=prec, half_batch=half_batch)


def agent_qs(agent_params, batch, *, prec="f32"):
    return afmoe.unroll_agent(agent_params, batch, sizes=SIZES, trunk=TRUNK,
                              prec=prec)[0]
