"""Plain reference of configuration ``agv16-kanana2-ep16``: member 0's
share of kanana-2-30b-a3b's published layers 0-4 as the QMIX agent's
trunk (16 AGVs x 4 MEC x 4 channels, 17 agent tokens at width 2048;
latent attention — 16 of 32 heads of [128 | 64] query/key and 128 value
dimensions over ONE 512-wide latent and ONE 64-wide rotary key a token,
both whole; experts 0-7 of 128 under the full 128-way sigmoid top-6
router with its selection bias, the two shared experts as one
feed-forward of 1536 and layer 0's dense feed-forward of 6144 whole;
layer 0 dense, 1-4 routed) with T2OMCA's mixer (35 tokens, one block at
2048) and the QMIX episode loss, float32 ``jax.numpy``
(``benchmark/reference/dsv3.py``) — the sizes stated here a second time,
independently of the program's config tree; ``check.py`` refuses a run
where ``SIZES`` and the program's disagree (``heads`` is the program's
unread default: a trunk's heads are ``TRUNK``'s)."""

from benchmark.reference import dsv3

SIZES = dict(n_agents=16, emb=2048, heads=3, depth=5, mixer_emb=2048,
             mixer_heads=16, mixer_depth=1, standard_heads=True,
             n_actions=5, n_mec=4)
GAMMA = 0.99
TRUNK = dict(q_heads=16, nope=128, rope=64, value=128, latent=512,
             experts=128, experts_held=8, expert_offset=0, top_k=6,
             route_scale=2.448, eps=1e-6, theta=1000000.0,
             layers=("dense", "experts", "experts", "experts", "experts"))


def episode_loss(params, target_params, batch, weights, *, prec="f32",
                 half_batch=False):
    return dsv3.episode_loss(params, target_params, batch, weights,
                             sizes=SIZES, trunk=TRUNK, gamma=GAMMA,
                             prec=prec, half_batch=half_batch)


def agent_qs(agent_params, batch, *, prec="f32"):
    return dsv3.unroll_agent(agent_params, batch, sizes=SIZES, trunk=TRUNK,
                             prec=prec)[0]
