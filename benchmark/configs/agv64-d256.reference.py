"""Plain reference of configuration ``agv64-d256``: the T2OMCA agent and
mixer (64 AGVs x 8 MEC x 8 channels, width 256, 65 agent tokens, 131 mixer tokens) and the QMIX episode loss, float32 ``jax.numpy``
(``benchmark/reference``), at this configuration's published sizes —
stated here a second time, independently of the program's config tree;
``check.py`` refuses a run where the two disagree."""

from benchmark.reference import qmix

SIZES = dict(n_agents=64, emb=256, heads=4, depth=2, mixer_emb=256,
             mixer_heads=4, mixer_depth=2, standard_heads=True,
             n_actions=9, n_mec=8)
GAMMA = 0.99


def episode_loss(params, target_params, batch, weights, *, prec="f32",
                 half_batch=False):
    return qmix.episode_loss(params, target_params, batch, weights,
                             sizes=SIZES, gamma=GAMMA, prec=prec,
                             half_batch=half_batch)


def agent_qs(agent_params, batch, *, prec="f32"):
    return qmix.unroll_agent(agent_params, batch, sizes=SIZES, prec=prec)[0]
