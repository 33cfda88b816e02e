"""``tiny_afmoe.make`` / ``run`` for a configuration with a
``deepseek_v3`` catalog trunk (``model.trunk`` with ``model_type:
deepseek_v3``: latent attention): a throw-away cell ``tinydsv3.train`` on
the classic three-program loop — a tiny share of published layers 0-2 of
a tiny model (a dense layer and two routed ones; 2 of 4 heads of [8 | 4]
query/key and 6 value dimensions over a latent of 12; 4 of 8 experts,
3 a token, two shared experts) as the agent's stack, its reference
through ``benchmark/reference/dsv3.py``. ``tiny_afmoe`` reads its cell's
name, trunk, reference and metrics from its module globals when called;
they are this module's for the length of a call."""

from unittest import mock

from benchmark.tests import tiny_afmoe

NOPE, ROPE, VALUE, RANK = 8, 4, 6, 12
TRUNK = {"model_type": "deepseek_v3", "hidden_size": tiny_afmoe.D,
         "head_dim": ROPE, "num_attention_heads": 4,
         "num_key_value_heads": 4, "num_hidden_layers": 3,
         "first_k_dense_replace": 1, "intermediate_size": 24,
         "moe_intermediate_size": 8, "n_routed_experts": 8,
         "num_experts_per_tok": 3, "n_shared_experts": 2,
         "routed_scaling_factor": 2.5, "kv_lora_rank": RANK,
         "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE,
         "qk_head_dim": NOPE + ROPE, "v_head_dim": VALUE,
         "rope_theta": 100.0, "experts_held": 4, "heads_held": 2,
         "share_index": 0, "first_layer": 0}

REFERENCE = '''"""Plain reference of the throw-away configuration."""
from benchmark.reference import dsv3

SIZES = dict(n_agents=%(agents)d, emb=16, heads=3, depth=3, mixer_emb=16,
             mixer_heads=2, mixer_depth=1, standard_heads=True, n_actions=3,
             n_mec=2)
GAMMA = 0.99
TRUNK = dict(q_heads=2, nope=8, rope=4, value=6, latent=12, experts=8,
             experts_held=4, expert_offset=0, top_k=3, route_scale=2.5,
             eps=1e-6, theta=100.0, layers=("dense", "experts", "experts"))


def episode_loss(params, target_params, batch, weights, *, prec="f32",
                 half_batch=False):
    return dsv3.episode_loss(params, target_params, batch, weights,
                             sizes=SIZES, trunk=TRUNK, gamma=GAMMA,
                             prec=prec, half_batch=half_batch)


def agent_qs(agent_params, batch, *, prec="f32"):
    return dsv3.unroll_agent(agent_params, batch, sizes=SIZES, trunk=TRUNK,
                             prec=prec)[0]
'''

#: the per-layer metrics the cell of this family reports, as
#: BENCHMARK.json has them
METRICS = tiny_afmoe.METRICS + ("attention_dev_ms", "latent_dev_ms",
                                "attention_roofline_pct")
NAME = "tinydsv3"


def _as_this_family():
    return mock.patch.multiple(tiny_afmoe, NAME=NAME, TRUNK=TRUNK,
                               REFERENCE=REFERENCE, METRICS=METRICS)


def make(**kw) -> str:
    """→ root of a temporary checkout with the cell ``tinydsv3.train``."""
    with _as_this_family():
        return tiny_afmoe.make(**kw)


def run(root: str, **kw):
    with _as_this_family():
        return tiny_afmoe.run(root, **kw)
