"""``benchmark/ops.py`` against counts made by hand at two small shapes,
and the dense count against the sliced one by the factor the formula
predicts."""

import pytest

from benchmark import ops


@pytest.mark.parametrize("emb,tokens,want", [
    # 32 E^2 + 4 (N+1) E
    (4, 3, 32 * 16 + 4 * 3 * 4),          # 560
    (8, 5, 32 * 64 + 4 * 5 * 8),          # 2208
])
def test_sliced_block_by_hand(emb, tokens, want):
    assert ops.sliced_agent_block(emb, tokens) == want


def test_agent_step_by_hand():
    # E=4, A=2 (3 tokens), depth 1, 3 actions, 9 features:
    # embed 2*(2*9*4)=144, block 560, q head 2*4*3=24
    assert ops.agent_step(emb=4, depth=1, n_agents=2, n_actions=3) == 728
    # E=8, A=4 (5 tokens), depth 2, 5 actions: 2*(2*9*8)=288 + 2*2208 + 80
    assert ops.agent_step(emb=8, depth=2, n_agents=4, n_actions=5) == 4784


def test_mixer_step_by_hand():
    # E=4, A=2: M=7 tokens, R=5 rows, depth 1, 8 features
    # embed 2*8*4*2=128; q 2*16*5=160; kv 4*16*7=448; attn 4*5*7*4=560;
    # unify 160; ff 16*16*5=1280; readout 2*2*4+8+8=32
    assert ops.mixer_step(emb=4, depth=1, n_agents=2) == 2768


def test_period_adds_up():
    s = dict(emb=8, depth=2, mixer_emb=8, mixer_depth=1, n_agents=4,
             n_actions=5, lanes=3, steps=6, batch=2)
    agent = dict(emb=8, depth=2, n_agents=4, n_actions=5)
    roll = 3 * 6 * 4 * 4784
    ag = 2 * 7 * 4 * 4784
    mx = ops.mixer_step(emb=8, depth=1, n_agents=4)
    learn = 3 * (ag + 2 * 6 * mx) + (ag + 2 * 7 * mx)
    assert ops.rollout(lanes=3, steps=6, **agent) == roll
    assert ops.period(s, 4) == 4 * (roll + learn) + roll


@pytest.mark.parametrize("emb,n_agents", [(256, 64), (128, 16)])
def test_dense_exceeds_sliced_by_the_formulas_factor(emb, n_agents):
    t = n_agents + 1
    factor = (24 * emb * emb * t + 4 * t * t * emb) / (32 * emb * emb
                                                      + 4 * t * emb)
    got = ops.dense_agent_block(emb, t) / ops.sliced_agent_block(emb, t)
    assert got == pytest.approx(factor)
    assert factor > 10                     # 49.3 at config 3, 12.6 at config 2
    dense = ops.agent_step(emb=emb, depth=2, n_agents=n_agents, n_actions=9,
                           dense=True)
    sliced = ops.agent_step(emb=emb, depth=2, n_agents=n_agents, n_actions=9)
    assert dense / sliced == pytest.approx(factor, rel=0.05)
