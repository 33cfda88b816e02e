"""Loss-scale levers (config.py: td_loss / huber_delta / reward_unit).

Per-step rewards are O(10^2) so the default MSE drives
grad_norm to 1e4-1e5 against grad_norm_clip=10 — every update is clipped to
a direction-only step. These tests pin the two flag-gated remedies:

- ``td_loss="huber"`` (2x-scaled Huber): exactly the MSE inside
  ``|td| <= huber_delta`` and linear outside, so delta->inf IS the MSE and
  each TD element's gradient contribution is bounded by 2*delta.
- ``reward_unit=u``: training with it is bit-identical to training with
  rewards pre-divided by u (static unit change, no state).

Both default OFF; the defaults-guard test keeps every parity config and all
committed learning evidence byte-identical.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from t2omca_tpu.components import PrioritizedReplayBuffer
from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                               TrainConfig, sanity_check)
from t2omca_tpu.controllers import BasicMAC
from t2omca_tpu.envs.registry import make_env
from t2omca_tpu.learners import QMixLearner


@pytest.fixture(scope="module")
def setup():
    cfg = sanity_check(TrainConfig(
        batch_size_run=2, batch_size=3,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6, fast_norm=False),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=10),
    ))
    env = make_env(cfg.env_args)
    info = env.get_env_info()
    mac = BasicMAC.build(cfg, info)
    learner = QMixLearner.build(cfg, mac, info)
    ls = learner.init_state(jax.random.PRNGKey(0))

    from t2omca_tpu.runners import ParallelRunner
    runner = ParallelRunner(env, mac, cfg)
    rs = runner.init_state(jax.random.PRNGKey(1))
    run = jax.jit(runner.run, static_argnames="test_mode")
    rs, batch, _ = run(ls.params["agent"], rs, test_mode=False)
    buf = PrioritizedReplayBuffer(
        capacity=10, episode_limit=cfg.env_args.episode_limit,
        n_agents=info["n_agents"], n_actions=info["n_actions"],
        obs_dim=info["obs_shape"], state_dim=info["state_shape"],
        alpha=0.6, beta0=0.4, t_max=1000)
    bs = buf.insert_episode_batch(buf.init(), batch)
    sample, idx, w = buf.sample(bs, jax.random.PRNGKey(2), cfg.batch_size, 0)
    return cfg, learner, ls, sample, w


def _with_cfg(learner, **kw):
    return dataclasses.replace(learner, cfg=learner.cfg.replace(**kw))


def _loss_and_grads(learner, ls, sample, w):
    grads, info = jax.grad(learner._loss, has_aux=True)(
        ls.params, ls.target_params, sample, w)
    import optax
    return float(info["loss"]), float(optax.global_norm(grads)), grads


def test_levers_off_by_default():
    cfg = TrainConfig()
    assert cfg.td_loss == "mse"
    assert cfg.reward_unit == 1.0


@pytest.mark.slow   # huge-delta recompile (~12 s); the gradient-bound huber test stays in-gate
def test_huber_inf_delta_matches_mse(setup):
    cfg, learner, ls, sample, w = setup
    l_mse, g_mse, grads_mse = _loss_and_grads(learner, ls, sample, w)
    hub = _with_cfg(learner, td_loss="huber", huber_delta=1e9)
    l_h, g_h, grads_h = _loss_and_grads(hub, ls, sample, w)
    assert l_h == l_mse
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 grads_mse, grads_h)


def test_huber_bounds_gradient_scale(setup):
    cfg, learner, ls, sample, w = setup
    # inflate rewards 1000x: the MSE gradient explodes linearly with the
    # TD scale; the Huber gradient is bounded per element by 2*delta
    big = dataclasses.replace(sample, reward=sample.reward * 1000.0)
    _, g_mse, _ = _loss_and_grads(learner, ls, big, w)
    hub = _with_cfg(learner, td_loss="huber", huber_delta=1.0)
    _, g_h, _ = _loss_and_grads(hub, ls, big, w)
    assert g_h < g_mse / 50.0
    # and it is still a descent signal, not zero
    assert g_h > 0.0


def test_reward_unit_equals_prescaled_rewards(setup):
    cfg, learner, ls, sample, w = setup
    u = 100.0
    lev = _with_cfg(learner, reward_unit=u)
    l_a, g_a, grads_a = _loss_and_grads(lev, ls, sample, w)
    pre = dataclasses.replace(sample, reward=sample.reward / u)
    l_b, g_b, grads_b = _loss_and_grads(learner, ls, pre, w)
    assert l_a == l_b
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 grads_a, grads_b)


def test_reward_unit_shrinks_gradients(setup):
    cfg, learner, ls, sample, w = setup
    _, g_raw, _ = _loss_and_grads(learner, ls, sample, w)
    lev = _with_cfg(learner, reward_unit=100.0)
    _, g_u, _ = _loss_and_grads(lev, ls, sample, w)
    assert g_u < g_raw


def test_train_step_with_levers_runs_and_is_finite(setup):
    cfg, learner, ls, sample, w = setup
    lev = _with_cfg(learner, td_loss="huber", huber_delta=10.0,
                    reward_unit=100.0)
    ls2, info = jax.jit(lev.train)(ls, sample, w, jnp.asarray(0),
                                   jnp.asarray(2))
    assert np.isfinite(float(info["loss"]))
    assert np.isfinite(float(info["grad_norm"]))
    changed = jax.tree.map(lambda a, b: not np.allclose(a, b),
                           ls.params, ls2.params)
    assert any(jax.tree.leaves(changed))


def _mixer_inputs(emb=16, a=3, n_ent=3, feat=8, b=4):
    k = jax.random.PRNGKey(5)
    return (jax.random.normal(jax.random.fold_in(k, 0), (b, 1, a)),
            jax.random.normal(jax.random.fold_in(k, 1), (b, a, emb)),
            jax.random.normal(jax.random.fold_in(k, 2), (b, 3, emb)),
            jax.random.normal(jax.random.fold_in(k, 3), (b, n_ent * feat)),
            jax.random.normal(jax.random.fold_in(k, 4),
                              (b, a, n_ent * feat)))


def test_mixer_zero_init_gate_outputs_zero_and_learns():
    """mixer_zero_init: q_tot is EXACTLY 0 at init (the O(emb) readout
    init scale is gated away), the recurrent hyper tokens are untouched,
    and the gate parameter receives gradient (it can open)."""
    from t2omca_tpu.models.mixer import TransformerMixer

    emb, a, n_ent, feat = 16, 3, 3, 8
    qv, hid, hyper, st, obs = _mixer_inputs(emb, a, n_ent, feat)
    kw = dict(n_agents=a, n_entities=n_ent, feat_dim=feat, emb=emb,
              heads=2, depth=2, state_entity_mode=True)
    gated = TransformerMixer(zero_init_gate=True, **kw)
    plain = TransformerMixer(**kw)
    params = gated.init(jax.random.PRNGKey(7), qv, hid, hyper, st, obs)

    y, hy = gated.apply(params, qv, hid, hyper, st, obs)
    np.testing.assert_array_equal(np.asarray(y), 0.0)
    # ungated output from the SAME underlying weights is O(10+) — the
    # gate is doing real work
    p_plain = {"params": {k: v for k, v in params["params"].items()
                          if k != "out_gate"}}
    y_plain, hy_plain = plain.apply(p_plain, qv, hid, hyper, st, obs)
    assert float(np.abs(np.asarray(y_plain)).max()) > 1.0
    np.testing.assert_array_equal(np.asarray(hy), np.asarray(hy_plain))

    g = jax.grad(lambda p: gated.apply(p, qv, hid, hyper, st,
                                       obs)[0].sum())(params)
    assert float(np.abs(np.asarray(
        g["params"]["out_gate"])).max()) > 0.0


def test_mixer_gate_qslice_matches_dense():
    """The qslice mixer forward must honor the gate param (opened off its
    0-init so the equality is non-trivial)."""
    from t2omca_tpu.models.mixer import TransformerMixer
    from t2omca_tpu.ops.query_slice import mixer_forward_qslice

    emb, a, n_ent, feat = 16, 3, 3, 8
    qv, hid, hyper, st, obs = _mixer_inputs(emb, a, n_ent, feat)
    mixer = TransformerMixer(n_agents=a, n_entities=n_ent, feat_dim=feat,
                             emb=emb, heads=2, depth=2,
                             state_entity_mode=True, zero_init_gate=True)
    params = mixer.init(jax.random.PRNGKey(7), qv, hid, hyper, st, obs)
    params["params"]["out_gate"] = jnp.full((1,), 0.7)

    y_ref, hy_ref = mixer.apply(params, qv, hid, hyper, st, obs)
    y_qs, hy_qs = mixer_forward_qslice(
        params, qv, hid, hyper, st, obs,
        n_agents=a, n_entities=n_ent, feat_dim=feat, emb=emb,
        heads=2, depth=2, pos_func="abs", pos_func_beta=1.0,
        state_entity_mode=True)
    np.testing.assert_allclose(np.asarray(y_qs), np.asarray(y_ref),
                               rtol=5e-4, atol=5e-5)
    np.testing.assert_allclose(np.asarray(hy_qs), np.asarray(hy_ref),
                               rtol=5e-4, atol=5e-5)


def test_train_step_with_gate_opens_gate(setup):
    """e2e: a learner built with mixer_zero_init trains and moves the
    gate off zero — the recipe's full flag set in one step."""
    cfg, learner, ls, sample, w = setup
    from t2omca_tpu.controllers import BasicMAC
    from t2omca_tpu.envs.registry import make_env

    cfg2 = cfg.replace(td_loss="huber", huber_delta=10.0,
                       reward_unit=100.0,
                       model=dataclasses.replace(cfg.model,
                                                 mixer_zero_init=True))
    env = make_env(cfg2.env_args)
    info = env.get_env_info()
    mac = BasicMAC.build(cfg2, info)
    lrn = QMixLearner.build(cfg2, mac, info)
    ls2 = lrn.init_state(jax.random.PRNGKey(0))
    assert np.asarray(
        ls2.params["mixer"]["params"]["out_gate"]).item() == 0.0
    ls3, info3 = jax.jit(lrn.train)(ls2, sample, w, jnp.asarray(0),
                                    jnp.asarray(2))
    assert np.isfinite(float(info3["loss"]))
    assert np.abs(np.asarray(
        ls3.params["mixer"]["params"]["out_gate"])).item() > 0.0


def test_sanity_check_validates_lever_flags():
    with pytest.raises(ValueError, match="td_loss"):
        sanity_check(TrainConfig(td_loss="l1"))
    with pytest.raises(ValueError, match="huber_delta"):
        sanity_check(TrainConfig(td_loss="huber", huber_delta=0.0))
    with pytest.raises(ValueError, match="reward_unit"):
        sanity_check(TrainConfig(reward_unit=-1.0))
    with pytest.raises(ValueError, match="double-scale"):
        sanity_check(TrainConfig(
            reward_unit=100.0,
            env_args=EnvConfig(reward_scaling=True)))
