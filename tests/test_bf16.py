"""Coverage for the bfloat16 perf modes: compute dtype (model.dtype) and
episode/replay storage dtype (replay.store_dtype) — the paths the
benchmark's cells use on TPU, exercised here on CPU at tiny scale."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                               TrainConfig, sanity_check)
from t2omca_tpu.run import Experiment


@pytest.fixture(scope="module")
def bf16_exp():
    cfg = sanity_check(TrainConfig(
        batch_size_run=2, batch_size=2,
        # fast_norm=False: this fixture pins the DENSE bf16 storage path
        # (compact entity storage keeps its leaves f32 by design)
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=4, fast_norm=False),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1,
                          standard_heads=True, dtype="bfloat16"),
        replay=ReplayConfig(buffer_size=8, store_dtype="bfloat16"),
    ))
    return Experiment.build(cfg)


@pytest.mark.slow   # bf16 rollout compile (~21 s); the bf16 train-step e2e stays in-gate
def test_bf16_rollout_storage_and_boundaries(bf16_exp):
    exp = bf16_exp
    ts = exp.init_train_state(0)
    rollout, insert, train_iter = exp.jitted_programs()
    rs, batch, stats = rollout(ts.learner.params["agent"], ts.runner,
                               test_mode=False)
    # storage arrays are compact; reward/Q-side math stays f32
    assert batch.obs.dtype == jnp.bfloat16
    assert batch.state.dtype == jnp.bfloat16
    assert batch.reward.dtype == jnp.float32
    # params are f32 (bf16 is compute dtype, not param dtype)
    leaf = jax.tree.leaves(ts.learner.params)[0]
    assert leaf.dtype == jnp.float32
    assert np.isfinite(np.asarray(stats.episode_return)).all()


@pytest.mark.slow   # bf16 train compile (~16 s); the f32-boundary forward test stays in-gate
def test_bf16_end_to_end_train_step(bf16_exp):
    exp = bf16_exp
    cfg = exp.cfg
    ts = exp.init_train_state(0)
    rollout, insert, train_iter = exp.jitted_programs()
    for _ in range(2):
        rs, batch, _ = rollout(ts.learner.params["agent"], ts.runner,
                               test_mode=False)
        ts = ts.replace(runner=rs, buffer=insert(ts.buffer, batch),
                        episode=ts.episode + cfg.batch_size_run)
    assert bool(exp.buffer.can_sample(ts.buffer, cfg.batch_size))
    ts2, info = train_iter(ts, jax.random.PRNGKey(1), jnp.asarray(16))
    assert np.isfinite(float(info["loss"]))
    assert np.isfinite(float(info["grad_norm"]))
    changed = jax.tree.map(lambda a, b: not np.allclose(a, b),
                           ts.learner.params, ts2.learner.params)
    assert any(jax.tree.leaves(changed))


def test_bf16_forward_close_to_f32():
    """bf16 compute tracks the f32 forward within bf16 tolerance on the
    same parameters."""
    from t2omca_tpu.controllers import BasicMAC
    from t2omca_tpu.envs.registry import make_env

    def build(dtype):
        cfg = sanity_check(TrainConfig(
            env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                               episode_limit=4),
            model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                              mixer_heads=2, mixer_depth=1,
                              standard_heads=True, dtype=dtype)))
        env = make_env(cfg.env_args)
        return BasicMAC.build(cfg, env.get_env_info()), env.get_env_info()

    mac32, info = build("float32")
    mac16, _ = build("bfloat16")
    params = mac32.init_params(jax.random.PRNGKey(0), info["obs_shape"])
    obs = jax.random.normal(jax.random.PRNGKey(1),
                            (2, info["n_agents"], info["obs_shape"]))
    h = mac32.init_hidden(2)
    q32, _ = mac32.forward(params, obs, h)
    q16, _ = mac16.forward(params, obs, h)
    assert q16.dtype == jnp.float32          # boundary cast back to f32
    np.testing.assert_allclose(np.asarray(q32), np.asarray(q16),
                               atol=0.15, rtol=0.15)
