"""Unit tests for the pure-functional MultiAgvOffloading environment.

SURVEY.md §4's recommended pyramid, layer 1: collision resolution, reward
branches (each branch of environment_multi_mec.py:229-293 enumerated), queue
pop/age/expire/generate ordering, availability masks, obs/state shapes,
teleport mobility, and vmap independence.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from t2omca_tpu.config import EnvConfig
from t2omca_tpu.envs import EnvState, MultiAgvOffloadingEnv
from t2omca_tpu.envs.normalization import NormState


def make_env(**kw) -> MultiAgvOffloadingEnv:
    defaults = dict(agv_num=4, mec_num=2, num_channels=2, episode_limit=10,
                    obs_entity_mode=True, state_entity_mode=True)
    defaults.update(kw)
    return MultiAgvOffloadingEnv(EnvConfig(**defaults))


def manual_state(env, mec_index, jobs, deadlines=None, pos=None) -> EnvState:
    """Build a deterministic EnvState. jobs: list of per-agent lists of
    (data_size, deadline)."""
    a, j = env.n_agents, env.max_jobs
    data = np.zeros((a, j), np.float32)
    dl = np.zeros((a, j), np.float32)
    valid = np.zeros((a, j), bool)
    for i, joblist in enumerate(jobs):
        for s, (d, t) in enumerate(joblist):
            data[i, s], dl[i, s], valid[i, s] = d, t, True
    if pos is None:
        pos = np.asarray(env.mec_positions())[np.asarray(mec_index)]
    return EnvState(
        time_slot=jnp.zeros((), jnp.int32),
        mec_index=jnp.asarray(mec_index, jnp.int32),
        pos=jnp.asarray(pos, jnp.float32),
        job_data=jnp.asarray(data), job_deadline=jnp.asarray(dl),
        job_valid=jnp.asarray(valid),
        last_ack=jnp.zeros((a,), jnp.int32),
        last_action=jnp.zeros((a,), jnp.int32),
        task_num=jnp.asarray([len(x) for x in jobs], jnp.int32),
        task_success=jnp.zeros((a,), jnp.int32),
        remain_delay=jnp.zeros((a,), jnp.float32),
        norm=NormState.create(env.obs_dim),
    )


KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------- collisions

def test_collision_same_channel_same_mec():
    env = make_env()
    st = manual_state(env, [0, 0, 1, 1],
                      [[(8000, 100)]] * 4)
    # agents 0,1 under MEC0 pick channel 1 -> collide; 2,3 under MEC1 pick
    # channels 1,2 -> both succeed (Q14: channel reuse across MECs)
    *_, = out = env.step(st, jnp.asarray([1, 1, 1, 2]), KEY)
    st2 = out[0]
    np.testing.assert_array_equal(np.asarray(st2.last_ack), [-1, -1, 1, 1])
    info = out[3]
    assert float(info.conflict_ratio) == 0.5


def test_action0_never_collides():
    env = make_env()
    st = manual_state(env, [0, 0, 0, 0], [[(8000, 100)]] * 4)
    out = env.step(st, jnp.asarray([0, 0, 0, 0]), KEY)
    np.testing.assert_array_equal(np.asarray(out[0].last_ack), [0, 0, 0, 0])
    assert float(out[3].conflict_ratio) == 0.0


def test_channel_utilization_counts_action0_slot():
    """Reference quirk: utilization sums all C+1 slots of the masked per-MEC
    bincount, including the action-0 slot (environment_multi_mec.py:319-329)."""
    env = make_env()
    st = manual_state(env, [0, 0, 1, 1], [[(8000, 100)]] * 4)
    # MEC0: one local (count[0]=1), one on ch1; MEC1: two locals (count 2 -> 0)
    out = env.step(st, jnp.asarray([0, 1, 0, 0]), KEY)
    # masked counts: MEC0 [1,1,0], MEC1 [0,0,0] -> sum=2; /(C=2 * M=2) = 0.5
    assert float(out[3].channel_utilization_rate) == pytest.approx(0.5)


# --------------------------------------------------------------- reward branches

def expected_local_delay(env, data):
    return round(env.computation_cycles * data / env.cfg.user_compute_cap * 1000, 2)


def test_reward_local_success_branch():
    env = make_env()
    data = 8000.0
    ld = expected_local_delay(env, data)      # 50.0 ms at 5 GHz
    st = manual_state(env, [0, 0, 1, 1],
                      [[(data, 100.0)], [], [], []])
    out = env.step(st, jnp.asarray([0, 0, 0, 0]), KEY)
    st2, reward, _, info = out[0], out[1], out[2], out[3]
    # deadline 100 - 50 > 0: success, no reward contribution
    assert float(reward) == 0.0
    assert int(st2.task_success[0]) == 1
    # remain_delay += latency_max - deadline + local_delay = 100-100+50
    assert float(st2.remain_delay[0]) == pytest.approx(ld)


def test_reward_local_miss_branch():
    env = make_env()
    st = manual_state(env, [0, 0, 1, 1], [[(8000.0, 40.0)], [], [], []])
    out = env.step(st, jnp.asarray([0, 0, 0, 0]), KEY)
    # local delay 50 > deadline 40 -> overtime penalty latency_max
    assert float(out[1]) == -100.0
    assert float(out[3].overtime_penalty) == 100.0
    assert int(out[0].task_success[0]) == 0


def test_reward_collision_branches():
    env = make_env()
    # two colliding agents under MEC0: one job expiring (deadline<=5), one not
    st = manual_state(env, [0, 0, 1, 1],
                      [[(8000.0, 5.0)], [(8000.0, 50.0)], [], []])
    out = env.step(st, jnp.asarray([1, 1, 0, 0]), KEY)
    np.testing.assert_array_equal(np.asarray(out[0].last_ack)[:2], [-1, -1])
    # only the expiring job is penalized (environment_multi_mec.py:257-259)
    assert float(out[1]) == -100.0


def test_reward_offload_success_branch():
    env = make_env()
    data = 8000.0
    st = manual_state(env, [0, 0, 1, 1], [[(data, 100.0)], [], [], []])
    out = env.step(st, jnp.asarray([1, 0, 0, 0]), KEY)
    st2, reward = out[0], out[1]
    ld = expected_local_delay(env, data)
    p = env.default_params()
    od = float(env._offload_delay(
        jnp.asarray([data]), st.pos[:1], st.mec_index[:1],
        p.replace(tx_scale=p.tx_scale[:1],
                  compute_scale=p.compute_scale[:1]))[0])
    assert od < ld, "offloading should beat local compute in the spec regime"
    assert float(reward) == pytest.approx(ld - od, abs=1e-3)
    assert int(st2.task_success[0]) == 1
    assert float(st2.remain_delay[0]) == pytest.approx(od, abs=1e-3)


def test_reward_empty_buffer_skipped():
    env = make_env()
    st = manual_state(env, [0, 1, 0, 1], [[], [], [], []])
    out = env.step(st, jnp.asarray([0, 0, 0, 0]), KEY)
    assert float(out[1]) == 0.0
    assert float(out[3].overtime_penalty) == 0.0


# --------------------------------------------------------------- queue dynamics

def test_queue_pop_age_expire_order():
    env = make_env(job_prob=0.0)  # disable generation to isolate dynamics
    # agent 0: head job + second job with deadline 5 (will expire after aging)
    st = manual_state(env, [0, 0, 1, 1],
                      [[(8000.0, 100.0), (6000.0, 5.0)], [], [], []])
    out = env.step(st, jnp.asarray([0, 0, 0, 0]), KEY)
    st2 = out[0]
    # head popped (ack=0), second aged 5->0 then expired -> queue empty
    assert not bool(st2.job_valid[0, 0])


def test_queue_no_pop_on_collision():
    env = MultiAgvOffloadingEnv(EnvConfig(agv_num=4, mec_num=2, num_channels=2,
                                          episode_limit=10, job_prob=0.0))
    st = manual_state(env, [0, 0, 1, 1],
                      [[(8000.0, 100.0)], [(6000.0, 100.0)], [], []])
    out = env.step(st, jnp.asarray([1, 1, 0, 0]), KEY)     # collide
    st2 = out[0]
    # job kept, aged by 5
    assert bool(st2.job_valid[0, 0])
    assert float(st2.job_deadline[0, 0]) == 95.0
    assert float(st2.job_data[0, 0]) == 8000.0


def test_queue_fifo_preserved_after_expiry_compaction():
    env = MultiAgvOffloadingEnv(EnvConfig(agv_num=1, mec_num=1, num_channels=2,
                                          episode_limit=10, job_prob=0.0))
    # head expires (collide so no pop), later jobs survive in order
    st = manual_state(env, [0], [[(1000.0, 5.0), (2000.0, 50.0),
                                  (3000.0, 80.0)]])
    out = env.step(st, jnp.asarray([1]), KEY)              # lone agent: ack=1!
    # ack=1 pops head; remaining [2000@45, 3000@75]
    st2 = out[0]
    np.testing.assert_allclose(np.asarray(st2.job_data[0, :2]), [2000, 3000])
    np.testing.assert_allclose(np.asarray(st2.job_deadline[0, :2]), [45, 75])
    assert not bool(st2.job_valid[0, 2])


def test_generation_appends_at_tail_and_counts():
    env = MultiAgvOffloadingEnv(EnvConfig(agv_num=2, mec_num=1, num_channels=2,
                                          episode_limit=10, job_prob=1.0))
    st = manual_state(env, [0, 0], [[(8000.0, 100.0)], []])
    out = env.step(st, jnp.asarray([0, 0]), KEY)
    st2 = out[0]
    # agent0: head popped, new job appended -> exactly 1 valid, deadline 100
    assert int(st2.job_valid[0].sum()) == 1
    assert float(st2.job_deadline[0, 0]) == 100.0
    assert int(st2.task_num[0]) == 2       # initial + generated
    assert int(st2.task_num[1]) == 1


# --------------------------------------------------------------- avail actions

def test_avail_actions_modes():
    env = make_env()
    st = manual_state(env, [0, 0, 1, 1], [[(8000.0, 100.0)], [], [], []])
    avail = np.asarray(env.get_avail_actions(st))
    np.testing.assert_array_equal(avail[0], [1, 1, 1])     # job: all legal
    np.testing.assert_array_equal(avail[1], [1, 0, 0])     # empty: idle only

    env_eo = MultiAgvOffloadingEnv(dataclasses.replace(env.cfg, edge_only=True))
    avail = np.asarray(env_eo.get_avail_actions(st))
    np.testing.assert_array_equal(avail[0], [0, 1, 1])     # local forbidden
    np.testing.assert_array_equal(avail[1], [1, 0, 0])


# --------------------------------------------------------------- obs/state

def test_obs_entity_structure():
    env = make_env()
    st = manual_state(env, [0, 1, 0, 1], [[(8000.0, 100.0)]] * 4)
    raw = np.asarray(env._raw_obs(st, env.default_params()))
    assert raw.shape == (4, 4 * 9)
    rows = raw.reshape(4, 4, 9)
    # observer 0 (MEC0) sees agents 0,2 (same MEC); rows for 1,3 are zeros
    assert rows[0, 1].sum() == 0 and rows[0, 3].sum() == 0
    assert rows[0, 2].sum() != 0
    # is_self flag only on own row
    assert rows[0, 0, 8] == 1 and rows[0, 2, 8] == 0
    # ack onehot for ack=0 is [0,1,0]
    np.testing.assert_array_equal(rows[0, 0, :3], [0, 1, 0])


def test_state_layout_and_shapes():
    env = make_env()
    st = manual_state(env, [0, 1, 0, 1], [[(8000.0, 100.0)]] * 4)
    gs = np.asarray(env.get_state(st))
    assert gs.shape == (env.state_dim,) == (4 * 8,)
    # first 12 entries = 4 agents' ack one-hots
    np.testing.assert_array_equal(gs[:12].reshape(4, 3),
                                  [[0, 1, 0]] * 4)
    info = env.get_env_info()
    assert info["obs_shape"] == 36 and info["state_shape"] == 32
    assert info["obs_entity_feats"] == 9 and info["state_entity_feats"] == 8
    assert info["n_actions"] == 3 and info["n_agents"] == 4


# --------------------------------------------------------------- episode / reset

def test_terminates_exactly_at_episode_limit():
    env = MultiAgvOffloadingEnv(EnvConfig(agv_num=2, mec_num=1, num_channels=2,
                                          episode_limit=3))
    st, *_ = env.reset(KEY)
    key = KEY
    for t in range(3):
        key, k = jax.random.split(key)
        st, _, term, info, *_ = env.step(st, jnp.zeros(2, jnp.int32), k)
        assert bool(term) == (t == 2)
    assert bool(info.episode_limit)
    assert 0.0 <= float(info.task_completion_rate) <= 1.0


def test_reset_reseeds_and_clears():
    env = make_env()
    st, obs, gs, avail = env.reset(KEY)
    assert obs.shape == (4, env.obs_dim)
    assert gs.shape == (env.state_dim,)
    assert avail.shape == (4, env.n_actions)
    assert int(st.task_success.sum()) == 0
    # positions inside serving MEC circle
    d = np.linalg.norm(np.asarray(st.pos)
                       - np.asarray(env.mec_positions())[np.asarray(st.mec_index)],
                       axis=1)
    assert (d <= env.cfg.communication_range_m + 1e-5).all()


def test_teleport_mobility_every_slot():
    env = make_env()
    st, *_ = env.reset(KEY)
    out = env.step(st, jnp.zeros(4, jnp.int32), jax.random.PRNGKey(7))
    assert not np.allclose(np.asarray(st.pos), np.asarray(out[0].pos))


# --------------------------------------------------------------- vmap behavior

def test_vmap_lanes_are_independent():
    env = make_env()
    keys = jax.random.split(KEY, 3)
    st, obs, gs, avail = jax.vmap(env.reset)(keys)
    assert st.pos.shape == (3, 4, 2)
    # different lanes, different worlds (Q8 seed-offset equivalent)
    assert not np.allclose(np.asarray(st.pos[0]), np.asarray(st.pos[1]))

    step_keys = jax.random.split(jax.random.PRNGKey(9), 3)
    actions = jnp.zeros((3, 4), jnp.int32)
    st2, reward, term, info, obs2, gs2, avail2 = jax.vmap(env.step)(
        st, actions, step_keys)
    assert reward.shape == (3,)
    # normalizer stats diverge per lane (carried in state, not shared)
    assert not np.allclose(np.asarray(st2.norm.mean[0]),
                           np.asarray(st2.norm.mean[1]))


def test_step_is_jittable_and_deterministic():
    env = make_env()
    st, *_ = env.reset(KEY)
    step = jax.jit(env.step)
    a = jnp.zeros(4, jnp.int32)
    out1 = step(st, a, jax.random.PRNGKey(3))
    out2 = step(st, a, jax.random.PRNGKey(3))
    np.testing.assert_allclose(np.asarray(out1[1]), np.asarray(out2[1]))
    np.testing.assert_allclose(np.asarray(out1[0].pos), np.asarray(out2[0].pos))


def test_fast_norm_env_equivalence():
    """fast_norm changes only get_obs: running statistics stay in lockstep
    with the sequential reference path along a shared trajectory, and the
    normalized observations converge (O(A/n) transient)."""
    env_seq = make_env(fast_norm=False)   # sequential reference path
    env_fast = make_env(fast_norm=True)
    st, obs_seq, *_ = env_seq.reset(KEY)
    fast_norm = env_fast.get_obs(st.replace(norm=NormState.create(
        env_fast.obs_dim)))[0].norm
    key = jax.random.PRNGKey(11)
    devs = []
    for t in range(40):
        key, ka, ks = jax.random.split(key, 3)
        actions = jax.random.randint(ka, (env_seq.n_agents,), 0,
                                     env_seq.n_actions)
        avail = env_seq.get_avail_actions(st)
        actions = jnp.where(avail[jnp.arange(4), actions] > 0, actions, 0)
        st, _, _, _, obs_seq, _, _ = env_seq.step(st, actions, ks)
        # same post-step state, fast normalizer carried independently
        fst, obs_fast = env_fast.get_obs(st.replace(norm=fast_norm))
        fast_norm = fst.norm
        devs.append(float(jnp.abs(obs_fast - obs_seq).max()))
    assert int(fast_norm.n) == int(st.norm.n)
    np.testing.assert_allclose(np.asarray(fast_norm.mean),
                               np.asarray(st.norm.mean), rtol=1e-3, atol=1e-3)
    # the two paths' outputs converge after warm-up
    assert np.mean(devs[-10:]) < np.mean(devs[:10])
    assert devs[-1] < 0.15, devs[-5:]


def test_state_last_action_flag():
    """state_last_action prepends per-agent action one-hots to the global
    state (reference declares the flag at :11, concat slot at :196)."""
    env = make_env(state_last_action=True)
    base = make_env()
    assert env.state_dim == base.state_dim + 4 * env.n_actions
    assert env.state_entity_feats == base.state_entity_feats + env.n_actions

    st, *_ = env.reset(KEY)
    actions = jnp.asarray([0, 1, 2, 0])
    avail = env.get_avail_actions(st)
    actions = jnp.where(avail[jnp.arange(4), actions] > 0, actions, 0)
    st2, _, _, _, _, gstate, _ = env.step(st, actions, jax.random.PRNGKey(1))
    la = np.asarray(gstate[:4 * env.n_actions]).reshape(4, env.n_actions)
    np.testing.assert_allclose(la, np.eye(env.n_actions)[np.asarray(actions)])


def test_fuzz_invariants_over_random_trajectories():
    """Structural invariants under 3 seeds x 60 random (legal) steps:
    whatever the action sequence, the state must stay well-formed —
    counters monotone and ordered, queue entries consistent, positions
    finite and inside the deployment disc, normalizer stats sane. Guards
    the queue pop->age->expire->generate pipeline against edge-case
    regressions no enumerated test covers."""
    env = make_env(episode_limit=60)
    a = env.n_agents
    r_max = 2.0 * env.cfg.mec_radius_m * max(env.cfg.mec_num, 1)
    for seed in range(3):
        key = jax.random.PRNGKey(100 + seed)
        st, *_ = env.reset(key)
        prev_task_num = np.zeros(a, np.int64)
        for t in range(60):
            key, ka, ks = jax.random.split(key, 3)
            avail = env.get_avail_actions(st)
            actions = jax.random.randint(ka, (a,), 0, env.n_actions)
            actions = jnp.where(avail[jnp.arange(a), actions] > 0,
                                actions, 0)
            st, reward, term, info, obs, gstate, _ = env.step(
                st, actions, ks)

            assert int(st.time_slot) == t + 1
            # counters: generated grows monotonically, successes bounded
            tn = np.asarray(st.task_num, np.int64)
            assert (tn >= prev_task_num).all()
            prev_task_num = tn
            assert (np.asarray(st.task_success) <= tn).all()
            # queue slots: invalid entries must be zeroed; valid entries
            # positive-sized with non-negative remaining deadline
            valid = np.asarray(st.job_valid)
            data = np.asarray(st.job_data)
            dl = np.asarray(st.job_deadline)
            assert (data[~valid] == 0).all() and (dl[~valid] == 0).all()
            assert (data[valid] > 0).all()
            assert (dl[valid] >= 0).all()
            # geometry: finite positions within the deployment extent
            pos = np.asarray(st.pos)
            assert np.isfinite(pos).all() and (np.abs(pos) <= r_max).all()
            # serving MEC ids in range; ack flags in the contract set
            mi = np.asarray(st.mec_index)
            assert ((mi >= 0) & (mi < env.cfg.mec_num)).all()
            assert np.isin(np.asarray(st.last_ack), [-1, 0, 1]).all()
            # normalizer: counters advance, stats finite, std >= 0
            assert np.isfinite(np.asarray(st.norm.mean)).all()
            assert (np.asarray(st.norm.std) >= 0).all()
            # outputs finite
            assert np.isfinite(float(reward))
            assert np.isfinite(np.asarray(obs)).all()
            assert np.isfinite(np.asarray(gstate)).all()


# --------------------------------------------------------------- lookups

class _GatherEnv(MultiAgvOffloadingEnv):
    """The env as it was before the per-MEC lookups became one-hot
    selects: the old indexing, kept here as the oracle."""

    def _mec_lookup(self, table, mec_index):
        return table[mec_index]


def _lookup_rollout(env, params_b, key, lanes=4, steps=150):
    """reset, then ``steps`` vmapped steps under random actions (even
    lanes: legal ones, as the selector gives; odd lanes: anything) →
    every output of every step, the states before each step and the
    actions taken."""
    def rollout(key):
        k_reset, k_scan = jax.random.split(key)
        st, obs, gs, avail = jax.vmap(env.reset, in_axes=(0, None, 0))(
            jax.random.split(k_reset, lanes), None, params_b)
        legal_only = (jnp.arange(lanes) % 2 == 0)[:, None]

        def body(carry, k):
            st, avail = carry
            ka, ks = jax.random.split(k)
            act = jax.random.randint(ka, (lanes, env.n_agents), 0,
                                     env.n_actions)
            legal = jnp.take_along_axis(avail, act[..., None], 2)[..., 0] > 0
            act = jnp.where(legal_only & ~legal, 0, act)
            out = jax.vmap(env.step)(st, act, jax.random.split(ks, lanes),
                                     params_b)
            probes = (jax.vmap(env.get_state)(out[0], params_b),
                      jax.vmap(env.get_avail_actions)(out[0], params_b))
            return (out[0], out[6]), (st.mec_index, act, out, probes)

        _, ys = jax.lax.scan(body, (st, avail),
                             jax.random.split(k_scan, steps))
        return (st, obs, gs, avail), ys
    return jax.jit(rollout)(key)


@pytest.mark.parametrize("padded", [False, True], ids=["full", "padded"])
@pytest.mark.parametrize("agv_num,mec_num,channels,fast_norm",
                         [(4, 2, 2, False), (16, 4, 4, True),
                          (64, 8, 8, True)],
                         ids=["agv4", "agv16", "agv64"])
def test_onehot_lookups_equal_the_gather_form_bit_for_bit(
        agv_num, mec_num, channels, fast_norm, padded):
    """The per-MEC lookups as one-hot selects (``_mec_lookup``) change no
    bit of anything the env hands out: every leaf of ``EnvState``, reward,
    every ``StepInfo`` field, obs, global state and availability of a
    150-step rollout from ``reset`` equal the gather form's, with padded
    agents (whose sentinel the gather wrapped onto a real row and the
    select reads as zeros) and a stretched MEC placement too."""
    kw = dict(agv_num=agv_num, mec_num=mec_num, num_channels=channels,
              episode_limit=150, fast_norm=fast_norm)
    env = make_env(**kw)
    oracle = _GatherEnv(env.cfg)
    lanes = 4
    p = env.default_params()
    if padded:
        p = p.replace(n_active=jnp.asarray(agv_num - agv_num // 4 - 1,
                                           jnp.int32),
                      mec_scale=jnp.asarray(1.37, jnp.float32),
                      teleport_prob=jnp.asarray(0.6, jnp.float32))
    params_b = jax.vmap(lambda _: p)(jnp.arange(lanes))
    key = jax.random.PRNGKey(30 + agv_num)
    got = _lookup_rollout(env, params_b, key, lanes)
    want = _lookup_rollout(oracle, params_b, key, lanes)
    paths = jax.tree_util.tree_flatten_with_path(got)[0]
    for (path, g), w in zip(paths, jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, path
        assert g.tobytes() == w.tobytes(), jax.tree_util.keystr(path)

    # the histogram's cell by the old 2-D indexing, in numpy: ACK 1 where
    # the agent is alone on its (MEC, channel), -1 where it is not
    mec, act, out, _ = jax.tree.map(np.asarray, got[1])
    ack = np.asarray(out[0].last_ack)
    n_active = int(p.n_active)
    for t in range(0, 150, 7):
        for lane in range(lanes):
            counts = np.zeros((mec_num, channels + 1), np.int64)
            np.add.at(counts, (mec[t, lane, :n_active],
                               act[t, lane, :n_active]), 1)
            masked = np.where(counts > 1, 0, counts)
            chosen = masked[mec[t, lane, :n_active], act[t, lane, :n_active]]
            a = act[t, lane, :n_active]
            np.testing.assert_array_equal(
                ack[t, lane, :n_active],
                np.where(a == 0, 0, np.where(chosen == 1, 1, -1)))
            assert (ack[t, lane, n_active:] == 0).all()
    assert (ack == 1).any() and (ack == -1).any()


@pytest.mark.parametrize("fast_norm", [False, True],
                         ids=["sequential-norm", "fast-norm"])
def test_env_lowers_without_gathers(fast_norm):
    """``step`` and ``reset`` hold no gather in their StableHLO (needs no
    chip; on a TPU a gather fetches its rows one after another, which is
    why the lookups by serving MEC are selects)."""
    env = make_env(agv_num=16, mec_num=4, num_channels=4,
                   fast_norm=fast_norm)
    keys = jax.random.split(KEY, 8)
    st = jax.eval_shape(jax.vmap(env.reset), keys)[0]
    actions = jnp.zeros((8, 16), jnp.int32)
    for text in (
            jax.jit(jax.vmap(env.step)).lower(st, actions, keys).as_text(),
            jax.jit(jax.vmap(env.reset)).lower(keys).as_text()):
        assert "stablehlo.add" in text
        assert "gather" not in text
