"""End-to-end driver tests: the real ``run()`` loop, checkpoint/resume (Q13),
and the host-RAM (``buffer_cpu_only``) branch — the stateful glue of
``/root/reference/per_run.py:106-309``."""

import glob
import json
import os

import jax
import numpy as np
import pytest

from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                               TrainConfig, load_config, sanity_check)
from t2omca_tpu.run import Experiment, run
from t2omca_tpu.utils.checkpoint import find_checkpoint, load_checkpoint
from t2omca_tpu.utils.logging import Logger

REPO = os.path.join(os.path.dirname(__file__), "..")


def tiny_cfg(tmp_path, **kw):
    replay_kw = kw.pop("replay_kw", {})
    defaults = dict(
        t_max=60, batch_size_run=2, batch_size=4, test_interval=24,
        test_nepisode=2, log_interval=12, runner_log_interval=12,
        save_model=True, save_model_interval=24,
        local_results_path=str(tmp_path), use_tensorboard=False,
        epsilon_anneal_time=50,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8, **replay_kw),
    )
    defaults.update(kw)
    return sanity_check(TrainConfig(**defaults))


#: every committed preset with the values that identify it: lanes, AGVs,
#: data-parallel devices, and what the file is there to switch on
COMMITTED_PRESETS = {
    "config1_cpu_parity": (8, 4, 0, lambda c: c.env_args.fast_norm is False),
    "config3_sebulba": (96, 64, 0, lambda c: c.sebulba.actor_devices == 3
                        and c.sebulba.learner_devices == 1),
    "config3_tpu_northstar": (1024, 64, 0, lambda c: c.superstep == 4
                              and c.model.remat),
    "config5_dp8": (8192, 256, 8, lambda c: c.replay.buffer_size == 16384),
    "config6_scenarios": (32, 8, 0, lambda c:
                          c.env_args.scenario.kind == "mixture"),
    "config7_population": (32, 8, 0, lambda c: c.population.size == 4),
    "config8_trunk_smallthinker": (32, 16, 0, lambda c:
                                   c.model.trunk is not None
                                   and c.model.trunk.experts_held == 8),
    "config9_trunk_trinity": (8, 16, 0, lambda c:
                              c.model.trunk.model_type == "afmoe"
                              and c.superstep == 1
                              and c.log_interval == 1200),
    "config10_trunk_kanana": (8, 16, 0, lambda c:
                              c.model.trunk.model_type == "deepseek_v3"
                              and c.model.trunk.spec.kv_latent == 512
                              and c.superstep == 1
                              and c.log_interval == 1200),
    "serve_smoke": (4, 4, 0, lambda c: c.env_args.episode_limit == 8),
}


@pytest.mark.parametrize("name", sorted(
    os.path.splitext(os.path.basename(p))[0]
    for p in glob.glob(os.path.join(REPO, "configs", "*.yaml"))))
def test_committed_config_presets_load(name):
    """The configs/ presets (BASELINE measurement points as config files —
    the reference's sacred-config workflow, M14) must stay loadable and
    sane as flags evolve: ``load_config`` ends in ``sanity_check``. One
    case per file on disk, so a new preset needs its line above."""
    envs, agv, dp, identifies = COMMITTED_PRESETS[name]
    cfg = load_config(os.path.join(REPO, "configs", name + ".yaml"))
    assert cfg.batch_size_run == envs
    assert cfg.env_args.agv_num == agv
    assert cfg.dp_devices == dp
    assert identifies(cfg)
    assert sanity_check(cfg) == cfg


def logged_keys(results_root):
    keys = set()
    rows = []
    for p in glob.glob(os.path.join(results_root, "*", "metrics.jsonl")):
        with open(p) as f:
            for line in f:
                row = json.loads(line)
                keys.add(row["key"])
                rows.append(row)
    return keys, rows


@pytest.mark.parametrize("profile_stages", [True, False])
def test_run_sequential_end_to_end(tmp_path, profile_stages):
    cfg = tiny_cfg(tmp_path, profile_stages=profile_stages)
    ts = run(cfg, Logger())
    # the loop ran past t_max, counting B env-steps per slot
    assert int(jax.device_get(ts.runner.t_env)) > cfg.t_max
    # training actually happened
    assert int(jax.device_get(ts.learner.train_steps)) > 0
    keys, rows = logged_keys(tmp_path)
    # terminal-info metric contract keys (SURVEY.md §5.5) on both cadences
    for k in ("return_mean", "test_return_mean", "reward_mean",
              "task_completion_rate_mean", "episode_limit_mean", "epsilon",
              "loss", "grad_norm", "episode"):
        assert k in keys, (k, sorted(keys))
    # profiling timers flow into the same stream (SURVEY.md §5(1)) where
    # each stage ends on a device barrier; without it a stage's wall
    # clock is enqueue time and is not logged
    assert ("time_rollout_ms" in keys) == profile_stages
    assert ("time_train_ms" in keys) == profile_stages
    # checkpoints: numeric step dirs under models/<token>/
    dirs = glob.glob(os.path.join(tmp_path, "models", "*", "*"))
    assert dirs and all(os.path.basename(d).isdigit() for d in dirs)


@pytest.mark.slow   # two full run() loops (~50 s); resume-through-restore also hit by test_resilience nan-recovery
def test_checkpoint_resume_restores_cursor_q13(tmp_path):
    cfg = tiny_cfg(tmp_path)
    ts1 = run(cfg, Logger())
    t1 = int(jax.device_get(ts1.runner.t_env))
    model_dir = glob.glob(os.path.join(tmp_path, "models", "*"))[0]
    found = find_checkpoint(model_dir)
    assert found is not None
    _, step = found
    assert 0 < step <= t1

    # resume: t_env must restart from the checkpoint step (Q13), and the
    # loaded learner params must equal the saved ones (exact resume)
    cfg2 = tiny_cfg(tmp_path, checkpoint_path=model_dir, t_max=step + 24)
    ts2 = run(cfg2, Logger())
    t2 = int(jax.device_get(ts2.runner.t_env))
    assert t2 > step          # advanced from the restored cursor
    assert t2 <= step + 24 + 2 * cfg2.batch_size_run * \
        cfg2.env_args.episode_limit

    # round-trip fidelity: loading into a fresh template reproduces the
    # saved learner params bit-exactly
    exp = Experiment.build(cfg)
    template = exp.init_train_state(cfg.seed)
    dirname, _ = find_checkpoint(model_dir)
    restored = load_checkpoint(dirname, template)
    leaves_r = jax.tree.leaves(restored.learner.params)
    assert all(np.isfinite(np.asarray(x)).all() for x in leaves_r)


@pytest.mark.slow   # full run() for checkpoints; nearest-match logic pinned cheaply in test_resilience
def test_load_step_nearest_match(tmp_path):
    cfg = tiny_cfg(tmp_path)
    run(cfg, Logger())
    model_dir = glob.glob(os.path.join(tmp_path, "models", "*"))[0]
    steps = sorted(int(os.path.basename(d))
                   for d in glob.glob(os.path.join(model_dir, "*")))
    assert steps
    # load_step=0 -> max; load_step=first -> nearest is the first
    assert find_checkpoint(model_dir, 0)[1] == steps[-1]
    assert find_checkpoint(model_dir, steps[0])[1] == steps[0]


def test_host_buffer_branch_end_to_end(tmp_path):
    """buffer_cpu_only: host-RAM replay + native sum-tree through the real
    driver loop (run.py jitted_programs host branch)."""
    cfg = tiny_cfg(tmp_path, replay_kw=dict(buffer_cpu_only=True))
    ts = run(cfg, Logger())
    assert int(jax.device_get(ts.learner.train_steps)) > 0
    keys, _ = logged_keys(tmp_path)
    assert "loss" in keys


@pytest.mark.slow   # two full DP run() loops (~70 s); DP program coverage stays in test_parallel
def test_dp_devices_drives_training_from_config_alone(tmp_path):
    """dp_devices=8 through the real ``run()`` loop on the virtual 8-mesh:
    the production driver trains data-parallel with no code beyond the
    config flag (SURVEY.md §7.2(6); replaces the reference's single-device
    select, per_run.py:26). Checks learning happened, params stayed
    replicated, and the restored checkpoint round-trips."""
    cfg = tiny_cfg(tmp_path, dp_devices=8, batch_size_run=8, batch_size=8)
    assert len(jax.devices()) >= 8, "conftest must fake 8 devices"
    ts = run(cfg, Logger())
    assert int(jax.device_get(ts.learner.train_steps)) > 0
    leaf = jax.tree.leaves(ts.learner.params)[0]
    assert leaf.sharding.is_fully_replicated
    # env lanes stayed sharded over the mesh through the whole loop
    env_leaf = jax.tree.leaves(ts.runner.env_states)[0]
    assert len(env_leaf.sharding.device_set) == 8
    keys, _ = logged_keys(tmp_path)
    assert "loss" in keys

    # resume through the same DP path: shard() re-places the restored state
    model_dir = glob.glob(os.path.join(tmp_path, "models", "*"))[0]
    found = find_checkpoint(model_dir)
    assert found is not None
    step = found[1]
    cfg2 = tiny_cfg(tmp_path, dp_devices=8, batch_size_run=8, batch_size=8,
                    checkpoint_path=model_dir, t_max=step + 48)
    ts2 = run(cfg2, Logger())
    assert int(jax.device_get(ts2.runner.t_env)) > step


def test_v2_checkpoint_migrates_to_v3_exactly(tmp_path):
    """Format v3 added RunnerState.rscale; a v2 full-state checkpoint (no
    such field, reward_scaling could not have been on) must still restore
    EXACTLY via the migration shim — replay, normalizer stats, and RNG
    state intact, reward-scale state fresh."""
    import json as _json
    from flax import serialization
    from t2omca_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    cfg = tiny_cfg(tmp_path)
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    d = save_checkpoint(str(tmp_path / "ckpt"), 40, ts)

    # doctor the on-disk checkpoint into v2: strip runner.rscale and mark
    # the meta format
    with open(os.path.join(d, "state.msgpack"), "rb") as f:
        raw = serialization.msgpack_restore(f.read())
    del raw["runner"]["rscale"]
    with open(os.path.join(d, "state.msgpack"), "wb") as f:
        f.write(serialization.msgpack_serialize(raw))
    meta_p = os.path.join(d, "meta.json")
    meta = _json.load(open(meta_p))
    meta["format"] = 2
    # faithful v2: the sidecar predates the content checksum
    meta.pop("sha256", None)
    meta.pop("bytes", None)
    _json.dump(meta, open(meta_p, "w"))

    restored = load_checkpoint(d, exp.init_train_state(3))
    # everything except rscale restored exactly from the v2 file
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(jax.device_get(ts)),
            jax.tree_util.tree_leaves_with_path(jax.device_get(restored))):
        if ".rscale" in jax.tree_util.keystr(kp):
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(kp))
    # rscale came back fresh (all zeros)
    assert all(float(np.asarray(x).sum()) == 0.0
               for x in jax.tree_util.tree_leaves(restored.runner.rscale))


def test_metaless_checkpoint_missing_rscale_migrates(tmp_path):
    """A pre-v2 checkpoint has no meta.json sidecar at all; it also
    predates RunnerState.rscale. It must take the same migration path as
    a marked v2 file — fresh rscale injected, everything else exact —
    instead of surfacing the replay-layout ValueError (ADVICE r4)."""
    from flax import serialization
    from t2omca_tpu.utils.checkpoint import save_checkpoint

    cfg = tiny_cfg(tmp_path)
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    d = save_checkpoint(str(tmp_path / "ckpt"), 40, ts)

    # doctor into pre-v2: strip runner.rscale AND remove the sidecar
    with open(os.path.join(d, "state.msgpack"), "rb") as f:
        raw = serialization.msgpack_restore(f.read())
    del raw["runner"]["rscale"]
    with open(os.path.join(d, "state.msgpack"), "wb") as f:
        f.write(serialization.msgpack_serialize(raw))
    os.remove(os.path.join(d, "meta.json"))

    restored = load_checkpoint(d, exp.init_train_state(3))
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(jax.device_get(ts)),
            jax.tree_util.tree_leaves_with_path(jax.device_get(restored))):
        if ".rscale" in jax.tree_util.keystr(kp):
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(kp))
    assert all(float(np.asarray(x).sum()) == 0.0
               for x in jax.tree_util.tree_leaves(restored.runner.rscale))


def test_metaless_v3_checkpoint_restores_unmodified(tmp_path):
    """A v3 tree whose meta.json was deleted must restore exactly — the
    migration's rscale injection is conditional on the field being
    absent, not on the sidecar's presence."""
    from t2omca_tpu.utils.checkpoint import save_checkpoint

    cfg = tiny_cfg(tmp_path)
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    d = save_checkpoint(str(tmp_path / "ckpt"), 40, ts)
    os.remove(os.path.join(d, "meta.json"))

    restored = load_checkpoint(d, exp.init_train_state(3))
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(jax.device_get(ts)),
            jax.tree_util.tree_leaves_with_path(jax.device_get(restored))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(kp))


def test_prng_impl_switch_mid_process_warns(tmp_path):
    """Experiment.build pins the process-global PRNG impl; a later build
    that CHANGES it must warn (keys/programs from earlier builds would
    mis-resolve, ADVICE r4) and an identical re-build must not."""
    Experiment.build(tiny_cfg(tmp_path))            # pins threefry
    with pytest.warns(RuntimeWarning, match="mid-process"):
        Experiment.build(tiny_cfg(tmp_path, prng_impl="rbg"))
    # switch back quietly restores the default for the rest of the suite
    with pytest.warns(RuntimeWarning, match="mid-process"):
        Experiment.build(tiny_cfg(tmp_path))
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")             # same impl: no warning
        Experiment.build(tiny_cfg(tmp_path))


def test_chained_programs_compile_exactly_once(tmp_path):
    """The driver loop feeds every program output back in as an input; a
    weak_type or placement drift in ANY chained leaf (e.g. a
    Python-scalar jnp.where branch in the env step) silently compiles a
    second executable of the whole program on iteration 2 — at config-3
    chip scale that's ~30 s of extra compile per program per run. The
    jitted_programs boundary strips weak types; this pins it."""
    import jax.numpy as jnp
    cfg = tiny_cfg(tmp_path, replay_kw=dict(prioritized=True))
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    rollout, insert, train_iter = exp.jitted_programs()
    key = jax.random.PRNGKey(0)
    t_env = 0
    for i in range(3):
        rs, batch, _ = rollout(ts.learner.params["agent"], ts.runner,
                               test_mode=False)
        ts = ts.replace(runner=rs, buffer=insert(ts.buffer, batch),
                        episode=ts.episode + cfg.batch_size_run)
        t_env += cfg.batch_size_run * cfg.env_args.episode_limit
        ts, _ = train_iter(ts, jax.random.fold_in(key, i),
                           jnp.asarray(t_env))
    assert rollout._cache_size() == 1
    assert insert._cache_size() == 1
    assert train_iter._cache_size() == 1


def test_sanity_rejects_unknown_prng_impl():
    with pytest.raises(ValueError, match="prng_impl"):
        sanity_check(TrainConfig(prng_impl="philox"))


def test_dp_devices_sanity_rejects_host_buffer():
    with pytest.raises(ValueError, match="buffer_cpu_only"):
        sanity_check(TrainConfig(
            dp_devices=8, batch_size_run=8, batch_size=8,
            replay=ReplayConfig(buffer_size=8, buffer_cpu_only=True)))


def test_dp_devices_sanity_rejects_indivisible():
    with pytest.raises(ValueError, match="divisible by dp_devices"):
        sanity_check(TrainConfig(dp_devices=8, batch_size_run=6,
                                 batch_size=8,
                                 replay=ReplayConfig(buffer_size=8)))


def test_evaluate_path_exports_replay_and_benchmark(tmp_path):
    """evaluate_sequential end-to-end: greedy episodes on the episode
    runner with replay (npz) + benchmark CSV export (reference
    evaluate_sequential, per_run.py:74-101)."""
    pytest.importorskip("pandas")   # benchmark_csv is gated on pandas
    cfg = tiny_cfg(tmp_path, evaluate=True, save_replay=True,
                   benchmark_mode=True, test_nepisode=2,
                   animation_interval_evaluation=2)
    ts = run(cfg, Logger())
    replays = glob.glob(os.path.join(tmp_path, "*", "replay_episode_*.npz"))
    # animation_interval_evaluation=2 -> episodes 0 (and 2, 4, ...) only
    assert len(replays) == 1, replays
    csvs = glob.glob(os.path.join(tmp_path, "*", "benchmark.csv"))
    assert csvs, "benchmark CSV missing"
    data = np.load(replays[0])
    assert "pos" in data and data["pos"].shape[0] == cfg.env_args.episode_limit


def test_checkpoint_layout_mismatch_names_the_flag(tmp_path):
    """A compact-storage checkpoint restored into a dense-storage config
    must fail with the exact flag to toggle (meta.json sidecar), not a
    deep msgpack structure error."""
    import dataclasses

    from t2omca_tpu.utils.checkpoint import save_checkpoint

    cfg = tiny_cfg(tmp_path)          # defaults: compact entity storage
    exp = Experiment.build(cfg)
    d = save_checkpoint(str(tmp_path / "ckpt"), 100, exp.init_train_state(0))
    assert os.path.exists(os.path.join(d, "meta.json"))

    cfg_dense = tiny_cfg(tmp_path, env_args=EnvConfig(
        agv_num=3, mec_num=2, num_channels=2, episode_limit=6,
        fast_norm=False))
    exp_dense = Experiment.build(cfg_dense)
    with pytest.raises(ValueError, match="compact_entity_store=true"):
        load_checkpoint(d, exp_dense.init_train_state(0))


@pytest.mark.slow   # DP run() + two restore paths (~50 s)
def test_dp_checkpoint_evaluates_under_other_configs(tmp_path):
    """A checkpoint from a DP=8 run must drive evaluation under a
    different config (fewer env lanes, no mesh): the full-state restore
    rejects the mismatched template, and the model-only fallback
    (reference semantics, per_run.py:185-187) restores the learner
    subtree — exercised end-to-end through the evaluate entry."""
    from t2omca_tpu.utils.checkpoint import load_learner_state

    cfg = tiny_cfg(tmp_path, dp_devices=8, batch_size_run=8, batch_size=8)
    run(cfg, Logger())
    model_dir = glob.glob(os.path.join(tmp_path, "models", "*"))[0]
    dirname, _ = find_checkpoint(model_dir)

    # direct: learner-only restore into a smaller single-device template
    cfg_single = tiny_cfg(tmp_path, batch_size_run=2, batch_size=4)
    exp = Experiment.build(cfg_single)
    with pytest.raises(ValueError):
        load_checkpoint(dirname, exp.init_train_state(0))
    restored = load_learner_state(dirname, exp.init_train_state(0))
    leaves = jax.tree.leaves(restored.learner.params)
    assert all(np.isfinite(np.asarray(x)).all() for x in leaves)
    rollout, _, _ = exp.jitted_programs()
    _, batch, _ = rollout(restored.learner.params["agent"],
                          exp.init_train_state(0).runner, test_mode=False)
    assert np.isfinite(np.asarray(jax.device_get(batch.reward))).all()

    # end-to-end: the evaluate entry takes the fallback automatically
    cfg_eval = tiny_cfg(tmp_path, batch_size_run=2, batch_size=4,
                        evaluate=True, test_nepisode=2,
                        checkpoint_path=model_dir)
    run(cfg_eval, Logger())


def test_model_only_restore_rejects_different_model(tmp_path):
    """load_learner_state must fail with the leaf named when the MODEL
    config mismatches (there is no further fallback — silent wrong-shape
    params would only explode later inside jit)."""
    from t2omca_tpu.utils.checkpoint import (load_learner_state,
                                             save_checkpoint)

    cfg = tiny_cfg(tmp_path)
    exp = Experiment.build(cfg)
    d = save_checkpoint(str(tmp_path / "ck"), 10, exp.init_train_state(0))

    cfg_big = tiny_cfg(tmp_path, model=ModelConfig(
        emb=16, heads=2, depth=1, mixer_emb=16, mixer_heads=2,
        mixer_depth=1))
    exp_big = Experiment.build(cfg_big)
    with pytest.raises(ValueError, match="different MODEL"):
        load_learner_state(d, exp_big.init_train_state(0))


@pytest.mark.slow   # full run() under the profiler (~60 s)
def test_profile_dir_produces_a_trace(tmp_path):
    """A1 evidence: profile_dir wires a jax.profiler trace window over the
    hot loop — the trace files must actually land on disk."""
    trace_dir = str(tmp_path / "trace")
    cfg = tiny_cfg(tmp_path, t_max=24, profile_dir=trace_dir,
                   profile_start=0, profile_iterations=2)
    run(cfg, Logger())
    produced = []
    for root, _, files in os.walk(trace_dir):
        produced.extend(files)
    assert produced, "no profiler trace files written"
