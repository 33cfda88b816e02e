"""Fault-tolerance suite (docs/RESILIENCE.md): crash-safe checkpoints,
``find_checkpoint`` edge cases + skip-back, the non-finite guard rail, and
preemption handling — exercised through deterministic fault injectors
(``t2omca_tpu.utils.resilience``) and short ``run_sequential`` runs on the
CPU backend.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                               ResilienceConfig, TrainConfig, load_config,
                               sanity_check)
from t2omca_tpu.run import Experiment, run
from t2omca_tpu.utils import resilience
from t2omca_tpu.utils.checkpoint import (CheckpointIntegrityError,
                                         find_checkpoint, load_checkpoint,
                                         prune_checkpoints, save_checkpoint,
                                         verify_checkpoint)
from t2omca_tpu.utils.logging import Logger


@pytest.fixture(autouse=True)
def _no_fault_leaks():
    """Every test starts and ends with an empty injector registry."""
    resilience.clear_faults()
    yield
    resilience.clear_faults()


def tiny_cfg(tmp_path, **kw):
    replay_kw = kw.pop("replay_kw", {})
    res_kw = kw.pop("res_kw", {})
    defaults = dict(
        t_max=60, batch_size_run=2, batch_size=4, test_interval=24,
        test_nepisode=2, log_interval=12, runner_log_interval=12,
        save_model=True, save_model_interval=12,
        local_results_path=str(tmp_path), use_tensorboard=False,
        epsilon_anneal_time=50,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8, **replay_kw),
        resilience=ResilienceConfig(**res_kw),
    )
    defaults.update(kw)
    return sanity_check(TrainConfig(**defaults))


def _save_steps(tmp_path, steps):
    """Write real (tiny but complete) checkpoints at the given steps."""
    cfg = tiny_cfg(tmp_path)
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    root = str(tmp_path / "ckpt")
    for s in steps:
        save_checkpoint(root, s, ts)
    return root, exp, ts


# ---------------------------------------------------------------------------
# find_checkpoint edge cases
# ---------------------------------------------------------------------------

def test_find_checkpoint_empty_and_missing_dir(tmp_path):
    assert find_checkpoint(str(tmp_path / "nope")) is None
    empty = tmp_path / "empty"
    empty.mkdir()
    assert find_checkpoint(str(empty)) is None


@pytest.mark.slow   # Experiment build + real checkpoints for a listdir edge case
def test_find_checkpoint_ignores_non_numeric_entries(tmp_path):
    root, _, _ = _save_steps(tmp_path, [10])
    os.makedirs(os.path.join(root, "tb_logs"))
    os.makedirs(os.path.join(root, "tmp.99"))         # staging leftover
    with open(os.path.join(root, "20"), "w") as f:    # FILE named like a step
        f.write("not a directory")
    assert find_checkpoint(root) == (os.path.join(root, "10"), 10)


def test_load_step_nearest_tie_prefers_smaller_step(tmp_path):
    root, _, _ = _save_steps(tmp_path, [10, 30])
    # 20 is equidistant from 10 and 30: the tie must resolve
    # deterministically to the SMALLER step (sorted candidate order)
    assert find_checkpoint(root, load_step=20)[1] == 10
    assert find_checkpoint(root, load_step=29)[1] == 30


# ---------------------------------------------------------------------------
# crash-safe write + integrity skip-back
# ---------------------------------------------------------------------------

def test_truncated_top_checkpoint_skips_back(tmp_path):
    root, _, _ = _save_steps(tmp_path, [10, 20])
    state_p = os.path.join(root, "20", "state.msgpack")
    blob = open(state_p, "rb").read()
    with open(state_p, "wb") as f:
        f.write(blob[: len(blob) // 2])               # torn write
    assert not verify_checkpoint(os.path.join(root, "20"))
    # the acceptance bar: a truncated state.msgpack is NEVER selected;
    # resume falls back to the newest VALID step
    assert find_checkpoint(root) == (os.path.join(root, "10"), 10)


def test_bitflip_detected_by_checksum_and_skipped(tmp_path):
    root, _, _ = _save_steps(tmp_path, [10, 20])
    state_p = os.path.join(root, "20", "state.msgpack")
    blob = bytearray(open(state_p, "rb").read())
    blob[len(blob) // 2] ^= 0xFF                      # same size, bad bytes
    with open(state_p, "wb") as f:
        f.write(bytes(blob))
    assert not verify_checkpoint(os.path.join(root, "20"))
    assert find_checkpoint(root)[1] == 10


def test_corrupt_checkpoint_direct_load_raises_integrity(tmp_path):
    root, exp, _ = _save_steps(tmp_path, [10])
    state_p = os.path.join(root, "10", "state.msgpack")
    blob = bytearray(open(state_p, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(state_p, "wb") as f:
        f.write(bytes(blob))
    with pytest.raises(CheckpointIntegrityError, match="integrity"):
        load_checkpoint(os.path.join(root, "10"), exp.init_train_state(0))


@pytest.mark.faultinject
def test_crash_mid_save_leaves_previous_checkpoint_usable(tmp_path):
    """A crash between the state write and the publish rename must leave
    only a tmp.* leftover; the previous step stays the resume target, and
    a later save of the same step succeeds over the leftover."""
    root, exp, ts = _save_steps(tmp_path, [10])

    def _crash(dirname, t_env):
        raise RuntimeError("injected crash mid-checkpoint")

    resilience.register_fault("checkpoint.staged", _crash)
    with pytest.raises(RuntimeError, match="injected crash"):
        save_checkpoint(root, 20, ts)
    assert os.path.isdir(os.path.join(root, "tmp.20"))
    assert not os.path.isdir(os.path.join(root, "20"))
    assert find_checkpoint(root) == (os.path.join(root, "10"), 10)

    resilience.clear_faults()
    d = save_checkpoint(root, 20, ts)                 # retry over leftover
    assert verify_checkpoint(d)
    assert find_checkpoint(root)[1] == 20


@pytest.mark.faultinject
def test_torn_but_published_write_caught_by_checksum(tmp_path):
    """Even if a torn state file somehow gets published (injector
    truncates the staged blob AFTER hashing), the checksum catches it on
    scan and selection skips back."""
    root, _, ts = _save_steps(tmp_path, [10])

    def _truncate(dirname, t_env):
        p = os.path.join(dirname, "state.msgpack")
        blob = open(p, "rb").read()
        with open(p, "wb") as f:
            f.write(blob[: len(blob) // 3])

    resilience.register_fault("checkpoint.staged", _truncate)
    save_checkpoint(root, 20, ts)                     # publishes torn bytes
    resilience.clear_faults()
    assert os.path.isdir(os.path.join(root, "20"))
    assert not verify_checkpoint(os.path.join(root, "20"))
    assert find_checkpoint(root)[1] == 10


def test_resave_same_step_replaces_published_dir(tmp_path):
    root, _, ts = _save_steps(tmp_path, [10])
    d = save_checkpoint(root, 10, ts)                 # emergency-at-cadence
    assert verify_checkpoint(d)
    assert find_checkpoint(root)[1] == 10


def test_retention_keeps_last_k_and_every_nth(tmp_path):
    root, _, _ = _save_steps(tmp_path, [10, 20, 30, 40, 50, 60])
    os.makedirs(os.path.join(root, "tmp.70"))         # crash leftover
    removed = prune_checkpoints(root, keep_last=2, keep_every=30)
    assert sorted(removed) == [10, 20, 40]
    kept = sorted(int(n) for n in os.listdir(root) if n.isdigit())
    assert kept == [30, 50, 60]
    assert not os.path.exists(os.path.join(root, "tmp.70"))
    assert all(verify_checkpoint(os.path.join(root, str(s))) for s in kept)


# ---------------------------------------------------------------------------
# non-finite guard rail
# ---------------------------------------------------------------------------

@pytest.mark.faultinject
def test_nonfinite_step_is_noop_on_params_and_opt(tmp_path):
    """An injected NaN loss at step k: all_finite trips, params AND
    optimizer state pass through bit-identical; the next (clean) step
    trains normally."""
    cfg = tiny_cfg(tmp_path, res_kw=dict(inject_nan_at_step=0))
    exp = Experiment.build(cfg)
    ts = exp.init_train_state(0)
    rollout, insert, train_iter = exp.jitted_programs()
    for i in range(2):                                # fill replay >= batch
        rs, batch, _ = rollout(ts.learner.params["agent"], ts.runner,
                               test_mode=False)
        ts = ts.replace(runner=rs, buffer=insert(ts.buffer, batch),
                        episode=ts.episode + cfg.batch_size_run)

    before = jax.device_get(ts.learner)
    prio_before = np.asarray(jax.device_get(ts.buffer.priorities))
    ts, info = train_iter(ts, jax.random.PRNGKey(1), jnp.asarray(12))
    info = jax.device_get(info)
    assert not bool(info["all_finite"])
    assert not np.isfinite(info["loss"])
    after = jax.device_get(ts.learner)
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(before.params),
            jax.tree_util.tree_leaves_with_path(after.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(kp))
    for a, b in zip(jax.tree_util.tree_leaves(before.opt_state),
                    jax.tree_util.tree_leaves(after.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # priorities untouched too (a NaN priority would win every PER draw)
    prio_after = np.asarray(jax.device_get(ts.buffer.priorities))
    np.testing.assert_array_equal(prio_before, prio_after)
    assert np.isfinite(prio_after).all()
    # train_steps still counts the attempt (fault step indices stay
    # monotonic across skips)
    assert int(after.train_steps) == int(before.train_steps) + 1

    # next step (train_steps=1 != inject_nan_at_step) trains normally
    ts2, info2 = train_iter(ts, jax.random.PRNGKey(2), jnp.asarray(24))
    assert bool(jax.device_get(info2["all_finite"]))
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(after.params),
                        jax.tree_util.tree_leaves(
                            jax.device_get(ts2.learner.params))))
    assert moved, "clean step after a skipped one must update params"


@pytest.mark.faultinject
def test_nan_injection_recovers_end_to_end(tmp_path):
    """Driver-level recovery: a NaN at train step k trips the guard, the
    driver restores the newest checkpoint (saved the same iteration, so
    its train_steps is already past k) and the run completes."""
    cfg = tiny_cfg(tmp_path, t_max=120,
                   res_kw=dict(inject_nan_at_step=2, nonfinite_tolerance=1,
                               max_restores=2))
    ts = run(cfg, Logger())
    # the run went the distance and kept training after the restore
    assert int(jax.device_get(ts.runner.t_env)) > cfg.t_max
    assert int(jax.device_get(ts.learner.train_steps)) > 3
    # the guard logged the event into the metric stream
    keys = set()
    for p in glob.glob(os.path.join(tmp_path, "*", "metrics.jsonl")):
        with open(p) as f:
            keys.update(json.loads(line)["key"] for line in f)
    assert "nonfinite_steps" in keys
    # params came out finite
    leaves = jax.tree_util.tree_leaves(
        jax.device_get(ts.learner.params))
    assert all(np.isfinite(np.asarray(x)).all() for x in leaves)


@pytest.mark.faultinject
@pytest.mark.slow   # full run() to an abort; the recover-with-checkpoint path stays in-gate
def test_nan_without_checkpoint_aborts_with_diagnosis(tmp_path):
    cfg = tiny_cfg(tmp_path, save_model=False,
                   res_kw=dict(inject_nan_at_step=0, nonfinite_tolerance=1))
    with pytest.raises(RuntimeError, match="diverged"):
        run(cfg, Logger())


# ---------------------------------------------------------------------------
# preemption handling
# ---------------------------------------------------------------------------

def test_shutdown_guard_latches_real_signal():
    prev = signal.getsignal(signal.SIGTERM)
    with resilience.ShutdownGuard.install() as guard:
        assert guard.installed and not guard.triggered
        signal.raise_signal(signal.SIGTERM)
        assert guard.triggered
        assert guard.signame == "SIGTERM"
    assert signal.getsignal(signal.SIGTERM) is prev


@pytest.mark.faultinject
@pytest.mark.slow   # full run() (~22 s); same guard path runs in-gate at K>1 in test_superstep
def test_sigterm_writes_emergency_checkpoint_and_returns(tmp_path):
    """A real SIGTERM mid-run: the loop breaks at the next iteration
    boundary, writes one emergency checkpoint, and returns normally (the
    CLI then exits 0) — preemption loses at most one iteration, not up to
    save_model_interval steps."""
    cfg = tiny_cfg(tmp_path, t_max=100_000, save_model_interval=10_000)

    def _preempt(t_env, guard, **kw):
        if t_env >= 24:
            signal.raise_signal(signal.SIGTERM)

    resilience.register_fault("driver.iteration", _preempt)
    ts = run(cfg, Logger())                           # returns, no raise
    stopped_at = int(jax.device_get(ts.runner.t_env))
    assert stopped_at < cfg.t_max, "run must have stopped early"

    model_dir = glob.glob(os.path.join(tmp_path, "models", "*"))[0]
    found = find_checkpoint(model_dir)
    assert found is not None
    dirname, step = found
    # the emergency checkpoint is the NEWEST step and covers the stop
    # point (save_model_interval alone would have left step 12)
    assert step >= 24
    assert verify_checkpoint(dirname)
    exp = Experiment.build(tiny_cfg(tmp_path, t_max=100_000,
                                    save_model_interval=10_000))
    restored = load_checkpoint(dirname, exp.init_train_state(1))
    leaves = jax.tree_util.tree_leaves(
        jax.device_get(restored.learner.params))
    assert all(np.isfinite(np.asarray(x)).all() for x in leaves)
    # default SIGTERM disposition restored after the run
    assert signal.getsignal(signal.SIGTERM) is signal.SIG_DFL


@pytest.mark.slow
@pytest.mark.faultinject
def test_sigterm_subprocess_exits_zero(tmp_path):
    """Full black-box preemption: SIGTERM to a real training process →
    exit code 0 + a loadable emergency checkpoint (acceptance criterion).
    Marked slow: pays a fresh interpreter + jit compile."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "t2omca_tpu", "train",
         "t_max=1000000", "batch_size_run=2", "batch_size=4",
         "env_args.agv_num=3", "env_args.episode_limit=6",
         "model.emb=8", "model.heads=2", "model.depth=1",
         "model.mixer_emb=8", "model.mixer_heads=2", "model.mixer_depth=1",
         "replay.buffer_size=8", "test_interval=1000000",
         "log_interval=120", "runner_log_interval=120",
         "save_model_interval=1000000",
         f"local_results_path={tmp_path}"],
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        # wait until the loop is demonstrably spinning (a checkpoint-free
        # signal: the cadence log line), then preempt
        deadline = time.time() + 300
        for line in proc.stdout:
            if "t_env:" in line or time.time() > deadline:
                break
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, out
    model_dirs = glob.glob(os.path.join(tmp_path, "models", "*"))
    assert model_dirs, out
    assert find_checkpoint(model_dirs[0]) is not None


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_resilience_config_sanity_and_overrides():
    with pytest.raises(ValueError, match="nonfinite_tolerance"):
        sanity_check(TrainConfig(
            resilience=ResilienceConfig(nonfinite_tolerance=-1)))
    with pytest.raises(ValueError, match="max_restores"):
        sanity_check(TrainConfig(
            resilience=ResilienceConfig(max_restores=-1)))
    with pytest.raises(ValueError, match="keep_last"):
        sanity_check(TrainConfig(
            resilience=ResilienceConfig(keep_last=-1)))
    with pytest.raises(ValueError, match="tests nothing"):
        sanity_check(TrainConfig(resilience=ResilienceConfig(
            inject_nan_at_step=5, nonfinite_tolerance=0)))
    # CLI-style overrides route into the sub-config, dotted or flat
    cfg = load_config(overrides=("resilience.keep_last=3",
                                 "nonfinite_tolerance=7"))
    assert cfg.resilience.keep_last == 3
    assert cfg.resilience.nonfinite_tolerance == 7


@pytest.mark.slow   # full run() with pruning (~24 s); prune_checkpoints logic pinned directly above
def test_retention_runs_inside_driver(tmp_path):
    """keep_last wired through run_sequential: after training, at most
    keep_last checkpoints remain on disk."""
    cfg = tiny_cfg(tmp_path, res_kw=dict(keep_last=2))
    run(cfg, Logger())
    model_dir = glob.glob(os.path.join(tmp_path, "models", "*"))[0]
    steps = [n for n in os.listdir(model_dir) if n.isdigit()]
    assert 0 < len(steps) <= 2
