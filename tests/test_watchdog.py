"""Dispatch watchdog + retry/backoff + degradation ladder
(``t2omca_tpu/utils/watchdog.py``, docs/RESILIENCE.md §5): unit tests at
millisecond timeouts for the heartbeat monitor, the transient-error
classification/backoff, and the ladder policy — then driver integration
on the CPU backend: an injected hang at ``dispatch.superstep`` must fire
the watchdog within the configured timeout, produce a VALID emergency
checkpoint, and let a fresh driver resume to the original t_env target
(the PR acceptance criterion); injected transient failures must be
retried with backoff; exhausted retries must walk the ladder
(superstep K→1 → restore → abort-with-diagnosis).
"""

import glob
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from t2omca_tpu.config import (EnvConfig, ModelConfig, ReplayConfig,
                               ResilienceConfig, TrainConfig, load_config,
                               sanity_check)
from t2omca_tpu.run import Experiment, run
from t2omca_tpu.utils import resilience, watchdog
from t2omca_tpu.utils.checkpoint import find_checkpoint, verify_checkpoint
from t2omca_tpu.utils.logging import Logger


@pytest.fixture(autouse=True)
def _no_fault_leaks():
    resilience.clear_faults()
    yield
    resilience.clear_faults()


# ---------------------------------------------------------------------------
# Watchdog unit tests (millisecond timeouts; no jax programs)
# ---------------------------------------------------------------------------

def _wait_for(pred, timeout=2.0, step=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(step)
    return pred()


def _warm(wd, *phases):
    """Complete each phase once: the strict timeout only applies to warm
    phases (first occurrence = compile, exempt)."""
    for p in phases:
        wd.stamp(p)
        wd.clear()


def test_watchdog_fires_on_stall_with_diagnosis():
    stalls, seen_states = [], []

    def _cb(diag):
        seen_states.append(diag.state)     # state visible TO the callback
        stalls.append(diag)

    wd = watchdog.Watchdog(0.05, on_stall=_cb, poll_s=0.01)
    with wd:
        _warm(wd, "dispatch.superstep")
        wd.stamp("dispatch.superstep", t_env=24, state="the-state")
        assert _wait_for(lambda: wd.stall_count == 1)
        diag = wd.take_diagnosis()
    assert diag is not None
    assert diag.phase == "dispatch.superstep"
    assert diag.t_env == 24
    assert diag.elapsed_s >= 0.05
    assert diag.timeout_s == 0.05
    assert diag.backend == jax.default_backend()
    # the emergency-save callback saw the stamped state; once it
    # completed, the retained diagnosis dropped the reference (keeping
    # it would pin the pre-stall TrainState — device ring included —
    # through the recovery and exit paths)
    assert seen_states == ["the-state"]
    assert _wait_for(lambda: diag.state is None)
    # the callback saw the same diagnosis; take_diagnosis consumed it
    assert stalls and stalls[0].phase == "dispatch.superstep"
    assert wd.take_diagnosis() is None
    # serializable diagnosis: state stays out of the JSON payload
    assert "state" not in diag.to_dict()
    assert json.dumps(diag.to_dict())


def test_watchdog_fires_once_per_stamp():
    wd = watchdog.Watchdog(0.03, poll_s=0.01)
    with wd:
        _warm(wd, "p")
        wd.stamp("p", t_env=1)
        assert _wait_for(lambda: wd.stall_count == 1)
        time.sleep(0.15)                       # stall persists, no re-fire
        assert wd.stall_count == 1
        wd.stamp("p", t_env=2)                 # NEW stamp can fire again
        assert _wait_for(lambda: wd.stall_count == 2)


def test_watchdog_wedged_on_stall_does_not_blind_monitor():
    """on_stall runs on its own thread: a callback stuck inside the
    stalled backend (the emergency save blocking on a dead device) must
    not stop the monitor from firing for LATER stalls — otherwise the
    first one permanently disables the hang detection the watchdog
    exists to provide."""
    fired = []
    release = threading.Event()

    def _wedging_cb(diag):
        fired.append(diag.phase)
        if len(fired) == 1:
            release.wait(5.0)              # first callback wedges

    wd = watchdog.Watchdog(0.03, on_stall=_wedging_cb, poll_s=0.01)
    try:
        with wd:
            _warm(wd, "a", "b")
            wd.stamp("a", t_env=1)
            assert _wait_for(lambda: len(fired) == 1)
            wd.clear()                     # the call returned late...
            wd.stamp("b", t_env=2)         # ...and the next one stalls
            assert _wait_for(lambda: len(fired) == 2), \
                "monitor went blind behind the wedged callback"
            wd.clear()
    finally:
        release.set()
    assert fired == ["a", "b"]


def test_watchdog_cleared_and_idle_never_fires():
    # generous timeout vs the stamp→clear gap: a loaded CI box can
    # deschedule this thread for tens of ms and must not cause a fire
    wd = watchdog.Watchdog(1.0, poll_s=0.01)
    with wd:
        for i in range(4):                     # fast calls: stamp → clear
            wd.stamp("fast", t_env=i)
            time.sleep(0.01)
            wd.clear()
        time.sleep(0.2)                        # idle (no armed stamp)
        assert wd.stall_count == 0
        assert wd.take_diagnosis() is None


def test_watchdog_watch_context_manager_and_exception_path():
    wd = watchdog.Watchdog(0.05, poll_s=0.01)
    with wd:
        with wd.watch("ok", t_env=1):
            pass
        with pytest.raises(ValueError):
            with wd.watch("boom", t_env=2):
                raise ValueError("dispatch failed")
        time.sleep(0.15)                       # both cleared → no fire
        assert wd.stall_count == 0


def test_watchdog_hard_exit_fires_after_grace():
    exits = []
    wd = watchdog.Watchdog(0.03, poll_s=0.01, grace_s=0.05,
                           exit_code=17, _exit=exits.append)
    with wd:
        _warm(wd, "wedged")
        wd.stamp("wedged", t_env=5)            # never cleared
        assert _wait_for(lambda: bool(exits))
    assert exits == [17]


def test_watchdog_hard_exit_canceled_when_main_progresses():
    exits = []
    # grace generous vs the detect→clear gap so CI load can't turn the
    # cancellation race into a spurious hard exit
    wd = watchdog.Watchdog(0.03, poll_s=0.01, grace_s=2.0,
                           _exit=exits.append)
    with wd:
        _warm(wd, "slow")
        wd.stamp("slow", t_env=5)
        assert _wait_for(lambda: wd.stall_count == 1)
        wd.clear()                             # the call returned late
        time.sleep(0.3)
    assert exits == []


def test_watchdog_hard_exit_canceled_by_stop():
    exits = []
    wd = watchdog.Watchdog(0.03, poll_s=0.01, grace_s=10.0,
                           _exit=exits.append)
    wd.start()
    _warm(wd, "wedged")
    wd.stamp("wedged", t_env=5)
    assert _wait_for(lambda: wd.stall_count == 1)
    wd.stop()                                  # orderly exit path
    time.sleep(0.05)
    assert exits == []


def test_watchdog_on_stall_runs_off_main_thread_and_survives_errors():
    seen = []

    def _cb(diag):
        seen.append(threading.current_thread())
        raise RuntimeError("callback bug must not kill the monitor")

    wd = watchdog.Watchdog(0.03, on_stall=_cb, poll_s=0.01)
    with wd:
        _warm(wd, "a", "b")
        wd.stamp("a", t_env=1)
        assert _wait_for(lambda: len(seen) == 1)
        assert seen[0] is not threading.main_thread()
        wd.stamp("b", t_env=2)                 # monitor still alive
        assert _wait_for(lambda: len(seen) == 2)


def test_watchdog_first_occurrence_is_compile_exempt():
    """The first occurrence of a phase includes the XLA compile — the
    strict timeout must NOT apply to it (default: unbounded), and an
    exception does not count as the warming completion (attempt 2 may
    still be the one that compiles)."""
    wd = watchdog.Watchdog(0.03, poll_s=0.01)
    with wd:
        wd.stamp("cold", t_env=0)              # first occurrence: compiling
        time.sleep(0.15)
        assert wd.stall_count == 0
        # an exception-terminated watch leaves the phase cold
        with pytest.raises(RuntimeError):
            with wd.watch("cold2", t_env=0):
                raise RuntimeError("injected failure on attempt 1")
        wd.stamp("cold2", t_env=0)             # retry: may compile now
        time.sleep(0.15)
        assert wd.stall_count == 0
        wd.clear()                             # completes → warm
        wd.stamp("cold2", t_env=1)
        assert _wait_for(lambda: wd.stall_count == 1)


def test_watchdog_first_timeout_bounds_cold_phases():
    """resilience.first_dispatch_timeout: an explicit bound on the cold
    occurrence (a hang at start-up) — the diagnosis must
    carry the limit that actually fired."""
    wd = watchdog.Watchdog(10.0, poll_s=0.01, first_timeout_s=0.05)
    with wd:
        wd.stamp("cold", t_env=0)
        assert _wait_for(lambda: wd.stall_count == 1)
        diag = wd.take_diagnosis()
    assert diag.timeout_s == 0.05


def test_watchdog_rejects_nonpositive_timeout():
    with pytest.raises(ValueError, match="timeout_s"):
        watchdog.Watchdog(0.0)


def test_exit_deadline_fires_when_region_overruns():
    """The preemption-exit save runs after wd.stop() — ExitDeadline is
    the only bound left over it. A region that outlives the bound must
    be hard-exited with the stall exit code."""
    exits = []
    with watchdog.ExitDeadline(0.05, 17, label="test save",
                               _exit=exits.append):
        assert _wait_for(lambda: bool(exits))
    assert exits == [17]


def test_exit_deadline_canceled_on_completion_and_exception():
    exits = []
    with watchdog.ExitDeadline(0.05, 17, _exit=exits.append):
        pass                                   # completes within bound
    with pytest.raises(RuntimeError):
        with watchdog.ExitDeadline(0.05, 17, _exit=exits.append):
            raise RuntimeError("save failed fast — deadline must still "
                               "be canceled")
    time.sleep(0.15)
    assert exits == []


# ---------------------------------------------------------------------------
# retry/backoff + classification
# ---------------------------------------------------------------------------

def test_is_transient_classification():
    assert watchdog.is_transient(RuntimeError(
        "EnforceNotMet: preamble size mismatch (gloo)"))
    assert watchdog.is_transient(ConnectionResetError(104, "reset"))
    assert watchdog.is_transient(TimeoutError())
    assert watchdog.is_transient(RuntimeError("DEADLINE_EXCEEDED: dcn"))
    assert watchdog.is_transient(OSError("Connection refused"))
    assert not watchdog.is_transient(ValueError("bad shape (4, 3)"))
    assert not watchdog.is_transient(KeyError("missing"))
    assert not watchdog.is_transient(SystemExit(1))


def test_backoff_delay_exponential_with_bounded_jitter():
    flat = [watchdog.backoff_delay(a, 0.5, jitter=0.0) for a in (1, 2, 3)]
    assert flat == [0.5, 1.0, 2.0]
    assert watchdog.backoff_delay(10, 0.5, max_s=3.0, jitter=0.0) == 3.0
    d = watchdog.backoff_delay(1, 1.0, jitter=0.25)
    assert 1.0 <= d <= 1.25


def test_retry_call_retries_transient_then_succeeds():
    sleeps, calls = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("connection reset by peer")
        return "ok"

    assert watchdog.retry_call(flaky, attempts=4, backoff_s=0.5,
                               jitter=0.0, sleep=sleeps.append) == "ok"
    assert len(calls) == 3
    assert sleeps == [0.5, 1.0]                # exponential between attempts


def test_retry_call_nonretriable_raises_first_attempt():
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("deterministic bug")

    with pytest.raises(ValueError, match="deterministic"):
        watchdog.retry_call(broken, attempts=5, sleep=lambda s: None)
    assert len(calls) == 1


def test_retry_call_exhaustion_reraises_last_error():
    calls = []

    def always():
        calls.append(1)
        raise TimeoutError(f"try {len(calls)}")

    with pytest.raises(TimeoutError, match="try 3"):
        watchdog.retry_call(always, attempts=3, sleep=lambda s: None)
    assert len(calls) == 3


# ---------------------------------------------------------------------------
# degradation ladder policy
# ---------------------------------------------------------------------------

def test_ladder_rung_order_degrade_restore_abort():
    ladder = watchdog.DegradationLadder(max_restores=2)
    assert ladder.next_action(can_degrade=True) == "degrade"
    assert ladder.degraded
    # degrade only happens once, even if the caller could still degrade
    assert ladder.next_action(can_degrade=True) == "restore"
    assert ladder.next_action(can_degrade=True) == "restore"
    assert ladder.next_action(can_degrade=True) == "abort"
    assert ladder.failures == 4
    assert ladder.restores == 2


def test_ladder_skips_degrade_when_not_applicable():
    ladder = watchdog.DegradationLadder(max_restores=1)
    assert ladder.next_action(can_degrade=False) == "restore"
    assert ladder.next_action(can_degrade=False) == "abort"
    assert watchdog.DegradationLadder(0).next_action(False) == "abort"


def test_dispatch_failed_carries_phase_and_cause():
    cause = RuntimeError("socket closed")
    df = watchdog.DispatchFailed("dispatch.superstep", 3, cause)
    assert df.phase == "dispatch.superstep"
    assert df.attempts == 3
    assert df.cause is cause
    assert "dispatch.superstep" in str(df) and "socket closed" in str(df)


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_resilience_watchdog_config_sanity_and_overrides():
    for bad in (dict(dispatch_timeout=-1.0), dict(stall_grace_s=-1.0),
                dict(stall_exit_code=0), dict(stall_exit_code=300),
                dict(dispatch_retries=-1), dict(retry_backoff_s=-0.5),
                dict(first_dispatch_timeout=-1.0),
                # silently-dead knob: first_dispatch_timeout only matters
                # once dispatch_timeout > 0 constructs the watchdog
                dict(first_dispatch_timeout=120.0, dispatch_timeout=0.0)):
        with pytest.raises(ValueError):
            sanity_check(TrainConfig(resilience=ResilienceConfig(**bad)))
    cfg = load_config(overrides=("resilience.dispatch_timeout=2.5",
                                 "dispatch_retries=4",
                                 "resilience.degrade_superstep=false"))
    assert cfg.resilience.dispatch_timeout == 2.5
    assert cfg.resilience.dispatch_retries == 4
    assert cfg.resilience.degrade_superstep is False
    # defaults: watchdog fully disabled
    assert TrainConfig().resilience.dispatch_timeout == 0.0


# ---------------------------------------------------------------------------
# driver integration (tiny CPU configs; millisecond watchdog timeouts)
# ---------------------------------------------------------------------------

def tiny_cfg(tmp_path, **kw):
    replay_kw = kw.pop("replay_kw", {})
    res_kw = kw.pop("res_kw", {})
    defaults = dict(
        t_max=60, batch_size_run=2, batch_size=4, test_interval=1_000_000,
        test_nepisode=2, log_interval=12, runner_log_interval=12,
        save_model=True, save_model_interval=12,
        local_results_path=str(tmp_path), use_tensorboard=False,
        epsilon_anneal_time=50,
        env_args=EnvConfig(agv_num=3, mec_num=2, num_channels=2,
                           episode_limit=6),
        model=ModelConfig(emb=8, heads=2, depth=1, mixer_emb=8,
                          mixer_heads=2, mixer_depth=1),
        replay=ReplayConfig(buffer_size=8, **replay_kw),
        resilience=ResilienceConfig(stall_grace_s=0.0, **res_kw),
    )
    defaults.update(kw)
    return sanity_check(TrainConfig(**defaults))


def _metric_rows(tmp_path):
    rows = []
    for p in glob.glob(os.path.join(tmp_path, "*", "metrics.jsonl")):
        with open(p) as f:
            rows.extend(json.loads(line) for line in f)
    return rows


@pytest.mark.faultinject
@pytest.mark.slow   # two full run() legs (~60 s); the same hang scenario
                    # runs in the chaos battery (scripts/chaos.sh) and the
                    # watchdog fire/diagnosis mechanics are pinned by the
                    # millisecond unit tests above
def test_injected_hang_fires_watchdog_then_fresh_driver_resumes(tmp_path):
    """The acceptance chaos criterion end-to-end: a hang injected at
    ``dispatch.superstep`` → the watchdog fires within the configured
    timeout (diagnosis proves it fired DURING the hang), writes a VALID
    emergency checkpoint, the run exits cleanly — and a fresh driver
    resumes from it and reaches the original t_max (losing at most K
    iterations)."""
    # timeout chosen with wide headroom over a warm tiny-config dispatch
    # (~tens of ms) so a loaded CI box cannot trip it spuriously, while
    # the injected hang still dwarfs it
    cfg = tiny_cfg(tmp_path, superstep=2,
                   res_kw=dict(dispatch_timeout=0.75))
    hang_s = 2.5
    hung = []

    def _hang(t_env, **kw):
        if t_env >= 24 and not hung:
            hung.append(t_env)
            time.sleep(hang_s)

    resilience.register_fault("dispatch.superstep", _hang)
    ts = run(cfg, Logger())
    assert hung == [24], "the hang must have been injected exactly once"
    stopped_at = int(jax.device_get(ts.runner.t_env))
    assert stopped_at < cfg.t_max, "watchdog must have stopped the run"

    model_dir = glob.glob(os.path.join(tmp_path, "models", "*"))[0]
    # diagnosis persisted, and it fired within the timeout — i.e. while
    # the call was still hung, well before the hang resolved on its own
    with open(os.path.join(model_dir, "stall_diagnosis.json")) as f:
        diag = json.load(f)
    assert diag["phase"] == "dispatch.superstep"
    assert diag["t_env"] == 24
    assert cfg.resilience.dispatch_timeout <= diag["elapsed_s"] < hang_s
    # a valid (verify_checkpoint-passing) checkpoint covering the stall
    found = find_checkpoint(model_dir)
    assert found is not None
    dirname, step = found
    assert verify_checkpoint(dirname)
    assert step >= 24, "emergency checkpoint must cover the stall point"

    # fresh driver, no faults: resumes from the emergency checkpoint and
    # reaches the original target
    resilience.clear_faults()
    cfg2 = cfg.replace(checkpoint_path=model_dir)
    ts2 = run(cfg2, Logger())
    assert int(jax.device_get(ts2.runner.t_env)) > cfg.t_max


@pytest.mark.faultinject
@pytest.mark.slow   # full run() (~45 s); retry mechanics pinned fast by
                    # the retry_call unit tests + the in-gate abort tests
def test_transient_dispatch_and_gather_failures_retried(tmp_path):
    """One transient failure at the fused dispatch and one at the
    checkpoint gather: both retried with backoff, the run completes, and
    the fault counter lands in the metric stream."""
    cfg = tiny_cfg(tmp_path, superstep=2,
                   res_kw=dict(dispatch_retries=2, retry_backoff_s=0.01))
    seen, gather_seen = [], []

    def _flaky_dispatch(t_env, attempt, **kw):
        seen.append((t_env, attempt))
        if t_env == 24 and attempt == 1:
            raise RuntimeError("injected: connection reset by peer")

    def _flaky_gather(t_env, **kw):
        gather_seen.append(t_env)
        if len(gather_seen) == 1:
            raise RuntimeError("injected: collective timed out")

    resilience.register_fault("dispatch.superstep", _flaky_dispatch)
    resilience.register_fault("collective.gather", _flaky_gather)
    ts = run(cfg, Logger())
    assert int(jax.device_get(ts.runner.t_env)) > cfg.t_max
    # the failed dispatch was re-attempted at the same t_env
    assert (24, 1) in seen and (24, 2) in seen
    # the first save survived its injected gather failure via retry
    assert len(gather_seen) >= 2
    model_dir = glob.glob(os.path.join(tmp_path, "models", "*"))[0]
    assert find_checkpoint(model_dir) is not None
    rows = _metric_rows(tmp_path)
    faults = [r for r in rows if r["key"] == "dispatch_faults"]
    assert faults and faults[-1]["value"] >= 1


@pytest.mark.faultinject
@pytest.mark.slow   # full run() on the host-buffer path (~40 s)
def test_host_buffer_transient_dispatch_not_retried_in_place(tmp_path):
    """buffer_cpu_only dispatches carry non-idempotent HOST side effects
    inside the dispatched fn (``buffer.sample()`` advances the host RNG,
    the ring insert mutates host RAM) that commit-after-success cannot
    cover — so a transient failure must go straight to the ladder
    (restore) instead of replaying the dispatch in place, which would
    train on a different batch or double-insert episodes."""
    cfg = tiny_cfg(tmp_path, replay_kw=dict(buffer_cpu_only=True),
                   res_kw=dict(dispatch_retries=2, retry_backoff_s=0.01))
    train_attempts, fired = [], []

    def _flaky_train(t_env, attempt, **kw):
        train_attempts.append((t_env, attempt))
        if not fired:
            fired.append(t_env)
            raise RuntimeError("injected: connection reset by peer")

    resilience.register_fault("dispatch.train", _flaky_train)
    ts = run(cfg, Logger())
    assert int(jax.device_get(ts.runner.t_env)) > cfg.t_max
    # the transient failure was seen exactly once and NEVER re-attempted
    # in place: despite dispatch_retries=2, every hook call is attempt 1
    assert fired and all(a == 1 for _, a in train_attempts)
    # it routed to the ladder (restore rung) instead
    rows = _metric_rows(tmp_path)
    failures = [r for r in rows if r["key"] == "dispatch_failures"]
    assert failures and failures[-1]["value"] >= 1


@pytest.mark.faultinject
@pytest.mark.slow   # Experiment.build (~8 s); ladder policy pinned fast
                    # by the DegradationLadder unit tests above
def test_exhausted_retries_without_checkpoint_abort_with_diagnosis(tmp_path):
    """K=1, persistent transient failure at the rollout dispatch,
    save_model off: the ladder has no degrade rung and no checkpoint to
    restore — the run must abort with the captured diagnosis naming the
    phase. Fast: the injector raises before the program would compile."""
    cfg = tiny_cfg(tmp_path, save_model=False,
                   res_kw=dict(dispatch_retries=1, retry_backoff_s=0.001))

    def _always(t_env, **kw):
        raise RuntimeError("injected: backend unavailable")

    resilience.register_fault("dispatch.rollout", _always)
    with pytest.raises(RuntimeError,
                       match="degradation ladder") as excinfo:
        run(cfg, Logger())
    msg = str(excinfo.value)
    assert "dispatch.rollout" in msg
    assert "no checkpoints exist" in msg
    assert isinstance(excinfo.value.__cause__, watchdog.DispatchFailed)


@pytest.mark.faultinject
@pytest.mark.slow   # Experiment.build (~8 s); classification pinned fast
                    # by test_is_transient + retry_call unit tests
def test_nontransient_dispatch_error_propagates_unretried(tmp_path):
    """A deterministic error in the dispatch path must NOT be retried or
    laddered — it surfaces immediately with its own type."""
    cfg = tiny_cfg(tmp_path, save_model=False,
                   res_kw=dict(dispatch_retries=3))
    calls = []

    def _bug(t_env, attempt, **kw):
        calls.append(attempt)
        raise ValueError("deterministic shape bug")

    resilience.register_fault("dispatch.rollout", _bug)
    with pytest.raises(ValueError, match="shape bug"):
        run(cfg, Logger())
    assert calls == [1]


@pytest.mark.faultinject
@pytest.mark.slow   # compiles both loop shapes (~35 s); policy pinned fast above
def test_ladder_degrades_superstep_to_classic_loop(tmp_path):
    """Persistent failure of the FUSED dispatch only: the ladder drops
    K→1 and the run completes on the classic three-program path (the
    smaller blast radius rung), recording the escalation in stats."""
    cfg = tiny_cfg(tmp_path, superstep=2, save_model=False,
                   res_kw=dict(dispatch_retries=1, retry_backoff_s=0.001))
    fused = []

    def _kill_fused(t_env, attempt, **kw):
        fused.append((t_env, attempt))
        raise RuntimeError("injected: fused dispatch socket closed")

    resilience.register_fault("dispatch.superstep", _kill_fused)
    ts = run(cfg, Logger())
    # both attempts of the fused dispatch failed, then the classic loop
    # carried the run to completion
    assert fused == [(0, 1), (0, 2)]
    assert int(jax.device_get(ts.runner.t_env)) > cfg.t_max
    assert int(jax.device_get(ts.learner.train_steps)) > 0
    rows = _metric_rows(tmp_path)
    assert any(r["key"] == "dispatch_failures" for r in rows)
    assert any(r["key"] == "superstep_k" and r["value"] == 1 for r in rows)


@pytest.mark.faultinject
@pytest.mark.slow   # full run + mid-run restore (~30 s)
def test_ladder_restores_last_good_checkpoint_and_continues(tmp_path):
    """K=1 with checkpoints on: a burst of transient train-dispatch
    failures exhausts in-place retries, the ladder restores the newest
    checkpoint (t_env rewinds, host mirrors re-sync), the fault clears,
    and the run still reaches t_max."""
    cfg = tiny_cfg(tmp_path,
                   res_kw=dict(dispatch_retries=0, retry_backoff_s=0.001,
                               max_restores=2))
    failures = []

    def _burst(t_env, **kw):
        if t_env >= 36 and len(failures) < 1:
            failures.append(t_env)
            raise RuntimeError("injected: train dispatch timed out")

    resilience.register_fault("dispatch.train", _burst)
    ts = run(cfg, Logger())
    assert failures == [36]
    assert int(jax.device_get(ts.runner.t_env)) > cfg.t_max
    rows = _metric_rows(tmp_path)
    assert any(r["key"] == "dispatch_failures" for r in rows)
    # training continued past the restore
    assert int(jax.device_get(ts.learner.train_steps)) > 0
