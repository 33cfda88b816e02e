"""The multi-host (DCN) leg, actually multi-process (SURVEY.md §2.2
'Communication backend'; A8): two OS processes x 4 virtual CPU devices
each form one 8-device global mesh via ``maybe_initialize_distributed``
(the production entry, driven by the standard topology env vars), and the
full rollout -> insert -> train step runs sharded ACROSS the process
boundary — the gradient psum rides the cross-process collective backend.

This is the strongest distributed evidence available without a pod: the
same code path on a TPU pod only swaps gloo for ICI/DCN."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from t2omca_tpu.parallel import maybe_initialize_distributed
from t2omca_tpu.utils import resilience

# two fresh interpreters + gloo rendezvous + full program compiles per
# 2-process test (~200 s on the 2-core CI box) — far outside the tier-1
# 870 s budget; those carry @pytest.mark.slow individually. The init
# retry/backoff tests below are in-gate (host-only, milliseconds).
REPO = os.path.join(os.path.dirname(__file__), "..")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _spawn_workers(extra_env=None):
    """Start the 2-process worker pair; return the live Popen handles."""
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.abspath(REPO)]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COORDINATOR_ADDRESS"] = f"localhost:{port}"
        env["JAX_NUM_PROCESSES"] = "2"
        env["JAX_PROCESS_ID"] = str(pid)
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join("tests", "mp_worker.py")],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    return procs


def _launch_workers(extra_env=None):
    """Start the 2-process worker pair; return their stdouts."""
    outs = []
    for p in _spawn_workers(extra_env):
        out, err = p.communicate(timeout=900)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        outs.append(out)
    return outs


def _parse(outs, tag):
    vals = []
    for out in outs:
        lines = [l for l in out.splitlines() if l.startswith(tag + " ")]
        assert len(lines) == 1, out
        vals.append(float(lines[0].split()[1]))
    return vals


@pytest.mark.slow
def test_two_process_train_step_agrees():
    losses = _parse(_launch_workers(), "LOSS")
    # identical loss on both processes: the psum crossed the boundary
    np.testing.assert_allclose(losses[0], losses[1], rtol=0, atol=0)


@pytest.mark.slow
def test_two_process_checkpoint_restores_single_process(tmp_path):
    """A checkpoint SAVED FROM the 2-process mesh
    (gather-to-process-0 collective in save_checkpoint) restores in a
    plain single-process build via the model-only fallback
    (load_learner_state) and evaluates to the identical greedy metric."""
    from mp_worker import eval_fingerprint, worker_config
    from t2omca_tpu.run import Experiment
    from t2omca_tpu.utils.checkpoint import find_checkpoint, load_learner_state

    ckpt_root = str(tmp_path / "mh_ckpt")
    outs = _launch_workers({"MP_CKPT_DIR": ckpt_root})
    evals = _parse(outs, "EVAL")
    # both processes evaluate the identically-trained replicated model
    np.testing.assert_allclose(evals[0], evals[1], rtol=0, atol=0)

    found = find_checkpoint(ckpt_root)
    assert found is not None, "process 0 must have written the checkpoint"
    dirname, step = found
    assert step == 32
    assert os.path.exists(os.path.join(dirname, "meta.json"))

    # single-process restore, model-only fallback (reference semantics:
    # runner-side state starts fresh — exactly what eval_fingerprint uses)
    exp = Experiment.build(worker_config())
    ts = load_learner_state(dirname, exp.init_train_state(0))
    metric = eval_fingerprint(exp, ts.learner.params["agent"])
    np.testing.assert_allclose(metric, evals[0], rtol=0, atol=0)


@pytest.mark.slow
@pytest.mark.chaos
def test_sigkill_one_host_survivor_exits_resumable(tmp_path):
    """graftmorph chaos acceptance (ISSUE/docs/RESILIENCE.md §6): SIGKILL
    one of the two gloo hosts after the complete collective save. The
    survivor's preemption barrier must fail BOUNDED (not hang on the
    corpse), degrade to the per-host shard save, skip the resulting
    incomplete partial via the all-shards-or-skip gate, and exit 0
    pointing at the newest COMPLETE save — which a fresh SINGLE-process
    build (2 hosts x 4 devices -> 1 host) then restores elastically to
    the identical eval fingerprint."""
    from mp_worker import eval_fingerprint, worker_config
    from t2omca_tpu.run import Experiment
    from t2omca_tpu.utils.checkpoint import (find_checkpoint,
                                             restore_elastic,
                                             verify_checkpoint)

    ckpt_root = str(tmp_path / "chaos_ckpt")
    procs = _spawn_workers({"MP_CKPT_DIR": ckpt_root, "MP_CHAOS": "1"})
    out0, err0 = procs[0].communicate(timeout=900)
    # the victim died by SIGKILL (rc is -9 by design — never asserted);
    # reap it so no zombie outlives the test
    procs[1].communicate(timeout=900)
    assert procs[0].returncode == 0, f"survivor failed:\n{err0[-3000:]}"

    # the survivor resolved the COMPLETE collective save at 32, not its
    # own incomplete 1-of-2 partial at 48
    ckpt_lines = [l for l in out0.splitlines() if l.startswith("CKPT ")]
    assert ckpt_lines == ["CKPT 32"], out0

    # the degraded shard landed on disk but fails the completeness gate
    part = os.path.join(ckpt_root, "48")
    assert os.path.exists(os.path.join(part, "shard.0-of-2.msgpack"))
    assert not verify_checkpoint(part)
    found = find_checkpoint(ckpt_root)
    assert found is not None and found[1] == 32
    assert verify_checkpoint(found[0])

    # single-process elastic restore of the survivor-selected save: the
    # replicated model evaluates bit-identically to the 2-process run
    exp = Experiment.build(worker_config())
    ts = restore_elastic(found[0], exp.init_train_state(0))
    metric = eval_fingerprint(exp, ts.learner.params["agent"])
    np.testing.assert_allclose(metric, _parse([out0], "EVAL")[0],
                               rtol=0, atol=0)


# ---------------------------------------------------------------------------
# init retry/backoff (in-gate: host-only, the real initialize is stubbed).
# The 2-process rendezvous used to die ~50% of the time on this box to a
# transient gloo EnforceNotMet (CHANGES.md); maybe_initialize_distributed
# now retries transient-classified failures with backoff
# (utils.watchdog.retry_call) and the `backend.init` injection point makes
# the flake reproducible on demand.
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _no_fault_leaks():
    resilience.clear_faults()
    yield
    resilience.clear_faults()


def test_init_retries_transient_rendezvous_failure(monkeypatch):
    """Attempt 1 hits the gloo flake (injected at backend.init), attempt
    2 succeeds — the job starts instead of dying at step zero. The real
    initialize must run exactly once (on the surviving attempt)."""
    calls = []
    monkeypatch.setattr("jax.distributed.initialize",
                        lambda **kw: calls.append(kw))
    attempts = []

    def _flaky(attempt):
        attempts.append(attempt)
        if attempt == 1:
            raise RuntimeError(
                "Gloo connectFullMesh failed: EnforceNotMet preamble "
                "size mismatch")

    resilience.register_fault("backend.init", _flaky)
    assert maybe_initialize_distributed(
        coordinator_address="localhost:1", num_processes=2, process_id=0,
        retries=3)
    assert attempts == [1, 2]
    assert len(calls) == 1
    assert calls[0]["num_processes"] == 2


def test_init_does_not_retry_deterministic_error(monkeypatch):
    """A non-transient init error (bad topology) must fail on the FIRST
    attempt — retrying a deterministic mistake only delays the real
    diagnosis."""
    calls = []

    def _bad(**kw):
        calls.append(kw)
        raise RuntimeError("invalid process id -7")

    monkeypatch.setattr("jax.distributed.initialize", _bad)
    with pytest.raises(RuntimeError, match="invalid process id"):
        maybe_initialize_distributed(coordinator_address="localhost:1",
                                     num_processes=2, process_id=0,
                                     retries=3)
    assert len(calls) == 1


def test_init_exhausted_retries_reraises(monkeypatch):
    """A persistent transient failure exhausts the attempts and surfaces
    the LAST error unmodified (callers keep their except clauses).
    ``retries`` counts attempts BEYOND the first (the resilience.
    dispatch_retries convention): retries=1 -> 2 total attempts."""
    calls = []

    def _always_flaky(**kw):
        calls.append(kw)
        raise RuntimeError("connection reset by peer")

    monkeypatch.setattr("jax.distributed.initialize", _always_flaky)
    with pytest.raises(RuntimeError, match="connection reset"):
        maybe_initialize_distributed(coordinator_address="localhost:1",
                                     num_processes=2, process_id=0,
                                     retries=1)
    assert len(calls) == 2


def test_init_retries_zero_means_single_attempt(monkeypatch):
    """retries=0 disables the retry entirely — one attempt, matching
    resilience.dispatch_retries=0 in the driver."""
    calls = []

    def _always_flaky(**kw):
        calls.append(kw)
        raise RuntimeError("connection reset by peer")

    monkeypatch.setattr("jax.distributed.initialize", _always_flaky)
    with pytest.raises(RuntimeError, match="connection reset"):
        maybe_initialize_distributed(coordinator_address="localhost:1",
                                     num_processes=2, process_id=0,
                                     retries=0)
    assert len(calls) == 1


def test_init_nonnumeric_env_retries_falls_back(monkeypatch):
    """A non-numeric T2OMCA_INIT_RETRIES must not crash the job at
    startup — it is ignored with a warning and the default (2 retries,
    3 attempts) applies."""
    monkeypatch.setenv("T2OMCA_INIT_RETRIES", "lots")
    calls = []

    def _always_flaky(**kw):
        calls.append(kw)
        raise RuntimeError("connection reset by peer")

    monkeypatch.setattr("jax.distributed.initialize", _always_flaky)
    with pytest.raises(RuntimeError, match="connection reset"):
        maybe_initialize_distributed(coordinator_address="localhost:1",
                                     num_processes=2, process_id=0)
    assert len(calls) == 3


def test_init_already_initialized_stays_idempotent(monkeypatch):
    """The runtime's own double-init error still reads as success — and
    is never retried."""
    calls = []

    def _dup(**kw):
        calls.append(kw)
        raise RuntimeError("jax.distributed is already initialized")

    monkeypatch.setattr("jax.distributed.initialize", _dup)
    assert maybe_initialize_distributed(coordinator_address="localhost:1",
                                        num_processes=2, process_id=0,
                                        retries=3)
    assert len(calls) == 1


def test_init_only_once_message_stays_idempotent(monkeypatch):
    """jax phrases the double-init error 'distributed.initialize
    should only be called once.' (no 'already' anywhere) — it must still
    read as success on a pre-initialized runtime."""
    calls = []

    def _dup(**kw):
        calls.append(kw)
        raise RuntimeError("distributed.initialize should only be "
                           "called once.")

    monkeypatch.setattr("jax.distributed.initialize", _dup)
    assert maybe_initialize_distributed(coordinator_address="localhost:1",
                                        num_processes=2, process_id=0,
                                        retries=3)
    assert len(calls) == 1


def test_init_retry_resets_partial_state(monkeypatch):
    """jax assigns global_state.service/.client BEFORE
    client.connect(), so a transient rendezvous failure leaves the
    runtime half-initialized and a bare retry would die on the
    double-init RuntimeError instead of re-attempting. The retry path
    must tear the partial state down (jax.distributed.shutdown) between
    attempts so attempt 2 genuinely re-initializes."""
    st = {"initialized": False, "connects": 0, "shutdowns": 0}

    def _partial_state_init(**kw):
        if st["initialized"]:
            raise RuntimeError("distributed.initialize should only be "
                               "called once.")
        st["initialized"] = True        # set BEFORE the connect attempt
        st["connects"] += 1
        if st["connects"] == 1:
            raise RuntimeError(
                "Gloo connectFullMesh failed: EnforceNotMet preamble "
                "size mismatch")

    def _shutdown():
        st["initialized"] = False
        st["shutdowns"] += 1

    monkeypatch.setattr("jax.distributed.initialize", _partial_state_init)
    monkeypatch.setattr("jax.distributed.shutdown", _shutdown)
    assert maybe_initialize_distributed(coordinator_address="localhost:1",
                                        num_processes=2, process_id=0,
                                        retries=3)
    assert st["connects"] == 2          # attempt 2 really re-initialized
    assert st["shutdowns"] == 1         # partial state torn down once
    assert st["initialized"]            # and the final state is live


def test_init_failed_reset_does_not_misread_double_init(monkeypatch):
    """If the between-attempts teardown fails, the double-init error on a
    RETRY means this call's own half-initialized runtime — not a
    pre-initialized one. It must surface as a failure instead of
    reporting success on a never-connected runtime that would wedge at
    the first collective."""
    st = {"initialized": False, "connects": 0}

    def _partial_state_init(**kw):
        if st["initialized"]:
            raise RuntimeError("distributed.initialize should only be "
                               "called once.")
        st["initialized"] = True        # set BEFORE the connect attempt
        st["connects"] += 1
        raise RuntimeError(
            "Gloo connectFullMesh failed: EnforceNotMet preamble "
            "size mismatch")

    def _broken_shutdown():
        raise RuntimeError("cannot shut down a half-connected client")

    monkeypatch.setattr("jax.distributed.initialize", _partial_state_init)
    monkeypatch.setattr("jax.distributed.shutdown", _broken_shutdown)
    with pytest.raises(RuntimeError, match="only be called once"):
        maybe_initialize_distributed(coordinator_address="localhost:1",
                                     num_processes=2, process_id=0,
                                     retries=3)
    assert st["connects"] == 1          # the real connect ran only once


def test_init_no_topology_is_a_noop(monkeypatch):
    for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "JAX_PROCESS_ID", "T2OMCA_MULTIHOST"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr("jax.distributed.initialize",
                        lambda **kw: pytest.fail("must not initialize"))
    assert not maybe_initialize_distributed()
