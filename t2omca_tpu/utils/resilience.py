"""Resilience primitives: graceful-shutdown guard + fault-injection hooks.

Long-lived runs die three ways the happy-path driver cannot survive:
preemption (TPU pods get SIGTERM'd mid-iteration), torn checkpoints (a
crash mid-``save_checkpoint`` leaves a truncated ``state.msgpack`` at the
HIGHEST step, which a naive resume then selects), and numeric collapse
(one NaN loss poisons params, then every checkpoint after it). Podracer
(arxiv 2104.06272) treats preemption-safe checkpointing as table stakes;
EnvPool (arxiv 2206.10558) shows a long-running vectorized loop must
survive component faults. This module holds the two process-level pieces:

* :class:`ShutdownGuard` — installs SIGTERM/SIGINT handlers that only SET A
  FLAG; the driver loop polls it once per iteration and performs an orderly
  exit (final emergency checkpoint, resume hint, exit code 0). The handler
  itself does no I/O — async-signal-safe by construction.
* fault-injection registry (``register_fault``/``fire``) — named hook
  points inside the checkpoint writer and the driver loop where tests
  deterministically inject crashes (truncate a staged file, raise
  mid-write, deliver a signal at an exact ``t_env``). Production code calls
  ``fire(...)`` unconditionally; with nothing registered it is a dict
  lookup returning immediately.

The third piece — the non-finite guard over loss/grads — lives inside the
jitted train step (``learners/qmix_learner.py``) because it must not block
the async dispatch pipeline; the driver only counts its ``all_finite``
flags at the log cadence (``run.py``). Config knobs: ``resilience.*`` in
``config.py``; contract: ``docs/RESILIENCE.md``.
"""

from __future__ import annotations

import logging
import signal
import threading
from typing import Callable, Dict, List, Optional

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------- faults

#: hook point name -> injector callables, fired in registration order.
#:
#: The device-facing point names below (``dispatch.*``, ``fetch.*``,
#: ``collective.gather``, ``backend.init``) double as graftscope span
#: phases (``obs/spans.KNOWN_PHASES``): when ``config.ObsConfig.enabled``
#: the driver records a span around the same region each hook fires in,
#: so an injected fault/hang and its telemetry trail share one name.
#: graftlint rule GL110 keeps the two sets from drifting apart.
#:
#: Known points (each passes keyword context):
#:   ``checkpoint.staged``   dirname=<staging dir>, t_env=<int>
#:       after state.msgpack is written+fsynced into the tmp.<t_env>
#:       staging directory, BEFORE the sidecar write and atomic publish —
#:       raising here simulates a crash mid-checkpoint; truncating
#:       <dirname>/state.msgpack here simulates a torn write that still
#:       gets published (the checksum must catch it on resume).
#:   ``driver.iteration``    t_env=<int>, guard=<ShutdownGuard|None>,
#:                           ts=<TrainState>, key=<driver key>,
#:                           train_infos=<pending info rows>
#:       top of every run_sequential iteration — deliver a signal or trip
#:       the guard at an exact env-step. ``ts``/``key``/``train_infos``
#:       are the loop's own objects, for readers (the benchmark's
#:       window): the next dispatch donates ``ts``, so copy what is kept.
#:   ``dispatch.superstep``  t_env=<int>, attempt=<int>, k=<int>
#:       before EACH attempt of the fused K-iteration dispatch (run.py
#:       `_dispatch`) — sleep here to simulate a hung dispatch (the
#:       watchdog must fire), raise a transient-classified error to
#:       exercise retry/backoff and the degradation ladder.
#:   ``dispatch.rollout`` / ``dispatch.train``   t_env=<int>, attempt=<int>
#:       same, for the classic three-program loop's two dispatches.
#:   ``dispatch.test``       t_env=<int>, attempt=<int>
#:       same, for each test-cadence evaluation rollout.
#:   ``dispatch.wait``       t_env=<int>
#:       before the run-ahead ``block_until_ready`` — the steady-state
#:       blocking point where async device faults surface when
#:       per-stage sync is off; transient errors route to the ladder's
#:       restore rung (no in-place retry is possible at a sync point).
#:   ``fetch.train_infos``   t_env=<int>, train_infos=<the rows fetched>
#:       before the log-cadence device→host fetch of the accumulated
#:       train-info rows (non-finite flags + last stats row) — same
#:       sync-point routing as ``dispatch.wait``.
#:   ``fetch.train_stats`` / ``fetch.test_stats``   t_env=<int>
#:       before each StatsAccumulator device fetch (the per-push fold
#:       and the runner-log / test-quota flushes) — same sync-point
#:       routing as ``dispatch.wait``.
#:   ``collective.gather``   t_env=<int>, multihost=<bool>
#:       inside save_checkpoint's retried gather-to-host step (before the
#:       multi-host process_allgather sequence, or before the
#:       single-process device_get) — raise to simulate a dropped/flaky
#:       collective; the driver's save cadence retries transient errors.
#:   ``backend.init``        attempt=<int>
#:       inside each retried jax.distributed.initialize attempt
#:       (parallel/distributed.py) — raise a transient error to exercise
#:       the init retry that de-flakes the gloo rendezvous.
#:   ``actor.dispatch``      t_env=<int>, attempt=<int>
#:       before EACH attempt of the sebulba actor thread's rollout
#:       dispatch (run.run_sebulba) — sleep to simulate a wedged actor
#:       mesh (the actor-side watchdog fires, trips the guard, and the
#:       learner exits resumably); raise transient to exercise the
#:       actor-side retry and the actor-failure→ladder handoff.
#:   ``learner.dispatch``    t_env=<int>, attempt=<int>
#:       same, for the sebulba learner thread's sample→train→priority
#:       dispatch — sleep here for the wedged-learner chaos scenario
#:       (watchdog fires while the actor thread exits resumably).
#:   ``queue.put`` / ``queue.get``   t_env=<int>
#:       at the trajectory queue's two ends (actor-side d2d copy + slot
#:       scatter / learner-side slot gather + ring insert) — raise to
#:       exercise the queue boundaries' failure surfacing. These wait
#:       under backpressure/starvation by design, so they carry spans
#:       but no watchdog stamp (a full/empty queue is idleness, not a
#:       stall).
#:   ``params.sync``         t_env=<int>
#:       at the learner→actor parameter publish (learner side, stamped)
#:       and the actor's staleness-bounded adopt wait (span only).
#:   ``fleet.dispatch``      engine=<int>, attempt=<int>, rid=<int>
#:       inside EACH attempt of a fleet engine's per-request dispatch
#:       (serve/fleet.py), under the engine's own watchdog stamp —
#:       sleep to simulate a wedged engine (quarantine + hedge +
#:       restart), raise transient to exercise the in-place retry,
#:       raise non-transient to kill the engine outright.
#:   ``fleet.selfcheck``     engine=<int>, stage=<str>
#:       inside the engine health-check dispatch (start / restart /
#:       degrade / refresh stages) — raise at stage="refresh" to trip
#:       the post-swap health check and force the rolling refresh's
#:       auto-rollback.
#:   ``fleet.refresh``       stage=<str>, ...
#:       at the hot-refresh fold (stage="fold", ckpt=) and per-bucket
#:       fingerprint check (stage="fingerprint", bucket=, fingerprint=)
#:       — raise at "fold" to poison a refresh (must be REFUSED while
#:       the fleet keeps serving).
#:   ``preempt.barrier``     t_env=<int>, processes=<int>
#:       inside the coordinated-preemption stop-step negotiation
#:       (parallel/distributed.negotiate_stop_step), before the bounded
#:       KV-store barrier — raise to simulate a peer dying
#:       mid-negotiation; the driver must degrade to the per-host
#:       shard save instead of attempting a collective emergency save.
#:   ``checkpoint.shard_save``   t_env=<int>, shard=<int>, shards=<int>
#:       at the top of the degraded per-host shard write
#:       (utils/checkpoint.save_checkpoint_shards) — raise to kill the
#:       fallback save itself; the driver's exit path must survive and
#:       leave the last cadence checkpoint as the resume point.
#:   ``checkpoint.elastic``  dirname=<str>, format=<int|None>
#:       inside restore_elastic after the (verified) host read, before
#:       any topology reshape or device placement — raise to fault the
#:       elastic resume boundary (docs/RESILIENCE.md §6).
_FAULTS: Dict[str, List[Callable]] = {}


def register_fault(point: str, fn: Callable) -> None:
    """Register ``fn(**context)`` to run whenever ``point`` fires.

    Test-only by intent: nothing in the production config path registers
    injectors. Injectors run inline in the faulting thread and may raise —
    that IS the fault."""
    _FAULTS.setdefault(point, []).append(fn)


def clear_faults(point: Optional[str] = None) -> None:
    """Drop all injectors (or just ``point``'s). Tests pair this with
    ``register_fault`` in a fixture finalizer so faults never leak."""
    if point is None:
        _FAULTS.clear()
    else:
        _FAULTS.pop(point, None)


def fire(point: str, **context) -> None:
    """Run every injector registered for ``point``. No-op (one dict
    lookup) when nothing is registered — safe on hot paths."""
    for fn in _FAULTS.get(point, ()):
        fn(**context)


# ---------------------------------------------------------------- shutdown

class ShutdownGuard:
    """Flag-based SIGTERM/SIGINT latch for the driver loop.

    Usage::

        with ShutdownGuard.install() as guard:
            while training:
                if guard.triggered:
                    break          # orderly: emergency checkpoint + exit 0
                ...

    The handler records WHICH signal fired (``guard.signame``) and sets a
    ``threading.Event`` — nothing else, so it is safe at any interrupt
    point. A second delivery of the same signal while shutdown is already
    in progress re-raises the default behavior (operator escalation:
    kill -TERM twice = die now), so a wedged emergency checkpoint cannot
    make the process unkillable.

    Signal handlers are process-global and main-thread-only; ``install``
    degrades gracefully (returns a guard with ``installed == False``) when
    called off the main thread, where ``triggered`` can still be tripped
    programmatically via :meth:`request` (fault injection uses this).
    """

    def __init__(self) -> None:
        self._event = threading.Event()
        self._prev: Dict[int, object] = {}
        self.signame: Optional[str] = None
        self.installed = False

    # -- construction ----------------------------------------------------

    @classmethod
    def install(cls, signals=(signal.SIGTERM, signal.SIGINT)
                ) -> "ShutdownGuard":
        guard = cls()
        for s in signals:
            try:
                guard._prev[s] = signal.signal(s, guard._handler)
            except ValueError:
                # not the main thread (or an unsupported signal on this
                # platform): signal.signal refuses — run guarded-by-flag
                # only, preemption falls back to the default disposition
                logger.warning(
                    "ShutdownGuard: cannot install handler for %s "
                    "(not the main thread?) — graceful shutdown limited "
                    "to programmatic request()", signal.Signals(s).name)
                continue
            guard.installed = True
        return guard

    def _handler(self, signum, frame) -> None:
        if self._event.is_set():
            # escalation: restore default dispositions so the NEXT signal
            # (or this one re-raised) terminates immediately
            self.uninstall()
            signal.raise_signal(signum)
            return
        self.signame = signal.Signals(signum).name
        self._event.set()

    # -- queries / control ----------------------------------------------

    @property
    def triggered(self) -> bool:
        return self._event.is_set()

    def request(self, name: str = "request") -> None:
        """Trip the guard without a real signal (fault injection, tests,
        or an in-process watchdog)."""
        self.signame = self.signame or name
        self._event.set()

    def uninstall(self) -> None:
        """Restore the previous handlers (idempotent)."""
        prev, self._prev = self._prev, {}
        for s, h in prev.items():
            try:
                signal.signal(s, h)
            except (ValueError, TypeError):
                pass
        self.installed = False

    # -- context manager -------------------------------------------------

    def __enter__(self) -> "ShutdownGuard":
        return self

    def __exit__(self, *exc) -> None:
        self.uninstall()
