"""Multi-host initialization — the DCN leg of the communication backend.

The reference's entire "distributed backend" is a per-host subprocess farm
over ``multiprocessing.Pipe`` (``/root/reference/parallel_runner.py:21-32,
234-273``, SURVEY.md §5.8); it has no cross-host story at all. Here the
cross-chip path is XLA collectives over ICI (``parallel/mesh.py``), and this
module supplies the cross-HOST leg: one ``jax.distributed.initialize`` call
makes ``jax.devices()`` span every host, after which ``make_mesh`` lays the
data axis across hosts and NOTHING else changes — GSPMD routes collectives
ICI-first, DCN only across host boundaries.

Environment contract (standard JAX multi-process convention): the
coordinator address and process topology come either from explicit arguments
or from the scheduler environment (``JAX_COORDINATOR_ADDRESS``,
``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``). On a Cloud TPU pod, where
``jax.distributed.initialize()`` resolves the topology from pod metadata
without any of those variables, set ``T2OMCA_MULTIHOST=1`` to opt in — an
unconditional auto-detect would be wrong for the common single-host case.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional, Tuple

import jax

from ..utils import resilience
from ..utils.watchdog import retry_call

logger = logging.getLogger(__name__)

#: coordinator key-value namespace for the coordinated-preemption
#: protocol (docs/RESILIENCE.md §6)
_KV_PREFIX = "t2omca/preempt"


def maybe_initialize_distributed(
        coordinator_address: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
        retries: Optional[int] = None) -> bool:
    """Initialize the multi-host runtime when a topology is configured.

    Returns True when ``jax.distributed.initialize`` ran (or had already
    run), False when no multi-host topology is configured — single-host
    runs are unaffected. Idempotent: a second call is a no-op.

    The init is a rendezvous: every process races to the coordinator, and
    a transient loss (coordinator pod not yet scheduled, gloo transport
    handshake crashing — the ``EnforceNotMet`` flake CHANGES.md records at
    ~50% on oversubscribed CPU) used to kill the whole job at step zero.
    Transient-classified failures now retry with exponential backoff
    (``utils.watchdog.retry_call``). ``retries`` — from the argument or
    ``T2OMCA_INIT_RETRIES``, default 2 — counts retries BEYOND the first
    attempt (total attempts = 1 + retries), matching the
    ``resilience.dispatch_retries`` convention everywhere else; a
    non-numeric env value is ignored with a warning. Deterministic
    errors (bad topology arguments) still fail on the first attempt. The
    ``backend.init`` fault-injection point fires inside each attempt
    (docs/RESILIENCE.md §4).
    """
    addr = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc = num_processes if num_processes is not None else int(
        os.environ.get("JAX_NUM_PROCESSES", "0") or 0)
    pid = process_id if process_id is not None else int(
        os.environ.get("JAX_PROCESS_ID", "-1") or -1)

    pod_auto = os.environ.get("T2OMCA_MULTIHOST") == "1"
    if not addr and nproc <= 1 and not pod_auto:
        return False
    kwargs = {}
    if addr:
        kwargs["coordinator_address"] = addr
    if nproc > 0:
        kwargs["num_processes"] = nproc
    if pid >= 0:
        kwargs["process_id"] = pid
    if retries is None:
        raw = os.environ.get("T2OMCA_INIT_RETRIES", "")
        try:
            retries = int(raw) if raw else 2
        except ValueError:
            logging.getLogger("t2omca").warning(
                f"ignoring non-numeric T2OMCA_INIT_RETRIES={raw!r} "
                f"(using the default of 2 retries)")
            retries = 2
    # retries counts attempts BEYOND the first (resilience.dispatch_retries
    # convention): retries=2 -> 3 total attempts
    attempts = 1 + max(retries, 0)
    attempt_box = [0]

    def _reset_partial_init() -> None:
        # jax assigns global_state.service/.client BEFORE
        # client.connect() (jax/_src/distributed.py), so a failed
        # rendezvous leaves the runtime half-initialized and a bare
        # retry dies on the double-init RuntimeError instead of
        # re-attempting. Best-effort teardown so the next attempt
        # starts from a clean state; never let cleanup mask the
        # original (classifiable) error.
        try:
            jax.distributed.shutdown()
        except Exception:       # noqa: BLE001 — half-connected client
            try:
                from jax._src import distributed as _dist
                st = _dist.global_state
                st.client = None
                if st.service is not None:
                    try:
                        st.service.shutdown()
                    except Exception:   # noqa: BLE001
                        pass
                    st.service = None
            except Exception:   # noqa: BLE001 — jax internals moved
                pass

    def _init_once() -> bool:
        attempt_box[0] += 1
        resilience.fire("backend.init", attempt=attempt_box[0])
        try:
            jax.distributed.initialize(**kwargs)
        except Exception as e:
            # idempotency via the runtime's own double-init error (there
            # is no public already-initialized predicate to query; jax
            # phrases it "should only be called once") — but only
            # on the FIRST attempt, where it can only mean a previous
            # successful call. On a retry the same message means THIS
            # call's failed attempt left the runtime half-initialized
            # and _reset_partial_init could not clean it up; reporting
            # success would hand back a never-connected runtime that
            # wedges at the first collective.
            msg = str(e).lower()
            if ("already" in msg or "only be called once" in msg) \
                    and attempt_box[0] == 1:
                return True
            _reset_partial_init()
            raise
        return True

    return retry_call(_init_once, attempts=attempts,
                      label="jax.distributed.initialize")


# --------------------------------------------------------------------------
# Coordinated multi-host preemption (docs/RESILIENCE.md §6)
# --------------------------------------------------------------------------
#
# A SIGTERM lands on ONE host (the scheduler rarely signals a pod slice
# atomically), but the emergency checkpoint is a collective — every host
# must cut at the SAME t_env or the gathered save interleaves two
# different steps. The protocol runs over the coordinator's key-value
# store (the same service jax.distributed.initialize stood up — no new
# transport):
#
#   1. the signaled host ANNOUNCES (``announce_shutdown``) as soon as its
#      ShutdownGuard trips;
#   2. every host's driver loop polls ``peer_shutdown_requested`` (time-
#      throttled — one cheap KV scan per interval, never per step) and
#      trips its own guard when a peer announced, so the signal
#      propagates without any host-to-host signal delivery;
#   3. once triggered, every host calls ``negotiate_stop_step`` with its
#      current t_env: publish, meet at a BOUNDED barrier, take the max —
#      hosts behind the consensus keep stepping until they reach it, so
#      the collective emergency save runs in lockstep at one t_env.
#
# A dead peer fails the barrier inside ``timeout_s`` and the call
# degrades explicitly — ``ok=False`` tells the driver to skip every
# collective and write a per-host shard save instead
# (``utils.checkpoint.save_checkpoint_shards``), which cannot hang on
# the corpse.


def _kv_client():
    """The coordinator key-value/barrier client, or None when the
    distributed runtime is not initialized (single-host) or jax's
    internals moved. Private-API access is deliberately fenced here so
    every caller degrades instead of crashing."""
    try:
        from jax._src import distributed as _dist
        return _dist.global_state.client
    except Exception:               # noqa: BLE001 — jax internals moved
        return None


def announce_shutdown(t_env: int) -> None:
    """Publish this host's shutdown intent (+ its t_env at signal time)
    to the coordinator KV store — step 1 of the protocol. Best-effort
    and idempotent: a lost announce only costs propagation latency (the
    peer barrier still bounds the exit), never correctness."""
    if jax.process_count() <= 1:
        return
    client = _kv_client()
    if client is None:
        return
    try:
        client.key_value_set(
            f"{_KV_PREFIX}/announce/{jax.process_index()}",
            str(int(t_env)))
    except Exception as e:          # noqa: BLE001 — KV RPC is best-effort
        logger.warning("announce_shutdown: coordinator KV set failed "
                       "(%r) — peers will rely on their own signals", e)


_peer_poll_state = {"last": 0.0, "hit": False}


def peer_shutdown_requested(min_interval_s: float = 1.0) -> bool:
    """True once ANY peer announced a shutdown — step 2, the driver
    loop-top poll. Time-throttled to one KV scan per ``min_interval_s``
    (a KV RPC per train step would dominate small steps); a positive
    result latches, mirroring ShutdownGuard semantics. Single-host runs
    return False without touching the KV store."""
    if _peer_poll_state["hit"]:
        return True
    if jax.process_count() <= 1:
        return False
    now = time.monotonic()
    if now - _peer_poll_state["last"] < min_interval_s:
        return False
    _peer_poll_state["last"] = now
    client = _kv_client()
    if client is None:
        return False
    try:
        entries = client.key_value_dir_get(f"{_KV_PREFIX}/announce/")
    except Exception:               # noqa: BLE001 — empty dir / RPC loss
        return False
    me = str(jax.process_index())
    for item in entries or []:
        key = item[0] if isinstance(item, (tuple, list)) else item
        if str(key).rstrip("/").rsplit("/", 1)[-1] != me:
            _peer_poll_state["hit"] = True
            logger.warning(
                "peer_shutdown_requested: a peer announced preemption "
                "(%s) — tripping the local shutdown guard", key)
            return True
    return False


def negotiate_stop_step(t_env: int,
                        timeout_s: float = 10.0) -> Tuple[int, bool]:
    """Step 3: agree on the SINGLE t_env every host cuts its emergency
    checkpoint at. Returns ``(target, ok)``:

    * ``ok=True``: all hosts met the barrier; ``target`` is the max of
      the published steps — hosts behind it keep stepping until they
      reach it, then run the collective save in lockstep.
    * ``ok=False``: the barrier timed out or the KV store is gone (a
      peer died mid-preemption). ``target`` is the caller's own t_env
      and the driver must DEGRADE: skip every collective and write a
      per-host shard save (``save_checkpoint_shards``) instead.

    Single-host runs return ``(t_env, True)`` immediately. The
    ``preempt.barrier`` resilience hook fires inside the guarded region,
    so chaos tests inject a peer-timeout by raising here
    (docs/RESILIENCE.md §4)."""
    t = int(t_env)
    try:
        # fault-injection point (docs/RESILIENCE.md §4): the bounded
        # peer barrier — raising here simulates a peer dying
        # mid-negotiation and exercises the degraded shard-save path
        resilience.fire("preempt.barrier", t_env=t,
                        processes=jax.process_count())
        if jax.process_count() <= 1:
            return t, True
        client = _kv_client()
        if client is None:
            logger.warning(
                "negotiate_stop_step: multi-host run without a "
                "coordinator KV client — degrading to per-host save")
            return t, False
        pid = jax.process_index()
        client.key_value_set(f"{_KV_PREFIX}/step/{pid}", str(t))
        client.wait_at_barrier("t2omca_preempt_cut",
                               max(int(timeout_s * 1000), 1))
        entries = client.key_value_dir_get(f"{_KV_PREFIX}/step/") or []
        steps = []
        for item in entries:
            val = item[1] if isinstance(item, (tuple, list)) \
                and len(item) > 1 else item
            try:
                steps.append(int(val))
            except (TypeError, ValueError):
                continue
        if len(steps) < jax.process_count():
            logger.warning(
                "negotiate_stop_step: barrier passed but only %d/%d "
                "hosts published a step — degrading to per-host save",
                len(steps), jax.process_count())
            return t, False
        target = max(steps)
        logger.info("negotiate_stop_step: consensus cut at t_env=%d "
                    "(local %d, %d hosts)", target, t, len(steps))
        return target, True
    except Exception as e:          # noqa: BLE001 — timeout/dead peer
        logger.warning(
            "negotiate_stop_step: peer barrier failed (%r) — a peer is "
            "likely dead; degrading to per-host shard save", e)
        return t, False
