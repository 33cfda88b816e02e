"""graftscope span tracing: the host-side runtime telemetry recorder.

The driver needs to know where a dispatch's wall-clock goes, and which
phase a stalled or crashed run was in and how long the phases before it
took. Podracer (arxiv 2104.06272) attributes its TPU
utilization wins to exactly this per-phase accounting. This module is
the host half of that story (device time per named scope is read from
the profiler's trace by ``benchmark/trace.py`` and
``benchmark/scopes.py``):

* :class:`SpanRecorder` — a low-overhead span recorder. The driver
  wraps every device-facing boundary it already stamps for the
  watchdog (``run.run_sequential`` ``_watched``/``_sync_point`` sites,
  the checkpoint save) in
  ``rec.span(phase, t_env=..., **meta)``; each completed span becomes
  one structured JSONL event in ``<run_dir>/spans.jsonl`` alongside the
  ``Logger`` sinks. Overhead is a couple of ``perf_counter`` calls, a
  dict build and a deque append per span (measured < 20 µs on the CI
  box — docs/OBSERVABILITY.md) — well under 1% of any steady-state
  iteration.
* **flight recorder** — the same recorder keeps a bounded in-memory
  ring of the last ``ring_size`` events plus every still-open span.
  ``tail()`` returns them completed-first, open-last (so the hanging
  span of a stalled dispatch is the LAST entry), and
  ``persist(path)`` writes the tail atomically (tmp + rename) — the
  driver calls it on stall, crash, non-finite trip and SIGTERM, and
  merges it into the watchdog's ``stall_diagnosis.json``.
* :class:`NullRecorder` — the default. Telemetry is opt-in
  (``config.ObsConfig.enabled``); with it off every ``span()`` returns
  a shared no-op context and the driver path is behaviorally identical
  to a build without this module.

Event schema (docs/OBSERVABILITY.md): every line is one JSON object.

``{"event": "span", "seq": N, "phase": str, "t_env": int, "t0":
<epoch s>, "wall_ms": float, "outcome": "ok" | "error:<Type>",
"depth": <nesting>, ["parent": <seq>,] ["first": true,]
[<counters>,] ...meta}``
    one completed span; ``parent`` is the ``seq`` of the span it is
    nested in on its own thread (left out at depth 0), so a phase's SELF
    time is its wall minus its children's; ``first`` marks the first
    completion of the phase (it includes the XLA compile — the
    watchdog's compile exemption made measurable, so compile-vs-stall
    is distinguishable post-mortem). ``<counters>`` are what
    :meth:`SpanRecorder.count` added while the span was the innermost
    one open on the counting thread — the compile listener's
    (``obs/compiles.py``, :data:`COUNTER_FIELDS`), each left out when
    zero. ``meta`` carries call-site context (``attempt``, ``k``, ...).
``{"event": "mark", "seq": N, "kind": str, "t0": <epoch s>, ...meta}``
    one point event (run header, ladder action, non-finite trip,
    shutdown, a long compilation). The ``kind == "run"`` mark is the run
    header the report CLI (``python -m t2omca_tpu.obs report``) uses to
    scale graftprog's audit-config FLOPs/bytes budgets to the run's
    shapes; it is also where set-up ends and the loop starts, and
    carries the process-wide counters so far.

Everything here is stdlib-only and jit-free — the report CLI and the
tests must not pay jax import/backend startup for it.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from ..utils.ioutil import write_json_atomic

#: The span phases the driver and the servers are allowed to use. graftlint rule
#: GL110 checks every ``_watched``/``_sync_point``/``_dispatch`` call
#: site with a literal phase against this set, so a NEW device-facing
#: boundary cannot silently appear without span (and therefore flight-
#: recorder) coverage. Keep in sync with the hook-point table in
#: ``utils/resilience.py`` and docs/RESILIENCE.md §5 — the phase names
#: ARE the fault-injection hook names where both exist.
KNOWN_PHASES = frozenset({
    # driver dispatch boundaries (run.py _dispatch via _watched)
    "dispatch.superstep", "dispatch.rollout", "dispatch.train",
    "dispatch.test",
    # driver sync/fetch boundaries (run.py _sync_point via _watched)
    "dispatch.wait", "fetch.train_infos", "fetch.train_stats",
    "fetch.test_stats",
    # the driver loop's own host work (run.run_sequential), so that no
    # stretch of an iteration is in no span: before the dispatch (gate
    # mirror, key splits), after it (mirror commit, info-row slicing),
    # and the log cadence after its fetch. WORKING spans: nothing in
    # them blocks on the device — the blocked ones are fetch.*,
    # dispatch.wait and checkpoint.save
    "driver.prepare", "driver.account", "driver.log",
    # sebulba decoupled-loop boundaries (run.run_sebulba,
    # parallel/sebulba.py): actor-mesh rollout dispatch, the trajectory
    # queue's two ends (put = actor-side d2d copy + slot scatter, its
    # wait is backpressure = actor idle; get = learner-side slot gather
    # + ring insert, its wait is starvation = learner idle), the
    # learner-mesh train dispatch, and the staleness-bounded
    # learner→actor parameter publish/adopt hop
    "actor.dispatch", "queue.put", "queue.get", "learner.dispatch",
    "params.sync",
    # checkpoint + startup boundaries. graftmorph (docs/RESILIENCE.md
    # §6) adds the elastic-resume routing boundary (checkpoint.elastic:
    # host read + topology reshape before placement), the coordinated-
    # preemption peer barrier (preempt.barrier: bounded KV-store
    # rendezvous agreeing on the cut step), and the degraded per-host
    # shard write (checkpoint.shard_save: the collective-free fallback
    # when a peer died mid-preemption)
    "checkpoint.save", "collective.gather", "backend.init",
    "checkpoint.elastic", "preempt.barrier", "checkpoint.shard_save",
    # set-up, stage by stage (run.run, run_sequential, run_sebulba), so
    # that no second between the recorder's creation and the ``run``
    # mark is in no span: backend.init is the backend's start and
    # nothing else; setup.build is Experiment.build; setup.telemetry
    # the pulse / memwatch / trace-trigger / sight objects;
    # setup.init_state whichever call makes the train state (or its
    # abstract template) and the driver's key; setup.restore checkpoint
    # discovery and load; setup.programs the jitted-program wrappers
    # (DP / population wrappers too) — building them traces nothing,
    # and the span's compile counters say so
    "setup.build", "setup.telemetry", "setup.init_state",
    "setup.restore", "setup.programs",
    # graftserve boundaries (serve/export.py, serve/frontend.py): the
    # exporter's lower/compile/export pass, artifact load, and the
    # three per-request front-end stages — `obs report` reads a
    # serving run's spans.jsonl exactly like a training run's
    "serve.export", "serve.load", "serve.pad", "serve.dispatch",
    "serve.unpad",
    # graftfleet multi-engine serving (serve/fleet.py): per-engine
    # artifact load, the supervised per-request dispatch envelope (the
    # watchdog-stamped boundary; serve.* spans nest inside it), the
    # engine health-check dispatch, a quarantined engine's restart
    # reload, and the rolling hot-param-refresh path (fold + roll
    # stages)
    "fleet.load", "fleet.dispatch", "fleet.selfcheck", "fleet.restart",
    "fleet.refresh",
    # graftpulse live telemetry plane (obs/pulse.py, obs/memwatch.py):
    # one /metrics-endpoint scrape, one per-device HBM snapshot, the
    # PULSE_TRACE-file / /trace-endpoint arming of a live trace window
    "pulse.scrape", "memwatch.snapshot", "trace.trigger",
    # graftsight (obs/sight.py): the host-side RL-health detector pass
    # over the log-cadence fetched train info — host-only (no device
    # traffic), spanned so a slow sink/detector shows up in the phase
    # tables instead of silently inflating the log cadence
    "sight.detect",
})

#: The device-side vocabulary: every literal the package passes to
#: ``jax.named_scope`` (tests/test_scopes.py scans the sources both ways).
#: A scope is opened where the work is written, so every program that
#: runs the work (classic, fused, population, data-parallel) carries the
#: same names. JAX wraps them in the operation's name stack
#: (``vmap(env.step)``, ``transpose(jvp(learner.agent))``,
#: ``checkpoint``/``rematted_computation``): a reader matches a scope as
#: a token inside those wrappers, takes the OUTERMOST token for a
#: layer's time and the innermost for a finer split (the ``agent.*``
#: children are the same under ``act.forward`` and ``learner.*``). The
#: benchmark's reader is ``benchmark/scopes.py``; docs/OBSERVABILITY.md
#: §8 has the table of where each is opened.
KNOWN_SCOPES = frozenset({
    # rollout (runners/parallel_runner.py, envs/, controllers/,
    # components/action_selectors.py)
    "rollout.reset", "env.obs", "env.step", "env.normalizer",
    "act.forward", "act.select", "rollout.store",
    # the model (models/, ops/query_slice.py), under act.forward and
    # under learner.agent / learner.mixer / learner.target alike
    "agent.embed", "agent.attention", "agent.ff", "agent.head",
    # a catalog trunk's layers (models/trunk.py): the router product,
    # its scores, selection bias, top-k, renormalisation and the held
    # experts' weights a token; the feed-forward's input norm, the routed
    # experts' products and the output norm; a shared expert's products;
    # a dense layer's feed-forward (not agent.ff: the mixer's block opens
    # that one too); inside agent.attention, what latent attention does
    # that grouped-query attention does not: the down-projection to the
    # latent and the shared rotary key, the latent's norm, the
    # up-projection to the held heads' no-position keys and values
    "agent.router", "agent.experts", "agent.shared", "agent.dense",
    "agent.latent",
    # replay ring (components/episode_buffer.py)
    "replay.insert", "replay.sample", "replay.priority",
    # learner (learners/qmix_learner.py)
    "learner.agent", "learner.mixer", "learner.target", "learner.loss",
    "learner.optimizer",
    # in-graph telemetry reduces (obs/sight.py)
    "sight",
})

#: The counters a span (and the ``run`` mark) may carry: what the compile
#: listener (``obs/compiles.py``) hands :meth:`SpanRecorder.count`. Counts
#: are integers, ``*_ms`` sums of milliseconds; ``compile_ms`` is backend
#: compilation proper and ``cache_load_ms`` the persistent cache's
#: retrievals, so the two add up to the time spent getting executables.
COUNTER_FIELDS = ("compile_n", "compile_ms", "trace_ms", "lower_ms",
                  "cache_hits", "cache_misses", "cache_load_ms")

_MS_FIELDS = tuple(n for n in COUNTER_FIELDS if n.endswith("_ms"))

_NOOP = contextlib.nullcontext()


class _Span:
    """Stamp/record pair (plain class with slots, same reasoning as
    ``watchdog._Watch``: contextmanager generators hold frames other
    threads would race, and allocation cost is the overhead budget)."""

    __slots__ = ("_rec", "_ev", "_pc0")

    def __init__(self, rec: "SpanRecorder", ev: Dict[str, Any]):
        self._rec = rec
        self._ev = ev
        self._pc0 = 0.0

    def __enter__(self) -> None:
        self._pc0 = self._rec._begin(self._ev)

    def __exit__(self, exc_type, *exc) -> None:
        self._rec._end(self._ev, self._pc0, exc_type)


class _Stacked:
    """Enter ``outer`` then ``inner``; exit in reverse. The driver pairs
    the watchdog stamp (outer — it must cover the span bookkeeping too)
    with the span record (inner) without paying an ExitStack."""

    __slots__ = ("_outer", "_inner", "_entered")

    def __init__(self, outer, inner):
        self._outer, self._inner = outer, inner
        self._entered = False

    def __enter__(self):
        self._outer.__enter__()
        try:
            self._inner.__enter__()
            self._entered = True
        except BaseException:
            self._outer.__exit__(None, None, None)
            raise
        return None

    def __exit__(self, *exc) -> None:
        try:
            if self._entered:
                self._inner.__exit__(*exc)
        finally:
            self._outer.__exit__(*exc)


def stacked(outer, inner) -> _Stacked:
    return _Stacked(outer, inner)


class SpanRecorder:
    """Span + event recorder with a bounded flight ring and an optional
    JSONL sink. Thread-safe: the watchdog/stall threads may record
    marks while the main thread holds open spans."""

    enabled = True

    def __init__(self, ring_size: int = 256,
                 jsonl_path: Optional[str] = None,
                 flush_every: int = 32,
                 annotate: Optional[Callable[[str], Any]] = None) -> None:
        self.ring_size = max(int(ring_size), 1)
        self.jsonl_path = jsonl_path
        self.flush_every = max(int(flush_every), 1)
        # ``annotate(phase)`` → a context manager entered and exited with
        # every span (LIFO, on the span's own thread). The driver injects
        # ``jax.profiler.TraceAnnotation`` so each span is also a host
        # event of the same name in the profiler's trace, on the
        # profiler's clock; injected, never imported: this module stays
        # stdlib-only
        self._annotate = annotate
        self._open_ann: Dict[int, Any] = {}  # seq -> entered annotation
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.ring_size)
        self._open: Dict[int, Dict[str, Any]] = {}   # seq -> open span event
        self._open_pc: Dict[int, float] = {}         # seq -> perf_counter at begin
        self._seq = 0
        self._first_pending: set = set()             # phases never completed
        # per thread: the spans it has open, outermost first — a span's
        # depth and parent, and the span a count() on that thread goes to
        self._tl = threading.local()
        # process-wide sums of everything count() was handed
        self.counters: Dict[str, float] = {}
        self._file = None
        self._unflushed = 0
        # per-phase aggregation for summary() — O(1) per span, no event
        # replay (the ring may have evicted early spans)
        self._agg: Dict[str, Dict[str, float]] = {}

    # -- recording -------------------------------------------------------

    def span(self, phase: str, t_env: int = 0, _ring: bool = True,
             **meta) -> _Span:
        """Context manager recording one span. ``meta`` must be
        JSON-serializable scalars (attempt counts, K, ...).
        ``_ring=False`` keeps the completed span OUT of the flight ring
        (it still lands in the JSONL sink and the per-phase aggregate):
        for high-frequency decorative spans — the pulse endpoint's
        per-scrape spans — which would otherwise evict the pre-stall
        phase history the ring exists to preserve (a 5 s scrape cadence
        fills a 256-slot ring in ~21 min, shorter than one
        compile-scale hang)."""
        ev: Dict[str, Any] = {"event": "span", "phase": phase,
                              "t_env": int(t_env)}
        if not _ring:
            ev["_ring"] = False
        if meta:
            ev.update(meta)
        return _Span(self, ev)

    def _begin(self, ev: Dict[str, Any]) -> float:
        stack = getattr(self._tl, "stack", None)
        if stack is None:
            stack = self._tl.stack = []
        ev["depth"] = len(stack)
        if stack:
            ev["parent"] = stack[-1]["seq"]
        ann = None
        if self._annotate is not None:
            # outermost: the annotation covers the bookkeeping too
            ann = self._annotate(ev["phase"])
            ann.__enter__()
        ev["t0"] = time.time()
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            self._open[ev["seq"]] = ev
            if ann is not None:
                self._open_ann[ev["seq"]] = ann
            pc0 = time.perf_counter()
            self._open_pc[ev["seq"]] = pc0
        stack.append(ev)
        return pc0

    def _end(self, ev: Dict[str, Any], pc0: float, exc_type) -> None:
        wall_ms = (time.perf_counter() - pc0) * 1000.0
        self._tl.stack.pop()        # spans close LIFO on their own thread
        phase = ev["phase"]
        with self._lock:
            # ev is still registered in _open until the pop below, and
            # tail() (called from the watchdog stall thread) copies
            # open-span dicts under this lock — inserting the
            # completion keys outside it would race that copy
            ev["wall_ms"] = round(wall_ms, 3)
            ev["outcome"] = ("ok" if exc_type is None
                             else f"error:{exc_type.__name__}")
            for name in _MS_FIELDS:
                if name in ev:
                    ev[name] = round(ev[name], 3)
            self._open.pop(ev["seq"], None)
            self._open_pc.pop(ev["seq"], None)
            ann = self._open_ann.pop(ev["seq"], None)
            a = self._agg.get(phase)
            if a is None:
                a = self._agg[phase] = {"n": 0, "total_ms": 0.0,
                                        "max_ms": 0.0, "first_ms": -1.0}
            a["n"] += 1
            a["total_ms"] += wall_ms
            a["max_ms"] = max(a["max_ms"], wall_ms)
            if exc_type is None and a["first_ms"] < 0:
                # first CLEAN completion = the compile-inclusive
                # occurrence (matches the watchdog's compile exemption:
                # an exception is not a completion)
                a["first_ms"] = wall_ms
                ev["first"] = True
            if ev.pop("_ring", True):
                self._ring.append(ev)
            self._sink(ev)
        if ann is not None:
            ann.__exit__(exc_type, None, None)

    def mark(self, kind: str, **meta) -> None:
        """Record one point event (run header, ladder action, ...)."""
        ev: Dict[str, Any] = {"event": "mark", "kind": kind,
                              "t0": round(time.time(), 3)}
        if meta:
            ev.update(meta)
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            self._ring.append(ev)
            self._sink(ev)

    def count(self, **amounts) -> Optional[str]:
        """Add ``amounts`` (:data:`COUNTER_FIELDS`) to the process-wide
        counters and to the innermost span open on the CALLING thread —
        work is counted where it happens, and a span of another thread
        never sees it. → that span's phase, ``None`` outside every span.
        Zero amounts are dropped, so a field a span never counted is
        left out of its event."""
        stack = getattr(self._tl, "stack", None)
        ev = stack[-1] if stack else None
        with self._lock:            # tail() copies open spans under it
            for name, amount in amounts.items():
                if amount:
                    self.counters[name] = self.counters.get(name, 0) + amount
                    if ev is not None:
                        ev[name] = ev.get(name, 0) + amount
        return ev["phase"] if ev is not None else None

    def totals(self) -> Dict[str, float]:
        """The process-wide counters so far (the ``run`` mark's; a
        listener puts every field there when it is installed, so the
        mark of a run that compiled nothing carries zeros)."""
        with self._lock:
            return {name: round(v, 3) if isinstance(v, float) else v
                    for name, v in self.counters.items()}

    # -- sink ------------------------------------------------------------

    def _sink(self, ev: Dict[str, Any]) -> None:
        """Append one event line (lock held). Best-effort: telemetry
        must never be the thing that crashes the run."""
        if self.jsonl_path is None:
            return
        try:
            # default=repr: a non-JSON meta value (numpy scalar, pytree
            # leaf) degrades to its repr instead of a TypeError out of
            # the hot-loop span bookkeeping
            line = json.dumps(ev, default=repr)
        except (TypeError, ValueError):     # circular refs etc.
            return                          # drop the event, keep the sink
        try:
            if self._file is None:
                os.makedirs(os.path.dirname(self.jsonl_path) or ".",
                            exist_ok=True)
                self._file = open(self.jsonl_path, "a")
            self._file.write(line + "\n")
            self._unflushed += 1
            if self._unflushed >= self.flush_every:
                self._file.flush()
                self._unflushed = 0
        except OSError:
            self.jsonl_path = None          # disk trouble: stop trying

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.flush()
                    self._file.close()
                except OSError:
                    pass
                self._file = None

    # -- flight recorder -------------------------------------------------

    def tail(self) -> List[Dict[str, Any]]:
        """Flight-recorder tail: the last ``ring_size`` completed
        events in completion order, then every still-open span (start
        order) marked ``"open": true`` with its wall so far — so a
        stalled dispatch's hanging span is always the LAST entry."""
        now = time.perf_counter()
        with self._lock:
            out = [dict(ev) for ev in self._ring]
            for seq in sorted(self._open):
                ev = dict(self._open[seq])
                ev.pop("_ring", None)   # internal flag, not schema
                ev["open"] = True
                ev["wall_ms"] = round(
                    (now - self._open_pc[seq]) * 1000.0, 3)
                out.append(ev)
        return out

    def persist(self, path: str,
                extra: Optional[Dict[str, Any]] = None) -> Optional[str]:
        """Atomically write the flight tail as JSON (tmp + rename).
        Best-effort; returns the path or None. ``extra`` is merged into
        the payload next to the events — the driver passes the HBM
        memwatch report (obs/memwatch.py) so an OOM/wedge flight dump
        says what held device memory."""
        try:
            # default=repr lives in the helper, same reason as _sink:
            # the flight dump runs on crash/stall paths where raising
            # is worst-case
            payload: Dict[str, Any] = {"version": 1, "events": self.tail()}
            if extra:
                payload.update(extra)
            return write_json_atomic(path, payload)
        except (OSError, TypeError, ValueError):
            return None

    # -- aggregation -----------------------------------------------------

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-phase aggregate: ``{phase: {n, total_ms, max_ms,
        first_ms, steady_ms}}``. ``first_ms`` is the compile-inclusive
        first clean completion (-1 when none completed cleanly);
        ``steady_ms`` is the mean over the rest (the warm rate)."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            for phase, a in self._agg.items():
                rest_n = a["n"] - (1 if a["first_ms"] >= 0 else 0)
                rest_total = a["total_ms"] - max(a["first_ms"], 0.0)
                out[phase] = {
                    "n": a["n"],
                    "total_ms": round(a["total_ms"], 3),
                    "max_ms": round(a["max_ms"], 3),
                    "first_ms": round(a["first_ms"], 3),
                    "steady_ms": (round(rest_total / rest_n, 3)
                                  if rest_n > 0 else -1.0),
                }
        return out


class NullRecorder:
    """The disabled-telemetry recorder: every operation is a no-op and
    ``span()`` returns one shared ``nullcontext`` — the driver hot loop
    pays a truthiness check and nothing else."""

    enabled = False
    jsonl_path = None

    def span(self, phase: str, t_env: int = 0, **meta):
        return _NOOP

    def mark(self, kind: str, **meta) -> None:
        pass

    def tail(self) -> List[Dict[str, Any]]:
        return []

    def persist(self, path: str, extra=None) -> Optional[str]:
        return None

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {}

    def close(self) -> None:
        pass


#: shared disabled recorder (stateless — safe to share process-wide)
NULL_RECORDER = NullRecorder()


def make_recorder(obs_cfg, run_dir: Optional[str] = None,
                  annotate: Optional[Callable[[str], Any]] = None):
    """Recorder for a run: :data:`NULL_RECORDER` unless
    ``obs_cfg.enabled``; the JSONL sink lands in
    ``<run_dir>/spans.jsonl`` when a run directory is given.
    ``annotate`` as in :class:`SpanRecorder`."""
    if obs_cfg is None or not getattr(obs_cfg, "enabled", False):
        return NULL_RECORDER
    path = (os.path.join(run_dir, "spans.jsonl")
            if run_dir else None)
    return SpanRecorder(ring_size=obs_cfg.ring_size, jsonl_path=path,
                        flush_every=obs_cfg.flush_every, annotate=annotate)
