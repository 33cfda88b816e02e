"""The program's own compile and cache counters: one listener on
``jax.monitoring`` that books every trace, lowering, backend compilation
and persistent-cache retrieval where it happens.

``run.run`` installs one for the life of a run whose telemetry is on
(``obs.enabled``), bound to the run's :class:`~.spans.SpanRecorder`; each
event is added to the recorder's process-wide counters and to the
innermost span open ON THE THREAD THAT COMPILED
(``SpanRecorder.count``), so a completed span says how many programs it
compiled, for how long, and what the cache served
(``spans.COUNTER_FIELDS``). A backend compilation or cache retrieval of
:data:`MARK_SECS` or more also writes a ``compile`` mark naming the
program, its seconds, whether the cache served it and the span it fell
in — "which step recompiled" as one line of ``spans.jsonl``. With
telemetry off nothing is installed.

Without a recorder (``chip_smoke.py``) the listener only keeps its own
books: seconds by program name and the cache's hits.

How JAX 0.9 reports one executable (``jax/_src/compiler.py``,
``interpreters/pxla.py``), all on the compiling thread:
``backend_compile_duration`` spans ``compile_or_get_cached`` whole, with
the program's name; inside it a cache HIT fires ``cache_hits`` and then
``cache_retrieval_time_sec``, a MISS compiles and fires ``cache_misses``
if the entry is worth writing (JAX's thresholds: a second of compile).
So a hit's retrieval time arrives just before the duration that holds
it: the listener keeps it per thread and splits the duration into
``cache_load_ms`` and ``compile_ms``, which therefore add up.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, List

from .spans import COUNTER_FIELDS

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"

_MS_FIELD = {TRACE: "trace_ms", LOWER: "lower_ms"}

#: a backend compilation or cache retrieval this long gets a mark of its own
MARK_SECS = 0.5


class CompileListener:
    """See the module docstring. ``rec``: an enabled ``SpanRecorder`` or
    ``None``."""

    def __init__(self, rec=None) -> None:
        self.rec = rec
        self.seconds: Dict[str, List[float]] = {}   # program -> [secs, ...]
        self.cache_hits = 0
        self._lock = threading.Lock()
        # per thread: the hit and the retrieval seconds that belong to
        # the backend duration still to come
        self._pending = threading.local()

    def install(self) -> "CompileListener":
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(self.on_duration)
        monitoring.register_event_listener(self.on_event)
        if self.rec is not None:
            # from here on the ``run`` mark carries every counter, the
            # zeros too: that they are there says that something listened
            # (a process that has run the same shapes before compiles
            # nothing on the way to its loop)
            for name in COUNTER_FIELDS:
                self.rec.counters.setdefault(name, 0)
        return self

    def uninstall(self) -> None:
        import jax.monitoring as monitoring
        monitoring.unregister_event_duration_listener(self.on_duration)
        monitoring.unregister_event_listener(self.on_event)

    # -- jax.monitoring callbacks -----------------------------------------

    def on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT:
            self._pending.hit = True
            with self._lock:
                self.cache_hits += 1
            if self.rec is not None:
                self.rec.count(cache_hits=1)
        elif event == CACHE_MISS and self.rec is not None:
            self.rec.count(cache_misses=1)

    def on_duration(self, event: str, secs: float, **kw) -> None:
        if event == CACHE_LOAD:
            self._pending.load = secs
        elif event == BACKEND:
            self._backend(secs, str(kw.get("fun_name", "?")))
        elif event in _MS_FIELD and self.rec is not None:
            self.rec.count(**{_MS_FIELD[event]: secs * 1e3})

    def _backend(self, secs: float, fun_name: str) -> None:
        pending = self._pending
        hit = getattr(pending, "hit", False)
        load = min(getattr(pending, "load", 0.0), secs)
        pending.hit, pending.load = False, 0.0
        with self._lock:
            self.seconds.setdefault(fun_name, []).append(round(secs, 3))
        if self.rec is None:
            return
        phase = self.rec.count(compile_n=1, compile_ms=(secs - load) * 1e3,
                               cache_load_ms=load * 1e3)
        if secs >= MARK_SECS:
            self.rec.mark("compile", fun_name=fun_name, secs=round(secs, 3),
                          cache_hit=hit, phase=phase)

    # -- readings -----------------------------------------------------------

    def of(self, *names: str) -> dict:
        """{program: [seconds, ...]} of the named jitted functions."""
        with self._lock:
            return {n: list(self.seconds[f"jit({n})"]) for n in names
                    if f"jit({n})" in self.seconds}


@contextlib.contextmanager
def listening(rec):
    """A :class:`CompileListener` bound to ``rec`` for the body's
    duration; with a disabled recorder nothing is installed."""
    if not rec.enabled:
        yield None
        return
    listener = CompileListener(rec).install()
    try:
        yield listener
    finally:
        listener.uninstall()
