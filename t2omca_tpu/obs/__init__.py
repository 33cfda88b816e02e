"""graftscope — runtime observability for the training and serving stack.

Four pieces (docs/OBSERVABILITY.md):

* **span tracing** (``spans.py``) — a low-overhead host-side span
  recorder threaded through every device-facing boundary the watchdog
  stamps, emitting structured JSONL alongside the Logger sinks;
* **flight recorder** (``spans.py``) — a bounded ring of recent
  events persisted atomically on stall/crash/non-finite/SIGTERM and
  merged into the watchdog's ``stall_diagnosis.json``;
* **compile and cache counters** (``compiles.py``) — one listener on
  ``jax.monitoring``, installed by ``run.run`` with telemetry on, that
  books every compilation to the span it happens in and marks the long
  ones by program name;
* **report CLI** (``python -m t2omca_tpu.obs report <run_dir>``) —
  joins the runtime telemetry against graftprog's FLOPs/bytes budgets
  into a per-program breakdown of wall time per dispatch, and prints
  where set-up went, stage by stage.

Plus the **graftpulse live plane** (docs/OBSERVABILITY.md §pulse):
``pulse.py`` (Prometheus-text ``/metrics`` + ``/healthz`` + on-demand
``/trace`` behind ``obs.pulse_port``) and ``memwatch.py`` (phase-
attributed HBM high-water snapshots merged into the flight/stall
artifacts). Device time comes from the profiler's trace, read by
``benchmark/trace.py`` and ``benchmark/scopes.py``.

Names resolve lazily — importing ``t2omca_tpu.obs`` must stay cheap
enough for the jax-free report CLI.
"""

from __future__ import annotations

from .spans import (KNOWN_PHASES, NULL_RECORDER, NullRecorder,
                    SpanRecorder, make_recorder, stacked)

_LAZY = {
    "PHASE_PROGRAMS": "report",
    "report_main": "report",
    # graftpulse live telemetry plane (stdlib-only modules; lazy so the
    # jax-free CLIs pay nothing for what they don't use)
    "MetricsHub": "pulse",
    "PulseServer": "pulse",
    "TraceController": "pulse",
    "make_pulse": "pulse",
    "MemWatch": "memwatch",
    "make_memwatch": "memwatch",
    # graftsight learning-dynamics telemetry (stdlib+numpy at import;
    # the in-graph helpers pull jax lazily inside their bodies)
    "SightMonitor": "sight",
    "make_monitor": "sight",
    "learning_main": "sight",
}

__all__ = ["KNOWN_PHASES", "NULL_RECORDER", "NullRecorder",
           "SpanRecorder", "make_recorder", "stacked", *sorted(_LAZY)]


def __getattr__(name: str):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f".{_LAZY[name]}", __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
